"""Kernels: the least time one decode step could take on this chip's HBM
(bytes it must read, ``flops.decode_step_bytes`` at the window's mean live
KV, over the peak bytes/s) as a share of the measured ``decode_step_ms``.
Memory-bound: at 32 rows a step the matmuls are far under the FLOP roof."""
from benchmark.common import load_module


def read(ctx):
    step_ms = load_module("layer_metrics", "decode_step_ms.py").read(ctx)
    if step_ms is None or ctx.peaks is None:
        return None
    need = ctx.flops.decode_step_bytes(
        ctx.config, ctx.window["kv_live_tokens_mean"])
    return need / ctx.peaks["hbm_bytes_per_s"] / (step_ms / 1e3) * 100.0
