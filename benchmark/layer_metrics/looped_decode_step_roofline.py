"""Kernels: the least time one decode step of a LOOPED model could take on
this chip's HBM, as a share of the measured ``decode_step_ms``. Bytes from
``flops_looped.decode_step_bytes`` at the window's mean live KV: the
stack's weights once a pass (four times a step), the head and the gate
once, the live keys and values of all ``total_ut_steps x
num_hidden_layers`` cache layers. Memory-bound: at 8 rows a step the
matmuls are far under the FLOP roof."""
from benchmark import flops_looped
from benchmark.common import load_module


def read(ctx):
    step_ms = load_module("layer_metrics", "decode_step_ms.py").read(ctx)
    if step_ms is None or ctx.peaks is None \
            or "ut_steps_per_decode_step" not in ctx.window:
        return None
    need = flops_looped.decode_step_bytes(
        ctx.config, ctx.window["kv_live_tokens_mean"])
    return need / ctx.peaks["hbm_bytes_per_s"] / (step_ms / 1e3) * 100.0
