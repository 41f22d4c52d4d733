"""Kernels: the least time one decode step of a latent-cache, routed-expert
model could take on this chip's HBM, as a share of the measured
``decode_step_ms``. Bytes from ``flops_latent_moe.decode_step_bytes``: the
weights outside the routed experts and the head, the experts the window's
steps actually hit (the program's ``moe_expert_hits`` per step), the
window's mean live latent rows. Memory-bound: at 64 rows a step the
matmuls are far under the FLOP roof."""
from benchmark import flops_latent_moe
from benchmark.common import load_module


def read(ctx):
    step_ms = load_module("layer_metrics", "decode_step_ms.py").read(ctx)
    hits = ctx.window.get("moe_expert_hits_per_step")
    if step_ms is None or hits is None or ctx.peaks is None:
        return None
    need = flops_latent_moe.decode_step_bytes(
        ctx.config, ctx.window["kv_live_tokens_mean"], hits)
    return need / ctx.peaks["hbm_bytes_per_s"] / (step_ms / 1e3) * 100.0
