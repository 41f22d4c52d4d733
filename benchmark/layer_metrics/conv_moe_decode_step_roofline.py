"""Kernels: the least time one decode step of a model with gated short-conv
layers beside grouped-query attention layers and all its routed experts
held could take on this chip's HBM, as a share of the measured
``decode_step_ms``. Bytes from ``flops_conv_moe.decode_step_bytes``: the
weights outside the routed experts and the head, the experts that the
window's steps actually hit (the program's ``moe_expert_hits`` per step),
the live rows of the attention layers (the engine's ``kv_rows_live`` per
step) and the conv state the decoding slots read and write (the engine's
``decode_tokens`` per step). These are REQUIRED bytes: the routing moves
this share as well as the kernels do."""
from benchmark import flops_conv_moe as f
from benchmark.common import load_module


def read(ctx):
    step_ms = load_module("layer_metrics", "decode_step_ms.py").read(ctx)
    hits = ctx.window.get("moe_expert_hits_per_step")
    rows = ctx.window.get("kv_rows_per_step")
    slots = ctx.window.get("live_slots_per_step")
    if step_ms is None or hits is None or rows is None or slots is None \
            or ctx.peaks is None or "conv_L_cache" not in ctx.config:
        return None
    need = f.decode_step_bytes(ctx.config, rows, hits, slots)
    return need / ctx.peaks["hbm_bytes_per_s"] / (step_ms / 1e3) * 100.0
