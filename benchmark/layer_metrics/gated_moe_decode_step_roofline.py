"""Kernels: the least time one decode step of a model with sliding and full
attention layers of their own head counts, a per-head gate, a shared
expert and a share of the routed experts could take on this chip's HBM, as
a share of the measured ``decode_step_ms``. Bytes from
``flops_gated_hybrid_moe.decode_step_bytes``: the weights outside the
routed experts and the head, the experts held that the window's steps
actually hit (the program's ``moe_expert_hits`` per step), the live rows
of the full layers and the rows inside the window of the sliding layers
(the engine's ``kv_rows_live`` / ``window_kv_rows_live`` per step). These
are REQUIRED bytes: the program's decode step reads every expert HELD
(``moe.FEW_ROWS``), and the experts held and not hit are not required, so
the routing moves this share as well as the kernels do."""
from benchmark import flops_gated_hybrid_moe as f
from benchmark.common import load_module


def read(ctx):
    step_ms = load_module("layer_metrics", "decode_step_ms.py").read(ctx)
    hits = ctx.window.get("moe_expert_hits_per_step")
    rows = ctx.window.get("kv_rows_per_step")
    if step_ms is None or hits is None or rows is None or ctx.peaks is None \
            or "num_attention_heads_per_layer" not in ctx.config:
        return None
    need = f.decode_step_bytes(ctx.config, rows,
                               ctx.window["window_kv_rows_per_step"], hits)
    return need / ctx.peaks["hbm_bytes_per_s"] / (step_ms / 1e3) * 100.0
