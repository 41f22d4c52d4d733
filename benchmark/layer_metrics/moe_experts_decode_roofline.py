"""Kernels: the routed expert layer alone in a decode step. Bytes of the
experts held that received a token (``flops_gated_hybrid_moe.expert_bytes``
at the program's ``moe_expert_hits`` per step, summed over the expert
layers: 18.87 MB an expert) over the peak bytes/s, as a share of the device
time of EVERYTHING under the scope ``moe_experts`` (``trace_scopes``: the
routed mix, not the router and not the shared expert) in the traced decode
blocks, per decode step. The program's step reads every expert HELD
(``moe.FEW_ROWS``), so a step that hits fewer than all of them reads more
than these required bytes: the share is then the routing's as much as the
layer's."""
from benchmark import flops_gated_hybrid_moe as f

SCOPE = "moe_experts"


def read(ctx):
    secs = (getattr(ctx, "scope_seconds", None) or {}).get(SCOPE)
    mod = ctx.trace_summary.get("modules", {}).get(
        ctx.window.get("decode_module"))
    hits = ctx.window.get("moe_expert_hits_per_step")
    if not secs or not secs[1] or not mod or not mod[0] or hits is None \
            or ctx.peaks is None \
            or "num_attention_heads_per_layer" not in ctx.config:
        return None
    per_step = secs[1] / (mod[0] * ctx.window["decode_block"])
    return f.expert_bytes(ctx.config, hits) / ctx.peaks["hbm_bytes_per_s"] \
        / per_step * 100.0
