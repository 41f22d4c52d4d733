"""Model step: device time under the scope ``dsa_select`` (the exact
top-``index_topk`` over every slot's scores, both full layers) per decode
step, from the trace (``trace_scopes``: the selection is no named kernel
but whatever XLA makes of ``lax.top_k``). A sort has no roofline here; this
is the number a later kernel for the selection is judged by."""

SCOPE = "dsa_select"


def read(ctx):
    secs = (getattr(ctx, "scope_seconds", None) or {}).get(SCOPE)
    mod = ctx.trace_summary.get("modules", {}).get(
        ctx.window.get("decode_module"))
    if not secs or not secs[1] or not mod or not mod[0]:
        return None
    return secs[1] / (mod[0] * ctx.window["decode_block"]) * 1e3
