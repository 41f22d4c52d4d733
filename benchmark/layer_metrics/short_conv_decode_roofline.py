"""Kernels: the gated short-conv mixers alone in a decode step. Bytes
(``flops_conv_moe.short_conv_decode_bytes``: every conv layer's in and out
projections and taps once, 235 MB at 7 layers of 2,048, and each decoding
slot's conv state read from one block and written to another, 57,344 B
each way) over the peak bytes/s, as a share of the device time of
EVERYTHING under the scope ``short_conv`` (``trace_scopes``: the
projections, the taps, the gates and the state's gather and scatter, not
the norm before the mixer) in the traced decode blocks, per decode step.
It reads the same work whether XLA or a kernel implements it."""
from benchmark import flops_conv_moe as f

SCOPE = "short_conv"


def read(ctx):
    secs = (getattr(ctx, "scope_seconds", None) or {}).get(SCOPE)
    mod = ctx.trace_summary.get("modules", {}).get(
        ctx.window.get("decode_module"))
    slots = ctx.window.get("live_slots_per_step")
    if not secs or not secs[1] or not mod or not mod[0] or slots is None \
            or ctx.peaks is None or "conv_L_cache" not in ctx.config:
        return None
    per_step = secs[1] / (mod[0] * ctx.window["decode_block"])
    return f.short_conv_decode_bytes(ctx.config, slots) \
        / ctx.peaks["hbm_bytes_per_s"] / per_step * 100.0
