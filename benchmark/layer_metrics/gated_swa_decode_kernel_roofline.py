"""Kernels: the sliding layers' decode read alone, in a cell whose layers
have their own numbers of query heads and whose packed rows are stored as
required (``[v 128 | k 128]``, 8 kv heads). Bytes the rows inside the
window take (``flops_gated_hybrid_moe.swa_decode_kernel_bytes`` at the
window's mean of the engine's ``window_kv_rows_live``, a layer a step:
min(live, 512) rows a slot x 4,096 B) over the peak bytes/s, as a share of
the device time of ``swa_paged_attention_decode``, summed over all its
sites in the trace (``trace_kernels``) and divided by the traced decode
steps and the sliding layers. Required bytes, not copied pages: a window
that starts inside a page costs the walk that whole page."""
from benchmark import flops_gated_hybrid_moe as f

KERNEL = "swa_paged_attention_decode"


def per_call_seconds(ctx, seconds, kind):
    """Device seconds a call: ``seconds = (events, total)`` over the traced
    decode steps and the layers of ``kind``; None where the trace, the
    counters or the configuration's per-layer heads are missing."""
    mod = ctx.trace_summary.get("modules", {}).get(
        ctx.window.get("decode_module"))
    if not seconds or not seconds[1] or not mod or not mod[0] \
            or ctx.peaks is None or "kv_rows_per_step" not in ctx.window \
            or "num_attention_heads_per_layer" not in ctx.config:
        return None
    steps = mod[0] * ctx.window["decode_block"]
    return seconds[1] / (steps * f.layers_of(ctx.config, kind))


def read(ctx):
    per_call = per_call_seconds(
        ctx, (getattr(ctx, "kernel_seconds", None) or {}).get(KERNEL),
        f.WINDOW)
    if per_call is None:
        return None
    need = f.swa_decode_kernel_bytes(ctx.config,
                                     ctx.window["window_kv_rows_per_step"])
    return need / ctx.peaks["hbm_bytes_per_s"] / per_call * 100.0
