"""Kernels: ONE call of the paged decode read in a looped model (16 KV
heads, one query head a KV head, 8 slots of 9-40 pages): the bytes the live
keys and values of one (layer, pass) take
(``flops_looped.decode_kernel_bytes`` at the window's mean live KV) over
the peak bytes/s, as a share of the device time of a call of
``paged_attention_decode``: summed over all its sites in the trace
(``trace_kernels``) and divided by the traced decode steps x the cache
layers (``total_ut_steps x num_hidden_layers`` calls a step). Required
bytes, not copied pages: a slot's last page is copied whole."""
from benchmark import flops_looped

KERNEL = "paged_attention_decode"


def read(ctx):
    secs = (getattr(ctx, "kernel_seconds", None) or {}).get(KERNEL)
    mod = ctx.trace_summary.get("modules", {}).get(
        ctx.window.get("decode_module"))
    if not secs or not secs[1] or not mod or not mod[0] \
            or ctx.peaks is None \
            or "ut_steps_per_decode_step" not in ctx.window:
        return None
    calls = mod[0] * ctx.window["decode_block"] \
        * flops_looped.cache_layers(ctx.config)
    need = flops_looped.decode_kernel_bytes(
        ctx.config, ctx.window["kv_live_tokens_mean"])
    return need / ctx.peaks["hbm_bytes_per_s"] / (secs[1] / calls) * 100.0
