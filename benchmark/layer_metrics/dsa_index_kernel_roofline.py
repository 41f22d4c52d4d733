"""Kernels: the indexer's decode walk alone. Bytes every live token's index
key takes (``flops_sparse_latent_moe.index_kernel_bytes`` at the window's
mean of the engine's ``kv_rows_live``, a full layer a step: 256 B a token)
over the peak bytes/s, as a share of the device time of
``dsa_index_scores_decode``, summed over all its sites in the trace
(``trace_kernels``) and divided by the traced decode steps and the full
layers."""
from benchmark import flops_sparse_latent_moe as f

KERNEL = "dsa_index_scores_decode"


def per_call_seconds(ctx, seconds, kind):
    """Device seconds a call: ``seconds = (events, total)`` over the traced
    decode steps and the layers of ``kind``."""
    mod = ctx.trace_summary.get("modules", {}).get(
        ctx.window.get("decode_module"))
    if not seconds or not seconds[1] or not mod or not mod[0] \
            or ctx.peaks is None or "kv_rows_per_step" not in ctx.window \
            or "layer_types" not in ctx.config:
        return None
    steps = mod[0] * ctx.window["decode_block"]
    return seconds[1] / (steps * f.layers_of(ctx.config, kind))


def read(ctx):
    per_call = per_call_seconds(
        ctx, (getattr(ctx, "kernel_seconds", None) or {}).get(KERNEL), f.FULL)
    if per_call is None:
        return None
    need = f.index_kernel_bytes(ctx.config, ctx.window["kv_rows_per_step"])
    return need / ctx.peaks["hbm_bytes_per_s"] / per_call * 100.0
