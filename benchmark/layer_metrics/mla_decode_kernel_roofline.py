"""Kernels: the latent decode read alone. Bytes its live rows take
(``flops_latent_moe.mla_decode_kernel_bytes`` at the window's mean live
rows, a layer a step) over the peak bytes/s, as a share of the device time
of ``mla_paged_attention_decode``, summed over all its sites in the trace
(``trace_kernels``) and divided by the traced decode steps and the layers.
Memory-bound by its bytes; its products (32 heads against every row, 1,088
columns) are a quarter of the MXU's peak away from binding."""
from benchmark import flops_latent_moe

KERNEL = "mla_paged_attention_decode"


def read(ctx):
    secs = (getattr(ctx, "kernel_seconds", None) or {}).get(KERNEL)
    mod = ctx.trace_summary.get("modules", {}).get(
        ctx.window.get("decode_module"))
    if not secs or not secs[1] or not mod or not mod[0] or ctx.peaks is None:
        return None
    steps = mod[0] * ctx.window["decode_block"]
    per_call = secs[1] / (steps * ctx.config["num_hidden_layers"])
    need = flops_latent_moe.mla_decode_kernel_bytes(
        ctx.config, ctx.window["kv_live_tokens_mean"])
    return need / ctx.peaks["hbm_bytes_per_s"] / per_call * 100.0
