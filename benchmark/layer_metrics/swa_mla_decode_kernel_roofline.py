"""Kernels: the sliding layers' latent decode read alone. Bytes the rows
inside the window take (``flops_sparse_latent_moe.window_read_bytes`` at the
window's mean of the engine's ``window_kv_rows_live``, a layer a step:
min(live, 513) rows a slot x 2,176 B) over the peak bytes/s, as a share of
the device time of ``swa_mla_paged_attention_decode``, summed over all its
sites in the trace (``trace_kernels``) and divided by the traced decode
steps and the sliding layers. Required bytes, not copied pages: a window
that starts inside a page costs the walk that whole page, and the stored
row is 1,152 wide for 1,088 required."""
from benchmark import flops_sparse_latent_moe as f
from benchmark.common import load_module

KERNEL = "swa_mla_paged_attention_decode"


def read(ctx):
    per_call = load_module(
        "layer_metrics", "dsa_index_kernel_roofline.py").per_call_seconds(
        ctx, (getattr(ctx, "kernel_seconds", None) or {}).get(KERNEL),
        f.WINDOW)
    if per_call is None:
        return None
    need = f.window_read_bytes(ctx.config,
                               ctx.window["window_kv_rows_per_step"])
    return need / ctx.peaks["hbm_bytes_per_s"] / per_call * 100.0
