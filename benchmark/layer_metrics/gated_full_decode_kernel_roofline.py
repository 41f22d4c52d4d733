"""Kernels: the full layers' decode read alone, in the cell of
``gated_swa_decode_kernel_roofline``. Bytes the live rows take
(``flops_gated_hybrid_moe.full_decode_kernel_bytes`` at the window's mean
of the engine's ``kv_rows_live``, a layer a step: live rows x 4,096 B) over
the peak bytes/s, as a share of the device time of
``paged_attention_decode``, summed over all its sites in the trace and
divided by the traced decode steps and the full layers."""
from benchmark import flops_gated_hybrid_moe as f
from benchmark.common import load_module

KERNEL = "paged_attention_decode"


def read(ctx):
    per_call = load_module(
        "layer_metrics", "gated_swa_decode_kernel_roofline.py"
    ).per_call_seconds(
        ctx, (getattr(ctx, "kernel_seconds", None) or {}).get(KERNEL), f.FULL)
    if per_call is None:
        return None
    need = f.full_decode_kernel_bytes(ctx.config,
                                      ctx.window["kv_rows_per_step"])
    return need / ctx.peaks["hbm_bytes_per_s"] / per_call * 100.0
