"""Kernels: the selected latent read alone. Bytes the selected rows take
(``flops_sparse_latent_moe.sparse_read_bytes`` at the window's mean of the
program's ``dsa_tokens_selected``, a full layer a step: 1,152 B a row) over
the peak bytes/s, as a share of the device time of EVERYTHING under the
scope ``dsa_read`` (``trace_scopes``): XLA's gather of the selected rows
through the table and the Pallas call ``dsa_sparse_mla_decode`` over them,
divided by the traced decode steps and the full layers. The gather writes
the rows and the kernel reads them again, so the share cannot pass a
third."""
from benchmark import flops_sparse_latent_moe as f
from benchmark.common import load_module

SCOPE = "dsa_read"


def read(ctx):
    per_call = load_module(
        "layer_metrics", "dsa_index_kernel_roofline.py").per_call_seconds(
        ctx, (getattr(ctx, "scope_seconds", None) or {}).get(SCOPE), f.FULL)
    if per_call is None or "dsa_selected_rows_per_step" not in ctx.window:
        return None
    need = f.sparse_read_bytes(ctx.config,
                               ctx.window["dsa_selected_rows_per_step"])
    return need / ctx.peaks["hbm_bytes_per_s"] / per_call * 100.0
