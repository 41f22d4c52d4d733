"""Kernels: the least time one decode step of a model with learned sparse
attention over a latent cache, windowed latent layers and routed experts
could take on this chip's HBM, as a share of the measured
``decode_step_ms``. Bytes from ``flops_sparse_latent_moe.decode_step_bytes``
at the window's mean live context: every parameter but the embedding (every
expert held is read every step), in each full layer every live token's
256 B index key and the selected latent rows (the program's
``dsa_tokens_selected`` per step), in each sliding layer the rows inside
the window (the engine's ``window_kv_rows_live`` per step). The selection
itself (a sort) reads no required bytes, so its time only lowers this."""
from benchmark import flops_sparse_latent_moe as f
from benchmark.common import load_module


def read(ctx):
    step_ms = load_module("layer_metrics", "decode_step_ms.py").read(ctx)
    w = ctx.window
    if step_ms is None or ctx.peaks is None \
            or "dsa_selected_rows_per_step" not in w:
        return None
    need = f.decode_step_bytes(
        ctx.config, w["kv_rows_per_step"], w["slots"],
        w["dsa_selected_rows_per_step"], w["window_kv_rows_per_step"])
    return need / ctx.peaks["hbm_bytes_per_s"] / (step_ms / 1e3) * 100.0
