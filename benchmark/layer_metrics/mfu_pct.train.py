"""Kernels + model step, training: ``train_tokens_per_s`` times the
operations a token requires (``flops.train_flops_per_token``: no recompute,
no embedding gather, causal attention) over the chip's bf16 peak."""


def read(ctx):
    rate = ctx.e2e.get("train_tokens_per_s")
    if rate is None or ctx.peaks is None:
        return None
    need = ctx.flops.train_flops_per_token(ctx.config, ctx.window["seq_len"])
    return rate * need / (ctx.entry["chips"] * ctx.peaks["bf16_flops_per_s"]) \
        * 100.0
