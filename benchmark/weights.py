"""Random weights from ``--seed``, made ON THE DEVICE in the dtype they run in.

The program's eager initializer samples every parameter on the host in
float64 numpy and ships it over (``nn.initializer._draw``: 37 s per 1e9
parameters on the chip machine's host, PR 21). Two routes around it, both
through the model's OWN initializers (so the distributions are the
program's):

``build_lazy``     under ``paddle.LazyGuard`` no initializer runs; then ONE
                   jitted call threads a key through every parameter's
                   deferred initializer (a traced key makes ``_draw`` take its
                   ``jax.random`` branch) and the results are set as values.
                   For models built layer by layer (serving).
``build_stacked``  the stacked trunk (``scan_layers=True``) reads every
                   layer's value while it builds, so laziness cannot survive
                   construction; for the duration of the build ``_draw``'s
                   device sampler is made the eager path (one small program
                   per distinct parameter shape, cached after the first run).
"""
from __future__ import annotations

import contextlib

import jax

from .common import fold_seed


def llama_config(c: dict, **extra):
    """The configuration file's sizes as a ``LlamaConfig`` (the one model
    class both configurations run through)."""
    from paddle_tpu.models.llama import LlamaConfig
    heads = c["num_attention_heads"]
    if c.get("head_dim") and c["head_dim"] * heads != c["hidden_size"]:
        raise ValueError("models/llama.py derives head_dim as hidden/heads; "
                         f"{c['head_dim']} x {heads} != {c['hidden_size']}")
    return LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_hidden_layers=c["num_hidden_layers"],
        num_attention_heads=heads,
        num_key_value_heads=c["num_key_value_heads"],
        max_position_embeddings=c["max_position_embeddings"],
        rms_norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        tie_word_embeddings=bool(c.get("tie_word_embeddings", False)),
        sliding_window=c.get("sliding_window"),
        tensor_parallel=False, dtype=c["torch_dtype"], **extra)


@contextlib.contextmanager
def _default_dtype(dtype: str):
    import paddle_tpu as paddle
    paddle.set_default_dtype(dtype)
    try:
        yield
    finally:
        paddle.set_default_dtype("float32")


def build_lazy(cfg, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu import framework
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.tensor import LazyParameter
    with _default_dtype(cfg.dtype), paddle.LazyGuard():
        model = LlamaForCausalLM(cfg)
    params = [p for _, p in model.named_parameters()]
    if not all(isinstance(p, LazyParameter) and not p.materialized()
               for p in params):
        raise RuntimeError("a parameter was initialised on the host during "
                           "the lazy build")
    specs = [p._lazy_init for p in params]

    def make(key):
        with framework.rng_context(key):
            return [init(shape, dtype) for init, shape, dtype in specs]

    values = jax.jit(make)(jax.random.PRNGKey(fold_seed(seed)))
    for p, v in zip(params, values):
        p._value = v
    return model


@contextlib.contextmanager
def _device_draw():
    from paddle_tpu import framework
    from paddle_tpu.nn import initializer as I
    host_draw = I._draw
    I._draw = lambda shape, dtype, host_fn, jax_fn: jax_fn(
        framework.split_key())
    try:
        yield
    finally:
        I._draw = host_draw


def build_stacked(cfg, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    paddle.seed(fold_seed(seed))
    with _default_dtype(cfg.dtype), _device_draw():
        return LlamaForCausalLM(cfg)
