"""Operations and bytes a LOOPED model's step REQUIRES, from the
configuration's shapes (``model_type: ouro``: one stack of sandwich-norm
layers run ``total_ut_steps`` times over the same weights, a key/value
cache for every pass).

``flops.decode_step_bytes`` reads every weight once and
``num_hidden_layers`` cache layers; a looped step reads the stack's weights
once a PASS (8 rows a step keep nothing of 4.9 GB on the chip between
passes) and ``total_ut_steps x num_hidden_layers`` cache layers. Nothing
here is measured: these are the numerators of the roofline shares.
"""
from __future__ import annotations

from .flops import (BYTES, head_dim, head_params,
                    kv_bytes_per_token_per_layer)


def passes(c: dict) -> int:
    return int(c["total_ut_steps"])


def layer_params(c: dict) -> int:
    """One sandwich layer: q, o (h x h), k, v (h x kv), the three SwiGLU
    matrices (h x ff) and the FOUR RMSNorm weights."""
    h, ff = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * head_dim(c)
    kv = c["num_key_value_heads"] * head_dim(c)
    return 2 * h * q + 2 * h * kv + 3 * h * ff + 4 * h


def gate_params(c: dict) -> int:
    """The exit gate: Linear(hidden, 1) with its bias."""
    return c["hidden_size"] + 1


def total_params(c: dict) -> int:
    """Layers, embedding, untied head with the final norm, the gate."""
    return (c["num_hidden_layers"] * layer_params(c) + head_params(c)
            + c["hidden_size"] * c["vocab_size"] + gate_params(c))


def cache_layers(c: dict) -> int:
    return passes(c) * c["num_hidden_layers"]


def decode_step_bytes(c: dict, live_kv_tokens: float,
                      weight_dtype: str = "bfloat16",
                      kv_dtype: str = "bfloat16") -> float:
    """Bytes one decode step (one token for every slot) must read from HBM:
    every layer's weights once a pass, the head (with the final norm) and
    the gate once, and the live keys and values of every (layer, pass). The
    embedding rows, activations and the KV written are left out, so the
    share errs low, never high."""
    weights = (passes(c) * c["num_hidden_layers"] * layer_params(c)
               + head_params(c) + gate_params(c)) * BYTES[weight_dtype]
    kv = live_kv_tokens * kv_bytes_per_token_per_layer(c, kv_dtype) \
        * cache_layers(c)
    return float(weights + kv)


def decode_kernel_bytes(c: dict, live_kv_tokens: float,
                        kv_dtype: str = "bfloat16") -> float:
    """Bytes ONE call of the paged decode read must see: the live keys and
    values of one (layer, pass)."""
    return float(live_kv_tokens
                 * kv_bytes_per_token_per_layer(c, kv_dtype))
