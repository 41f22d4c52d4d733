"""Bytes an LFM2-MoE-class decode step REQUIRES, from the configuration's
shapes and the program's counters.

A model of gated short-conv layers beside grouped-query attention layers
(``layer_types``: ``conv`` / ``full_attention``), leading dense SwiGLU
layers (``num_dense_layers``) and then routed experts, all held. Nothing
here is measured: these are the numerators of the roofline shares. Bytes
are REQUIRED bytes: K's and V's ``head_dim`` values a token a kv head (2,048
B a token a layer at 8 kv heads of 64, the arena's row as stored), the
rows a read must see (every live token of a decoding slot), the conv
state's entries a decoding slot reads and writes, the experts that
received a token (not every expert held, which the program may read
whatever the routing).
"""
from __future__ import annotations

from .flops import BYTES

CONV, ATTN = "conv", "full_attention"


def layer_types(c: dict):
    return list(c["layer_types"][:c["num_hidden_layers"]])


def layers_of(c: dict, kind: str) -> int:
    return layer_types(c).count(kind)


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def conv_params(c: dict) -> int:
    """One conv mixer: ``in_proj`` (hidden -> 3 hidden), the depthwise
    taps, ``out_proj``."""
    h = c["hidden_size"]
    return 3 * h * h + c["conv_L_cache"] * h + h * h


def attention_params(c: dict) -> int:
    """q, k, v, o and the two norms of a head's width."""
    h, d, kvh = c["hidden_size"], head_dim(c), c["num_key_value_heads"]
    return 2 * h * h + 2 * h * kvh * d + 2 * d


def expert_params(c: dict) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    """The router and its selection bias."""
    return c["hidden_size"] * c["num_experts"] + c["num_experts"]


def moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def fixed_params(c: dict) -> int:
    """Everything a decode step reads whatever the routing: the mixers,
    the two norms a layer, the dense SwiGLUs, the routers, the final norm
    and the head (not the embedding, of which a step reads a row a slot)."""
    h = c["hidden_size"]
    mixers = layers_of(c, CONV) * conv_params(c) \
        + layers_of(c, ATTN) * attention_params(c)
    dense = c["num_dense_layers"] * 3 * h * c["intermediate_size"]
    return mixers + c["num_hidden_layers"] * 2 * h + dense \
        + moe_layers(c) * router_params(c) + h * c["vocab_size"] + h


def total_params(c: dict) -> int:
    return fixed_params(c) + moe_layers(c) * c["num_experts"] \
        * expert_params(c) + c["hidden_size"] * c["vocab_size"]


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """What one attention layer's cache REQUIRES a token: K and V of every
    kv head."""
    return c["num_key_value_heads"] * 2 * head_dim(c) * BYTES[dtype]


def conv_state_bytes(c: dict, dtype: str = "bfloat16") -> int:
    """One block's (or one slot's) conv state: the last ``L - 1`` values
    of ``u`` of every conv layer (57,344 B at 7 layers of 2,048)."""
    return layers_of(c, CONV) * (c["conv_L_cache"] - 1) * c["hidden_size"] \
        * BYTES[dtype]


def expert_bytes(c: dict, expert_hits: float,
                 dtype: str = "bfloat16") -> float:
    """Bytes of the routed experts that received a token, ``expert_hits``
    summed over the expert layers: 18.87 MB an expert."""
    return float(expert_hits * expert_params(c) * BYTES[dtype])


def short_conv_decode_bytes(c: dict, slots: float,
                            dtype: str = "bfloat16") -> float:
    """Bytes the conv mixers of one decode step must move: every conv
    layer's projections and taps once, and each of ``slots`` decoding
    slots' conv state read from one block and written to another."""
    return float(layers_of(c, CONV) * conv_params(c) * BYTES[dtype]
                 + 2 * slots * conv_state_bytes(c, dtype))


def decode_step_bytes(c: dict, rows: float, expert_hits: float,
                      slots: float, dtype: str = "bfloat16") -> float:
    """Bytes one decode step (one token for every slot) must read from
    HBM: the weights outside the routed experts, once; the routed experts
    that received a token (``expert_hits`` over the expert layers); the
    ``rows`` live tokens of every attention layer; the conv state of the
    ``slots`` decoding slots, read and written. The embedding rows,
    activations and the keys and values written are left out (under 0.1%),
    so the share errs low, never high."""
    weights = fixed_params(c) * BYTES[dtype] + expert_bytes(c, expert_hits,
                                                            dtype)
    cache = layers_of(c, ATTN) * rows * kv_bytes_per_token(c, dtype) \
        + 2 * slots * conv_state_bytes(c, dtype)
    return float(weights + cache)
