"""Bytes a Laguna-class decode step REQUIRES, from the configuration's
shapes and the program's counters.

The twin of ``flops_hybrid_moe.py`` (which reads MiMo's keys: one
``num_attention_heads``, a sink, no gate, no shared expert) for a model
whose window and full layers have their own numbers of query heads
(``num_attention_heads_per_layer``), a per-head gate on every attention
output, one shared expert beside the routed ones, and a share of the
routed experts held on this chip. Nothing here is measured: these are the
numerators of the roofline shares. Bytes are REQUIRED bytes: K's and V's
128 values a token a kv head (4,096 B a token a layer at 8 kv heads, the
arena's row as stored), the rows a read must see (a sliding layer: at most
``sliding_window`` a slot, whatever pages the walk copies), the experts
that received a token (not the experts held, which a decode step reads
whatever the routing).
"""
from __future__ import annotations

from .flops import BYTES

FULL, WINDOW = 0, 1
_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}


def layer_kinds(c: dict):
    """(attention kind, expert layer?) of each layer of the depth that runs."""
    n = c["num_hidden_layers"]
    return [(_KINDS[t], int(m == "sparse")) for t, m in
            zip(c["layer_types"][:n], c["mlp_layer_types"][:n])]


def heads(c: dict, layer: int) -> int:
    return c["num_attention_heads_per_layer"][layer]


def attention_params(c: dict, layer: int) -> int:
    """q, k, v, o and the per-head gate ``(hidden, H_l)``."""
    h, d, kvh, n = (c["hidden_size"], c["head_dim"],
                    c["num_key_value_heads"], heads(c, layer))
    return 2 * h * n * d + 2 * h * kvh * d + h * n


def router_width(c: dict) -> int:
    return c.get("num_experts_published", c["num_experts"])


def experts_held(c: dict) -> int:
    held = c.get("experts_held")
    return held[1] if held else c["num_experts"]


def expert_params(c: dict) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["shared_expert_intermediate_size"]


def layer_fixed_params(c: dict, layer: int) -> int:
    """A layer outside its routed experts: attention, the two norms, and
    the router with its (zero) selection bias and the shared expert
    (expert layers) or the dense SwiGLU."""
    h = c["hidden_size"]
    _, moe = layer_kinds(c)[layer]
    rest = h * router_width(c) + router_width(c) + shared_params(c) if moe \
        else 3 * h * c["intermediate_size"]
    return attention_params(c, layer) + 2 * h + rest


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"] + c["hidden_size"]


def fixed_params(c: dict) -> int:
    """Everything a decode step reads whatever the routing: all layers
    outside their routed experts, the final norm and the head."""
    return sum(layer_fixed_params(c, i) for i in range(len(layer_kinds(c)))) \
        + head_params(c)


def moe_layers(c: dict) -> int:
    return sum(m for _, m in layer_kinds(c))


def total_params(c: dict) -> int:
    return (fixed_params(c) + moe_layers(c) * experts_held(c)
            * expert_params(c) + c["hidden_size"] * c["vocab_size"])


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """What a layer's cache REQUIRES a token: K and V of every kv head."""
    return c["num_key_value_heads"] * 2 * c["head_dim"] * BYTES[dtype]


def layers_of(c: dict, kind: int) -> int:
    return sum(1 for k, _ in layer_kinds(c) if k == kind)


def full_decode_kernel_bytes(c: dict, rows: float,
                             dtype: str = "bfloat16") -> float:
    """Bytes the decode read of ONE full layer must move in one step:
    ``rows`` live tokens, summed over the slots."""
    return float(rows * kv_bytes_per_token(c, dtype))


def swa_decode_kernel_bytes(c: dict, window_rows: float,
                            dtype: str = "bfloat16") -> float:
    """Bytes the decode read of ONE sliding layer must move in one step:
    ``window_rows`` = the sum over the slots of min(live tokens, window)."""
    return float(window_rows * kv_bytes_per_token(c, dtype))


def expert_bytes(c: dict, expert_hits: float,
                 dtype: str = "bfloat16") -> float:
    """Bytes of the routed experts that received a token, ``expert_hits``
    summed over the expert layers: 18.87 MB an expert."""
    return float(expert_hits * expert_params(c) * BYTES[dtype])


def decode_step_bytes(c: dict, rows: float, window_rows: float,
                      expert_hits: float, dtype: str = "bfloat16") -> float:
    """Bytes one decode step (one token for every slot) must read from
    HBM: the weights outside the routed experts (the shared experts, the
    routers, the gates, the dense layer) and the head's, once; the routed
    experts held that received a token; the live rows of every full layer
    and the rows inside the window of every sliding layer. The embedding
    rows, activations and the rows written are left out (under 0.1%), so
    the share errs low, never high."""
    weights = fixed_params(c) * BYTES[dtype] + expert_bytes(c, expert_hits,
                                                            dtype)
    cache = layers_of(c, FULL) * full_decode_kernel_bytes(c, rows, dtype) \
        + layers_of(c, WINDOW) * swa_decode_kernel_bytes(c, window_rows,
                                                          dtype)
    return float(weights + cache)
