"""Operations and bytes a DeepSeek-V3-class step REQUIRES, from the
configuration's shapes and the program's routing counters.

The twin of ``flops.py`` for a model with a latent cache and routed experts.
Nothing here is measured: these are the numerators of the roofline shares.
What a decode step must read depends on the routing (an expert nobody chose
is not read), so the bytes take ``expert_hits``, the program's own counter
``moe_expert_hits`` per step, summed over the expert layers.
"""
from __future__ import annotations

from .flops import BYTES


def attention_params(c: dict) -> int:
    """q (direct or through q_lora), kv_a (+ its norm), kv_b, o."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    rank = c["kv_lora_rank"]
    q = h * heads * qk if c.get("q_lora_rank") is None else \
        h * c["q_lora_rank"] + c["q_lora_rank"] \
        + c["q_lora_rank"] * heads * qk
    return (q + h * (rank + c["qk_rope_head_dim"]) + rank
            + rank * heads * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + heads * c["v_head_dim"] * h)


def expert_params(c: dict) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_layer_fixed_params(c: dict) -> int:
    """An expert layer outside its routed experts: attention, the two
    norms, the router (and its selection bias) and the shared experts."""
    h, e = c["hidden_size"], c["n_routed_experts"]
    return (attention_params(c) + 2 * h + h * e + e
            + 3 * h * c["moe_intermediate_size"] * c["n_shared_experts"])


def dense_layer_params(c: dict) -> int:
    h = c["hidden_size"]
    return attention_params(c) + 2 * h + 3 * h * c["intermediate_size"]


def layers(c: dict):
    """(dense layers, expert layers) of the depth that runs."""
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def experts_held(c: dict) -> int:
    held = c.get("experts_held")
    return held[1] if held else c["n_routed_experts"]


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"] + c["hidden_size"]


def total_params(c: dict) -> int:
    dense, moe = layers(c)
    return (dense * dense_layer_params(c)
            + moe * (moe_layer_fixed_params(c)
                     + experts_held(c) * expert_params(c))
            + head_params(c) + c["hidden_size"] * c["vocab_size"])


def latent_bytes_per_token_per_layer(c: dict, dtype: str = "bfloat16") -> int:
    """What the cache REQUIRES a token a layer: c_kv and the shared k_pe
    (the arena stores the row padded to whole lane tiles)."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BYTES[dtype]


def decode_step_bytes(c: dict, live_tokens: float, expert_hits: float,
                      dtype: str = "bfloat16") -> float:
    """Bytes one decode step (one token for every slot) must read from
    HBM: every layer's weights outside the routed experts and the head's,
    once; the routed experts that received a token (``expert_hits``, summed
    over the expert layers); the live latent rows of every layer. The
    embedding rows, activations and the rows written are left out (under
    0.1%), so the share errs low, never high."""
    dense, moe = layers(c)
    weights = (dense * dense_layer_params(c)
               + moe * moe_layer_fixed_params(c) + head_params(c)
               + expert_hits * expert_params(c)) * BYTES[dtype]
    cache = live_tokens * latent_bytes_per_token_per_layer(c, dtype) \
        * c["num_hidden_layers"]
    return float(weights + cache)


def decode_step_flops(c: dict, slots: int, live_tokens: float) -> float:
    """Operations of one decode step: two per weight a token meets (top-k
    experts, not all), and the absorbed read's two products over the live
    rows (rank + rope wide for the scores, rank for the values)."""
    dense, moe = layers(c)
    per_token = (dense * dense_layer_params(c)
                 + moe * (moe_layer_fixed_params(c)
                          + c["num_experts_per_tok"] * expert_params(c))
                 + head_params(c))
    read = c["num_attention_heads"] * (2 * c["kv_lora_rank"]
                                       + c["qk_rope_head_dim"])
    return 2.0 * slots * per_token \
        + 2.0 * live_tokens * read * c["num_hidden_layers"]


def mla_decode_kernel_bytes(c: dict, live_tokens: float,
                            dtype: str = "bfloat16") -> float:
    """Bytes the latent decode read of ONE layer must move in one step:
    the live rows (q and the output are under 0.1% of them)."""
    return float(live_tokens * latent_bytes_per_token_per_layer(c, dtype))
