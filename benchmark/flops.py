"""Operations and bytes a step REQUIRES, from the configuration's shapes.

The benchmark's own arithmetic (``LlamaForCausalLM.flops_per_token`` counts
the embedding table as a matmul and attention as non-causal; this does
neither). Nothing here is measured: these are the numerators of the MFU and
roofline shares, and a CPU run may print them.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def layer_params(c: dict) -> int:
    """Parameters of one decoder layer: q, o (h x h), k, v (h x kv), the
    three SwiGLU matrices (h x ff) and the two RMSNorm weights."""
    h, ff = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * head_dim(c)
    kv = c["num_key_value_heads"] * head_dim(c)
    return 2 * h * q + 2 * h * kv + 3 * h * ff + 2 * h


def head_params(c: dict) -> int:
    """The output head (h x vocab) and the final norm."""
    return c["hidden_size"] * c["vocab_size"] + c["hidden_size"]


def embedding_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def total_params(c: dict) -> int:
    tied = bool(c.get("tie_word_embeddings"))
    return (c["num_hidden_layers"] * layer_params(c) + head_params(c)
            + (0 if tied else embedding_params(c)))


def kv_bytes_per_token_per_layer(c: dict, kv_dtype: str = "bfloat16") -> int:
    return 2 * c["num_key_value_heads"] * head_dim(c) * BYTES[kv_dtype]


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward + backward operations one trained token requires: 6 per
    matmul parameter (the embedding is a gather: none), plus causal
    attention, 2*s*q forward (QK^T and PV over half the square) and twice
    that backward. Recomputed operations do not count."""
    q = c["num_attention_heads"] * head_dim(c)
    matmul = c["num_hidden_layers"] * (layer_params(c) - 2 * c["hidden_size"]) \
        + c["hidden_size"] * c["vocab_size"]
    attn = c["num_hidden_layers"] * 6 * seq_len * q
    return 6.0 * matmul + attn


def decode_step_bytes(c: dict, live_kv_tokens: float,
                      weight_dtype: str = "bfloat16",
                      kv_dtype: str = "bfloat16") -> float:
    """Bytes one decode step (one token for every slot) must read from HBM:
    every layer's and the head's weights once, and the live keys and values.
    The embedding rows, activations and the KV written are left out (under
    0.1% at these sizes), so the share errs low, never high."""
    weights = (c["num_hidden_layers"] * layer_params(c) + head_params(c)) \
        * BYTES[weight_dtype]
    kv = live_kv_tokens * kv_bytes_per_token_per_layer(c, kv_dtype) \
        * c["num_hidden_layers"]
    return float(weights + kv)
