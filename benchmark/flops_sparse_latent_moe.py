"""Operations and bytes a dots3-note-class step REQUIRES, from the
configuration's shapes and the program's counters.

The twin of ``flops_hybrid_moe.py`` for a model whose full layers read a
latent cache through a learned indexer (every live token's index key, then
only the selected latent rows) and whose sliding layers read a second,
wider latent cache under a window, with routed experts of which this chip
holds a share and one shared expert. Nothing here is measured: these are
the numerators of the roofline shares. Bytes are REQUIRED bytes, whatever
implements the read: an index key's 128 values, a latent row's 576 (the
arena stores 640) or 1,088 (stores 1,152).
"""
from __future__ import annotations

from .flops import BYTES

FULL, WINDOW = 0, 1
_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}


def layer_kinds(c: dict):
    """(attention kind, expert layer?) of each layer of the depth that runs."""
    n = c["num_hidden_layers"]
    return [(_KINDS[t], int(i >= c["first_k_dense_replace"]))
            for i, t in enumerate(c["layer_types"][:n])]


def layers_of(c: dict, kind: int) -> int:
    return sum(1 for k, _ in layer_kinds(c) if k == kind)


def _sizes(c: dict, kind: int):
    p = "swa_" if kind == WINDOW else ""
    return (c[p + "num_attention_heads"], c[p + "q_lora_rank"],
            c[p + "kv_lora_rank"], c[p + "qk_nope_head_dim"],
            c[p + "qk_rope_head_dim"], c[p + "v_head_dim"])


def attention_params(c: dict, kind: int) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o, the headwise gate, the two rank
    norms; a full layer's indexer (W^I_q, W^I_k, W^I_w, LayerNorm)."""
    h = c["hidden_size"]
    heads, rq, rkv, dn, dr, dv = _sizes(c, kind)
    n = (h * rq + rq * heads * (dn + dr) + h * (rkv + dr)
         + rkv * heads * (dn + dv) + heads * dv * h + h * heads + rq + rkv)
    if kind == FULL:
        ih, idim = c["index_n_heads"], c["index_head_dim"]
        n += rq * ih * idim + h * idim + h * ih + 2 * idim
    return n


def router_width(c: dict) -> int:
    return c.get("n_routed_experts_published", c["n_routed_experts"])


def experts_held(c: dict) -> int:
    held = c.get("experts_held")
    return held[1] if held else c["n_routed_experts"]


def expert_params(c: dict) -> int:
    """One routed expert (or the shared one): three SwiGLU matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c: dict, kind: int, moe: int) -> int:
    """A whole layer as this chip holds it: attention, the two norms, and
    the dense SwiGLU, or the router with its selection bias, the shared
    experts and the routed experts HELD."""
    h = c["hidden_size"]
    if not moe:
        rest = 3 * h * c["intermediate_size"]
    else:
        rest = h * router_width(c) + router_width(c) + expert_params(c) * (
            c["n_shared_experts"] + experts_held(c))
    return attention_params(c, kind) + 2 * h + rest


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"] + c["hidden_size"]


def total_params(c: dict) -> int:
    return sum(layer_params(c, k, m) for k, m in layer_kinds(c)) \
        + head_params(c) + c["hidden_size"] * c["vocab_size"]


def step_weight_params(c: dict) -> int:
    """What a decode step reads whatever the routing: every parameter but
    the embedding, every expert held (``DroplessMoE``'s form for a share at
    few rows reads them all, as ``flops_hybrid_moe`` counts them)."""
    return total_params(c) - c["hidden_size"] * c["vocab_size"]


def index_key_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return c["index_head_dim"] * BYTES[dtype]


def latent_bytes_per_token(c: dict, kind: int,
                           dtype: str = "bfloat16") -> int:
    """What a layer's latent cache REQUIRES a token: c_kv and the shared
    k_r (the arena stores the row padded to whole lane tiles)."""
    _, _, rkv, _, dr, _ = _sizes(c, kind)
    return (rkv + dr) * BYTES[dtype]


def index_kernel_bytes(c: dict, live_tokens: float,
                       dtype: str = "bfloat16") -> float:
    """Bytes ONE full layer's indexer must move in one step: every live
    token's index key, summed over the slots."""
    return float(live_tokens * index_key_bytes(c, dtype))


def sparse_read_bytes(c: dict, selected_rows: float,
                      dtype: str = "bfloat16") -> float:
    """Bytes ONE full layer's selected read must move in one step:
    ``selected_rows`` = the sum over the slots of min(context, index_topk)
    latent rows."""
    return float(selected_rows * latent_bytes_per_token(c, FULL, dtype))


def window_read_bytes(c: dict, window_rows: float,
                      dtype: str = "bfloat16") -> float:
    """Bytes ONE sliding layer's read must move in one step:
    ``window_rows`` = the sum over the slots of min(context, window)."""
    return float(window_rows * latent_bytes_per_token(c, WINDOW, dtype))


def decode_step_bytes(c: dict, live_tokens: float, slots: float,
                      selected_rows: float = None, window_rows: float = None,
                      dtype: str = "bfloat16") -> float:
    """Bytes one decode step (one token for every slot) must read from
    HBM: every parameter but the embedding, once; in each full layer every
    live token's index key and the selected latent rows; in each sliding
    layer the rows inside the window. ``selected_rows`` / ``window_rows``
    default to ``slots x min(mean context, index_topk | window)``. The
    embedding rows, activations, the scores and the rows written are left
    out, so the share errs low, never high."""
    context = live_tokens / max(slots, 1)
    if selected_rows is None:
        selected_rows = slots * min(context, c["index_topk"])
    if window_rows is None:
        window_rows = slots * min(context, c["sliding_window_size"])
    cache = layers_of(c, FULL) * (
        index_kernel_bytes(c, live_tokens, dtype)
        + sparse_read_bytes(c, selected_rows, dtype)) \
        + layers_of(c, WINDOW) * window_read_bytes(c, window_rows, dtype)
    return float(step_weight_params(c) * BYTES[dtype] + cache)
