"""LFM2-MoE-class decoder: gated short-convolution layers beside grouped-query
attention layers with a norm on every query and key head, and dropless
routed experts chosen by sigmoid scores plus a selection-only bias.

The published block (``model_type: lfm2_moe``), pre-norm residual, RMSNorm
(``norm_eps``) before each half, no bias on any projection:
``h = x + mixer_l(RMSNorm(x))``, ``out = h + FFN_l(RMSNorm(h))``; a final
RMSNorm (the published ``embedding_norm``) before the untied head.
``layer_types[l]`` says ``conv`` or ``full_attention``.

- Short conv: ``[B | C | x~] = y W_in`` (hidden -> 3 hidden); ``u_t = B_t
  x~_t``; ``z_t = sum_k w_k u_{t - L + 1 + k}`` over the ``L =
  conv_L_cache`` taps, depthwise (one filter a channel) and causal, ``u``
  before position 0 taken as 0; ``out = (C_t z_t) W_out``. The decode state
  of a layer is the last ``L - 1`` values of ``u``.
- Attention: ``q = y W_q -> (H, d)``, ``k``, ``v = y W_k``, ``y W_v -> (kvh,
  d)``, ``d = hidden / H``; ``q <- RMSNorm_d(q)``, ``k <- RMSNorm_d(k)`` a
  head at a time (``q_layernorm``, ``k_layernorm``), then RoPE (rotate-half
  over all d at ``rope_theta``); causal ``softmax(q . k / sqrt(d)) v``;
  ``out = concat(o) W_o``.
- FFN: the first ``num_dense_layers`` layers a SwiGLU of
  ``intermediate_size``; the rest ``DroplessMoE``: ``s = sigmoid(y W_g)``
  in float32, the picks the top ``num_experts_per_tok`` of ``s +
  expert_bias`` (the layer's ``e_score_correction_bias``: the choice only),
  the weights ``s`` at the picks normalised over them and times
  ``routed_scaling_factor``; ``y = sum_e w_e SwiGLU_e(y)``, no shared
  expert.

Served, the paged cache keeps two kinds of state under ONE block table:

- a packed arena ``(blocks, block_size * kvh, 128)`` per attention layer, a
  row ``[v 64 | k 64]`` a token a kv head (one whole lane tile: nothing
  padded), read by ``models.generation.packed_cached_attention``;
- ONE conv-state leaf ``(blocks, conv layers, L - 1, hidden)``: block j's
  entry holds every conv layer's state after the last position written in
  block j. Position p reads the entry of block ``(p - 1) // block_size`` and
  writes that of block ``p // block_size``; a prefill chunk reads the state
  before its first position and writes each block it fills with that
  block's end state, its last, partial block with the state at its last
  valid column (``valid_len``); columns past it write nothing. A prefix hit
  at a block boundary so starts from the exact state at the cut, with
  nothing recomputed; the trash block 0 takes idle slots' writes, as in the
  arenas.

The counters leaf (``cache_counters``) is last, as in
``models.deepseek_v3``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..incubate.distributed.models.moe import DroplessMoE
from ..nn import functional as F
from ..nn import initializer as I
from ..tensor import Tensor, apply_op
from .generation import GenerationMixin, packed_cached_attention
from .mimo_v2 import _partial_rope

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM",
           "lfm2_moe_tiny_config"]

_LANES = 128
CONV, ATTN = "conv", "full_attention"
# the published 40 layers: two conv layers, then (full, conv, conv, conv)
_PUBLISHED_TYPES = (CONV, CONV) + (ATTN, CONV, CONV, CONV) * 9 + (ATTN, CONV)


def _published_rope() -> dict:
    return {"rope_theta": 1000000, "rope_type": "default"}


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    max_position_embeddings: int = 128000
    rope_parameters: dict = field(default_factory=_published_rope)
    # per layer (the published 40 by default), cut to num_hidden_layers
    layer_types: Optional[Sequence[str]] = None
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    def __post_init__(self):
        n = self.num_hidden_layers
        types = tuple(_PUBLISHED_TYPES if self.layer_types is None
                      else self.layer_types)[:n]
        if len(types) != n or set(types) - {CONV, ATTN}:
            raise ValueError(f"layer_types must give {CONV!r} or {ATTN!r} "
                             f"for each of the {n} layers, got {types}")
        self.layer_types = types
        if ATTN not in types:
            raise ValueError("the conv state shares the attention arenas' "
                             "blocks: at least one full_attention layer")
        if self.conv_bias or self.tie_word_embeddings \
                or not self.use_expert_bias:
            raise ValueError("a conv bias, a tied head and a router without "
                             "its selection bias are not built")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache must be at least 2")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must split the hidden size and be whole "
                             "groups of the kv heads")
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise ValueError(f"rope_type {self.rope_parameters['rope_type']!r}"
                             " is not built")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_row(self) -> int:
        """Values an arena stores a token a kv head: ``[v | k]`` in whole
        lane tiles (128 at head_dim 64: nothing padded)."""
        return -(-2 * self.head_dim // _LANES) * _LANES

    @property
    def conv_layers(self) -> int:
        return self.layer_types.count(CONV)


def lfm2_moe_tiny_config(**kw):
    """Every form of layer in five: dense + conv, experts + attention,
    experts + conv x 3; 4 heads of 16 over 2 kv heads, 8 experts top-2."""
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_hidden_layers=5,
                num_attention_heads=4, num_key_value_heads=2,
                num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
                max_position_embeddings=512,
                layer_types=(CONV, ATTN, CONV, CONV, CONV))
    base.update(kw)
    return Lfm2MoeConfig(**base)


def _head_norm(x, w, eps):
    """RMSNorm over the last axis (a head's ``d``), float32 statistics."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w.astype(x.dtype)


class Lfm2MoeMLP(nn.Layer):
    def __init__(self, hidden: int, ffn: int):
        super().__init__()
        self.gate_proj = nn.Linear(hidden, ffn, bias_attr=False)
        self.up_proj = nn.Linear(hidden, ffn, bias_attr=False)
        self.down_proj = nn.Linear(ffn, hidden, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class Lfm2ShortConv(nn.Layer):
    """The gated short conv: ``in_proj`` (B, C, x~), the depthwise causal
    filter ``conv_weight (L, hidden)`` (tap k multiplies ``u_{t-L+1+k}``)
    and ``out_proj``."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h, taps = config.hidden_size, config.conv_L_cache
        self.in_proj = nn.Linear(h, 3 * h, bias_attr=False)
        bound = 1.0 / math.sqrt(taps)
        self.conv_weight = self.create_parameter(
            (taps, h), default_initializer=I.Uniform(-bound, bound))
        self.out_proj = nn.Linear(h, h, bias_attr=False)

    def forward(self, x, prev=None, ends=None):
        """``x (b, s, hidden)``; ``prev (b, L - 1, hidden)``: ``u`` at the
        L - 1 positions before the first (zeros from position 0 on);
        ``ends (b, n)``: columns whose state to return. Returns ``(out,
        states (b, n, L - 1, hidden) or None)``."""
        def mix(bcx, w, *given):
            b, s, _ = bcx.shape
            taps, h = w.shape
            gate_b, gate_c, xt = bcx[..., :h], bcx[..., h:2 * h], \
                bcx[..., 2 * h:]
            u = gate_b * xt
            before = given[0].astype(u.dtype) if prev is not None \
                else jnp.zeros((b, taps - 1, h), u.dtype)
            ext = jnp.concatenate([before, u], axis=1)  # (b, s + L - 1, h)
            z = sum(ext[:, k:k + s].astype(jnp.float32)
                    * w[k].astype(jnp.float32) for k in range(taps))
            y = gate_c * z.astype(gate_c.dtype)
            if ends is None:
                return y
            cols = given[-1]
            n = cols.shape[1]
            at = (cols[:, :, None] + 1 + jnp.arange(taps - 1)).reshape(b, -1)
            st = jnp.take_along_axis(ext, at[..., None], axis=1)
            return y, st.reshape(b, n, taps - 1, h)

        args = (self.in_proj(x), self.conv_weight) \
            + ((prev,) if prev is not None else ()) \
            + ((ends,) if ends is not None else ())
        out = apply_op(mix, *args)
        if ends is None:
            return self.out_proj(out), None
        y, states = out
        return self.out_proj(y), states


class Lfm2MoeAttention(nn.Layer):
    """Grouped-query attention with a norm on every query and key head,
    over a packed arena when served."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = self.config = config
        h, d = c.hidden_size, c.head_dim
        self.heads, self.kvh = c.num_attention_heads, c.num_key_value_heads
        self.q_proj = nn.Linear(h, self.heads * d, bias_attr=False)
        self.k_proj = nn.Linear(h, self.kvh * d, bias_attr=False)
        self.v_proj = nn.Linear(h, self.kvh * d, bias_attr=False)
        self.out_proj = nn.Linear(self.heads * d, h, bias_attr=False)
        self.q_layernorm = nn.RMSNorm(d, c.norm_eps)
        self.k_layernorm = nn.RMSNorm(d, c.norm_eps)

    def forward(self, x, cache=None, pos=None, block_table=None):
        """``cache`` is this layer's packed arena, ``pos (b,)`` the per-row
        write offsets and ``block_table`` the slots' table; without a cache
        the whole sequence attends to itself."""
        c = self.config
        b, s, _ = x.shape
        heads, kvh, d = self.heads, self.kvh, c.head_dim
        theta, eps = float(c.rope_parameters["rope_theta"]), c.norm_eps
        scale = 1.0 / math.sqrt(d)

        def heads_of(qv, kv_, vv, qw, kw, posv):
            start = jnp.zeros((b,), jnp.int32) if posv is None else \
                jnp.broadcast_to(jnp.asarray(posv, jnp.int32), (b,))
            positions = start[:, None] + jnp.arange(s)[None, :]
            qv = _partial_rope(_head_norm(qv.reshape(b, s, heads, d), qw, eps),
                               positions, theta, d)
            kv_ = _partial_rope(_head_norm(kv_.reshape(b, s, kvh, d), kw, eps),
                                positions, theta, d)
            return qv, kv_, vv.reshape(b, s, kvh, d)

        def plain(qv, kv_, vv, qw, kw):
            qv, kv_, vv = heads_of(qv, kv_, vv, qw, kw, None)
            g = heads // kvh
            f32 = jnp.float32
            scores = jnp.einsum(
                "bskgd,btkd->bkgst", qv.reshape(b, s, kvh, g, d).astype(f32),
                kv_.astype(f32)) * scale
            seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, f32(-1e30)), -1)
            out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(vv.dtype), vv)
            return out.reshape(b, s, heads * d)

        def cached(qv, kv_, vv, qw, kw, arena, posv, table):
            qv, kv_, vv = heads_of(qv, kv_, vv, qw, kw, posv)
            out, arena = packed_cached_attention(qv, kv_, vv, arena, posv,
                                                 table, scale=scale)
            return out.reshape(b, s, heads * d), arena

        qkv = (self.q_proj(x), self.k_proj(x), self.v_proj(x),
               self.q_layernorm.weight, self.k_layernorm.weight)
        if cache is None:
            out, arena = apply_op(plain, *qkv), None
        else:
            out, arena = apply_op(cached, *qkv, cache, pos, block_table)
        return self.out_proj(out), arena


class Lfm2MoeDecoderLayer(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig, layer_idx: int):
        super().__init__()
        c = config
        self.kind = c.layer_types[layer_idx]
        self.operator_norm = nn.RMSNorm(c.hidden_size, c.norm_eps)
        if self.kind == ATTN:
            self.self_attn = Lfm2MoeAttention(c)
        else:
            self.conv = Lfm2ShortConv(c)
        self.ffn_norm = nn.RMSNorm(c.hidden_size, c.norm_eps)
        self.is_moe = layer_idx >= c.num_dense_layers
        if self.is_moe:
            self.feed_forward = DroplessMoE(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok, scoring="sigmoid", n_group=1,
                topk_group=1, norm_topk_prob=c.norm_topk_prob,
                scaling=c.routed_scaling_factor)
        else:
            self.feed_forward = Lfm2MoeMLP(c.hidden_size,
                                           c.intermediate_size)

    def forward(self, x, arena=None, prev=None, ends=None, pos=None,
                block_table=None, forced_idx=None):
        """Returns ``(x, arena, conv states, picks, stats)``: the arena on
        an attention layer with a cache, the states at ``ends`` on a conv
        layer given them, the last two None on a dense layer."""
        y = self.operator_norm(x)
        states = None
        if self.kind == ATTN:
            with jax.named_scope("attn"):
                a, arena = self.self_attn(y, cache=arena, pos=pos,
                                          block_table=block_table)
        else:
            with jax.named_scope("short_conv"):
                a, states = self.conv(y, prev, ends)
        h = x + a
        y = self.ffn_norm(h)
        if not self.is_moe:
            with jax.named_scope("mlp"):
                return h + self.feed_forward(y), arena, states, None, None
        routed, picks, stats = self.feed_forward(y, forced_idx)
        return h + routed, arena, states, picks, stats


def conv_state_slots(pos, table, valid_len, s: int, block_size: int,
                     num_blocks: int):
    """Where a call of ``s`` columns at per-row positions ``pos (b,)`` reads
    and writes the conv state through ``table (b, max_blocks)``: ``(read
    (b,) block, has_prior (b,) bool, write (b, n) blocks, cols (b, n))``.
    Row r reads block ``table[(pos - 1) // bs]`` (nothing before position
    0); the n = ``cdiv(s + bs - 1, bs)`` blocks its columns can touch are
    each written with the state at their last column before
    ``valid_len`` (all ``s`` when None); a block with no valid column, or
    past the table, gets ``num_blocks``: out of range, so the write is
    dropped."""
    b, mb = table.shape
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    nv = jnp.int32(s) if valid_len is None \
        else jnp.asarray(valid_len, jnp.int32)
    rows = jnp.arange(b)
    read = table[rows, jnp.clip((posv - 1) // block_size, 0, mb - 1)]
    n = (s + block_size - 2) // block_size + 1
    off = (posv % block_size)[:, None]
    k = jnp.arange(n)[None, :]
    first = jnp.maximum(k * block_size - off, 0)
    cols = jnp.minimum((k + 1) * block_size - 1 - off, nv - 1)
    col_block = (posv[:, None] + cols) // block_size
    write = jnp.where(
        (first < nv) & (col_block < mb),
        jnp.take_along_axis(table, jnp.clip(col_block, 0, mb - 1), axis=1),
        num_blocks)
    return read, posv > 0, write, cols


class Lfm2MoeModel(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [Lfm2MoeDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.embedding_norm = nn.RMSNorm(config.hidden_size, config.norm_eps)

    def forward(self, input_ids, cache=None, pos=None, block_table=None,
                valid_len=None, forced_picks=None):
        """Returns ``(hidden, new_cache, picks)``: ``picks`` the routed
        choice of every expert layer, ``(moe_layers, tokens, k)``;
        ``forced_picks`` of that shape replaces the routers' choice.
        ``valid_len``: the columns of a right-padded chunk that hold a
        token (the rest write no conv state)."""
        c = self.config
        x = self.embed_tokens(input_ids)
        s = int(input_ids.shape[1])
        prev = ends = write = None
        if cache is not None:
            conv = cache["mixer_state"]
            bs = int(cache["layers"][0].shape[1]) // c.num_key_value_heads

            def read_state(arena, posv, table, *nv):
                rd, prior, wr, cols = conv_state_slots(
                    posv, table, nv[0] if nv else None, s, bs,
                    arena.shape[0])
                st = jnp.where(prior[:, None, None, None], arena[rd],
                               jnp.zeros((), arena.dtype))
                return st, wr, cols
            with jax.named_scope("short_conv"):
                prev, write, ends = apply_op(
                    read_state, conv, pos, block_table,
                    *(() if valid_len is None else (valid_len,)))
        arenas, states, picks, stats = [], [], [], []
        for layer in self.layers:
            forced = None if forced_picks is None or not layer.is_moe \
                else forced_picks[len(picks)]
            arena = None
            if cache is not None and layer.kind == ATTN:
                arena = cache["layers"][len(arenas)]
            layer_prev = None
            if prev is not None and layer.kind == CONV:
                j = len(states)
                layer_prev = apply_op(lambda p: p[:, j], prev)
            x, arena, st, p, sts = layer(
                x, arena=arena, prev=layer_prev,
                ends=ends if layer.kind == CONV else None, pos=pos,
                block_table=block_table, forced_idx=forced)
            if layer.kind == ATTN and cache is not None:
                arenas.append(arena)
            if layer.kind == CONV and cache is not None:
                states.append(st)
            if layer.is_moe:
                picks.append(p)
                stats.append(sts)
        picks = apply_op(lambda *p: jnp.stack(p), *picks) if picks else None
        if cache is None:
            return self.embedding_norm(x), None, picks

        def write_state(arena, wr, *st):
            # (b, n, conv layers, L - 1, hidden): each write's every layer
            new = jnp.stack(st, axis=2).astype(arena.dtype)
            return arena.at[wr].set(new, mode="drop")
        with jax.named_scope("short_conv"):
            conv = apply_op(write_state, cache["mixer_state"], write, *states)
        new_cache = {"layers": arenas, "mixer_state": conv,
                     "moe_counters": cache["moe_counters"]}
        if stats:
            row = 0 if s == 1 else 1

            def count(counters, *st):
                st = jnp.stack(st)                       # (moe_layers, 3)
                counters = counters.at[row, :2].add(jnp.sum(st[:, :2], 0))
                return counters.at[row, 2].max(jnp.max(st[:, 2]))
            new_cache["moe_counters"] = apply_op(
                count, cache["moe_counters"], *stats)
        return self.embedding_norm(x), new_cache, picks


class Lfm2MoeForCausalLM(nn.Layer, GenerationMixin):
    # as ``DeepseekV3ForCausalLM``: what the programs count into the paged
    # cache's last leaf, an int32 (2, 3) array, row 0 by s = 1 calls
    cache_counters = {"moe_picks": "sum", "moe_expert_hits": "sum",
                      "moe_max_load": "max"}

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Lfm2MoeModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    @property
    def conv_state_layers(self) -> int:
        """Layers whose state the paged cache carries a block at a time
        besides the arenas (``serving.paging.refuse_conv_state``)."""
        return self.config.conv_layers

    def init_paged_kv_cache(self, num_blocks: int, block_size: int,
                            kv_int8: bool = False, dtype=None):
        """Per attention layer ONE packed arena ``(num_blocks, block_size *
        kvh, kv_row)``, the conv-state leaf ``(num_blocks, conv layers, L -
        1, hidden)`` (block 0 the trash block in both), and the counters.
        The leaves flatten in key order: the arenas first (what reads the
        cache's first leaf finds an attention arena), the conv state, the
        counters last (the engine reads them there)."""
        if kv_int8:
            raise NotImplementedError(
                "kv_int8 over a conv state: the packed arena and the conv "
                "state have no int8 form")
        c = self.config
        dt = jnp.dtype(dtype or c.dtype)
        kvh = c.num_key_value_heads
        return {
            "layers": [Tensor(jnp.zeros((num_blocks, block_size * kvh,
                                         c.kv_row), dt))
                       for t in c.layer_types if t == ATTN],
            "mixer_state": Tensor(jnp.zeros(
                (num_blocks, c.conv_layers, c.conv_L_cache - 1,
                 c.hidden_size), dt)),
            "moe_counters": Tensor(jnp.zeros((2, len(self.cache_counters)),
                                             jnp.int32))}

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        """``generate()``'s cache: the paged layout with one block a row
        (``forward`` then reads row r through table ``[[r]]``)."""
        return self.init_paged_kv_cache(batch, max_len, dtype=dtype)

    def forward(self, input_ids, labels=None, cache=None, pos=None,
                pad=None, block_table=None, valid_len=None,
                forced_picks=None, output_router_picks=False):
        """Causal LM forward: logits, or ``(loss, logits)`` with labels,
        or ``(logits, new_cache)`` with a cache. ``output_router_picks``
        adds the routers' choice as a last element."""
        if cache is not None and block_table is None:
            if pad is not None:
                raise NotImplementedError(
                    "left-padded ragged prompts have no conv-state path; "
                    "serve ragged batches through the paged engine")
            block_table = Tensor(jnp.arange(
                int(input_ids.shape[0]), dtype=jnp.int32)[:, None])
        h, new_cache, picks = self.model(
            input_ids, cache=cache, pos=pos, block_table=block_table,
            valid_len=valid_len, forced_picks=forced_picks)
        with jax.named_scope("lm_head"):
            logits = self.lm_head(h)
        extra = (picks,) if output_router_picks else ()
        if cache is not None:
            return (logits, new_cache) + extra
        if labels is None:
            return (logits,) + extra if extra else logits
        from ..ops.manipulation import reshape
        loss = F.cross_entropy(reshape(logits, (-1, logits.shape[-1])),
                               reshape(labels, (-1,)), reduction="mean")
        return (loss, logits) + extra

    def num_params(self):
        return sum(p.size for p in self.parameters())
