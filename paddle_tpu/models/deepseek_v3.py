"""DeepSeek-V3-class decoder: multi-head latent attention (MLA) over a
compressed cache, and dropless sparse experts with always-on shared ones.

The published block (``model_type: deepseek_v3``), pre-norm residual:

- Attention. ``q = x W_q`` (or through ``q_lora_rank``) -> heads x
  (``qk_nope_head_dim`` | ``qk_rope_head_dim``); ``[c_kv | k_pe] = x
  W_kva``; ``c_kv <- RMSNorm(c_kv)``; ``k_pe`` is ONE head shared by all.
  RoPE on ``q_pe`` and ``k_pe`` (``rope_interleave``: the pair dimensions
  are de-interleaved, even | odd, before rotate-half). ``[k_nope | v] =
  c_kv W_kvb``; ``scores = (q_nope . k_nope + q_pe . k_pe) /
  sqrt(qk_head_dim)``; causal softmax; ``out = (P v) W_o``.
- Experts (layers from ``first_k_dense_replace`` on): the router of
  ``incubate.distributed.models.moe.route_topk`` over ``n_routed_experts``,
  ``y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)`` with the shared width
  ``n_shared_experts * moe_intermediate_size``. Earlier layers: a dense
  SwiGLU of ``intermediate_size``.

Served, the cache keeps ``c_kv`` after its norm and ``k_pe`` after RoPE:
``kv_lora_rank + qk_rope_head_dim`` values a token a layer, one row for all
heads, stored in an arena ``(num_blocks, block_size, w)`` with the row
padded to whole 128-lane tiles. Every cached read is ABSORBED
(``models.generation.latent_cached_attention``): ``q' = q_nope W_kvb,k^T``
per head, ``scores = q' . c_kv + q_pe . k_pe``, ``o_latent = P c_kv``,
``out_head = o_latent W_kvb,v`` — the s = 1 read through the Pallas walk
``mla_paged_attention_decode`` on a TPU. The forward without a cache
expands instead (``k_nope`` and ``v`` for every token); the two agree to
rounding, which the tests hold them to.

``forward`` takes ``cache``, ``pos``, ``pad``, ``block_table`` as
``LlamaForCausalLM.forward`` does, so ``serving.PagedModelStepBackend``
takes the model unchanged. ``experts_held`` tells the expert layers which
global experts they hold (expert parallelism's share; all by default).
The paged cache's last leaf is an int32 counter array the programs fill
(``cache_counters``): picks, experts hit and the largest load, decode
steps (row 0) apart from prefill chunks (row 1).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..incubate.distributed.models.moe import DroplessMoE
from ..nn import functional as F
from ..tensor import Tensor, apply_op
from ..ops.pallas.dsa_select import dsa_select_topk
from ..ops.pallas.dsa_select import kernel_ok as select_kernel_ok
from .generation import (GenerationMixin, latent_cached_attention,
                         latent_index_scores)

__all__ = ["DeepseekV3Config", "DeepseekV3Model", "DeepseekV3ForCausalLM",
           "deepseek_v3_tiny_config"]

_LANES = 128


@dataclass
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_interleave: bool = True
    tie_word_embeddings: bool = False
    # (first, count): the global routed experts this chip holds
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError(f"n_group={self.n_group} does not divide "
                             f"n_routed_experts={self.n_routed_experts}")
        if self.tie_word_embeddings:
            raise ValueError("the DeepSeek-V3 head is untied")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values the cache REQUIRES a token a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Values the arena STORES a token a layer: the row padded to
        whole lane tiles, so a page is the matrix the kernel reads."""
        return -(-self.latent_width // _LANES) * _LANES


def deepseek_v3_tiny_config(**kw):
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, q_lora_rank=None, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts=8, n_shared_experts=2,
                num_experts_per_tok=3, first_k_dense_replace=1, n_group=1,
                topk_group=1, routed_scaling_factor=2.448,
                max_position_embeddings=256)
    base.update(kw)
    return DeepseekV3Config(**base)


def _rope_cache(config: DeepseekV3Config):
    d = config.qk_rope_head_dim
    inv = 1.0 / (config.rope_theta ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.outer(jnp.arange(config.max_position_embeddings,
                                 dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _rope(x, cos, sin, positions, interleave):
    """x (b, s, ..., d) at ``positions (b, s)``: rotate-half, after
    de-interleaving the pairs (even | odd) where the checkpoint stores
    them interleaved."""
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    shape = positions.shape + (1,) * (x.ndim - 3) + (x.shape[-1],)
    c = cos[positions].reshape(shape).astype(x.dtype)
    sn = sin[positions].reshape(shape).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * c + jnp.concatenate([-x2, x1], axis=-1) * sn


class DeepseekV3MLP(nn.Layer):
    def __init__(self, hidden: int, ffn: int):
        super().__init__()
        self.gate_proj = nn.Linear(hidden, ffn, bias_attr=False)
        self.up_proj = nn.Linear(hidden, ffn, bias_attr=False)
        self.down_proj = nn.Linear(ffn, hidden, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


def _rope_tables(positions, theta: float, d: int):
    """cos / sin ``(b, s, d)`` at ``positions (b, s)``, the angles in
    float32 from the positions (no table of ``max_position_embeddings``
    rows): for a model whose layers turn at different bases."""
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, :, None] * inv[None, None]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rope_at(x, c, sn):
    """rotate-half of ``x (b, s, ..., d)`` by cos / sin ``(b, s, d)``."""
    shape = c.shape[:2] + (1,) * (x.ndim - 3) + (x.shape[-1],)
    c, sn = c.reshape(shape).astype(x.dtype), sn.reshape(shape).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * c + jnp.concatenate([-x2, x1], axis=-1) * sn


class DeepseekV3Attention(nn.Layer):
    """Multi-head latent attention. Every size is an argument, the
    config's value by default, so that one model may hold layers of
    several geometries (``models/dots3_note.py``: full and sliding layers
    at different head counts, ranks and head sizes). Beyond the published
    DeepSeek-V3 block:

    - ``rope_theta``: the angles are computed from the positions at this
      base (half-split unless ``rope_interleave``), and ``forward``'s
      ``cos`` / ``sin`` tables are not read;
    - ``q_rescale`` / ``kv_rescale``: constants on the two rank norms'
      outputs;
    - ``gate``: a headwise sigmoid gate on the attention output, ``g =
      sigmoid(x W_g) (heads,)``, ``o_h <- g_h o_h``;
    - ``window``: key j is seen from query i iff ``0 <= i - j < window``;
      the cached read goes through a ring table;
    - ``indexer = {"n_heads", "head_dim", "topk"}``: learned sparse
      attention. ``q^I = c_q W^I_q``, ``k^I = LayerNorm(x W^I_k)``, RoPE
      on the first ``qk_rope_head_dim`` dims of each, ``w = x W^I_w /
      sqrt(n_heads * head_dim)``, ``I(t, s) = sum_j w_j ReLU(q^I_j(t) .
      k^I(s))``; a query attends to the ``topk`` keys ``s <= t`` of
      largest ``I`` (all of them while ``t + 1 <= topk``), chosen
      exactly: by ``lax.top_k`` without a cache, by the Pallas kernel
      ``dsa_select_topk`` (no sort; the ids in ascending position order)
      on the cached path. The cache is then the PAIR ``(latent arena,
      index-key arena)`` under one block id, and ``forward`` returns a
      third value: ``(ids (b, s, k), n_valid (b, s), counts (4,))`` with
      the tokens scored and selected and the rows selected, all and by
      the kernel, summed over the live rows."""

    def __init__(self, config: DeepseekV3Config, *, heads=None,
                 q_lora_rank=-1, kv_lora_rank=None, qk_nope_head_dim=None,
                 qk_rope_head_dim=None, v_head_dim=None, rope_theta=None,
                 rope_interleave=None, q_rescale=1.0, kv_rescale=1.0,
                 gate=False, window=None, indexer=None):
        super().__init__()
        c = self.config = config
        h = c.hidden_size

        def given(v, default):
            return default if v is None else v
        self.heads = heads = given(heads, c.num_attention_heads)
        self.q_rank = c.q_lora_rank if q_lora_rank == -1 else q_lora_rank
        self.rank = given(kv_lora_rank, c.kv_lora_rank)
        self.nope = given(qk_nope_head_dim, c.qk_nope_head_dim)
        self.rope_dim = given(qk_rope_head_dim, c.qk_rope_head_dim)
        self.v_dim = given(v_head_dim, c.v_head_dim)
        self.rope_theta = rope_theta
        self.interleave = given(rope_interleave,
                                getattr(c, "rope_interleave", False))
        self.q_rescale, self.kv_rescale = float(q_rescale), float(kv_rescale)
        self.window, self.indexer = window, indexer
        qk = self.nope + self.rope_dim
        if self.q_rank is None:
            self.q_proj = nn.Linear(h, heads * qk, bias_attr=False)
        else:
            self.q_a_proj = nn.Linear(h, self.q_rank, bias_attr=False)
            self.q_a_layernorm = nn.RMSNorm(self.q_rank, c.rms_norm_eps)
            self.q_b_proj = nn.Linear(self.q_rank, heads * qk,
                                      bias_attr=False)
        self.kv_a_proj_with_mqa = nn.Linear(h, self.rank + self.rope_dim,
                                            bias_attr=False)
        self.kv_a_layernorm = nn.RMSNorm(self.rank, c.rms_norm_eps)
        self.kv_b_proj = nn.Linear(
            self.rank, heads * (self.nope + self.v_dim), bias_attr=False)
        self.o_proj = nn.Linear(heads * self.v_dim, h, bias_attr=False)
        if gate:
            self.head_gate = nn.Linear(h, heads, bias_attr=False)
        self.has_gate = bool(gate)
        if indexer is not None:
            if self.q_rank is None:
                raise ValueError("the indexer reads the query's low-rank "
                                 "latent: it needs a q_lora_rank")
            n, d = indexer["n_heads"], indexer["head_dim"]
            self.idx_q_proj = nn.Linear(self.q_rank, n * d, bias_attr=False)
            self.idx_k_proj = nn.Linear(h, d, bias_attr=False)
            self.idx_k_norm = nn.LayerNorm(d)
            self.idx_w_proj = nn.Linear(h, n, bias_attr=False)

    def _positions(self, b, s, posv):
        start = jnp.zeros((b,), jnp.int32) if posv is None else \
            jnp.broadcast_to(jnp.asarray(posv, jnp.int32), (b,))
        return start[:, None] + jnp.arange(s)[None, :]

    def _turn(self, x, cos, sin, positions):
        """RoPE on ``x (b, s, ..., rope_dim)``: at the layer's own base
        where it has one, from the model's tables otherwise."""
        if self.rope_theta is None:
            return _rope(x, cos, sin, positions, self.interleave)
        if self.interleave:
            x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        return _rope_at(x, *_rope_tables(positions, self.rope_theta,
                                         self.rope_dim))

    def _index(self, c_q, x):
        """The indexer's three projections: ``(q (b, s, n, d), k (b, s,
        d), w (b, s, n))`` before RoPE."""
        n, d = self.indexer["n_heads"], self.indexer["head_dim"]
        b, s, _ = x.shape
        q = self.idx_q_proj(c_q)
        k = self.idx_k_norm(self.idx_k_proj(x))
        w = self.idx_w_proj(x)

        def shape(qv, wv):
            return qv.reshape(b, s, n, d), \
                wv.astype(jnp.float32) * (1.0 / math.sqrt(n * d))
        q, w = apply_op(shape, q, w)
        return q, k, w

    def _turn_index(self, q, k, cos, sin, positions):
        """RoPE on the first ``rope_dim`` dims of the indexer's q and k."""
        r = self.rope_dim
        q = jnp.concatenate([self._turn(q[..., :r], cos, sin, positions),
                             q[..., r:]], axis=-1)
        k = jnp.concatenate([self._turn(k[..., :r], cos, sin, positions),
                             k[..., r:]], axis=-1)
        return q, k

    def forward(self, x, cos, sin, cache=None, pos=None, block_table=None,
                valid_len=None):
        """``cache`` is this layer's latent arena (with an indexer: the
        pair ``(latent arena, index-key arena)``) and ``pos (b,)`` the
        per-row write offsets (the absorbed, cached read); without a
        cache the whole sequence attends to itself, expanded.
        ``valid_len`` (a scalar; a right-padded prefill chunk's real
        columns): the indexer's selection and selected read skip the rows
        that are all padding (the selection a block of 8, the read a block
        of 128)."""
        b, s, _ = x.shape
        heads, rank, nope, vd = self.heads, self.rank, self.nope, self.v_dim
        window, indexer = self.window, self.indexer
        qk = nope + self.rope_dim
        if self.q_rank is None:
            c_q, q = None, self.q_proj(x)
        else:
            c_q = self.q_a_layernorm(self.q_a_proj(x))
            if self.q_rescale != 1.0:
                c_q = c_q * self.q_rescale
            q = self.q_b_proj(c_q)
        kva = self.kv_a_proj_with_mqa(x)
        c_kv = self.kv_a_layernorm(kva[:, :, :rank])
        if self.kv_rescale != 1.0:
            c_kv = c_kv * self.kv_rescale
        scale = 1.0 / math.sqrt(qk)
        extra = []
        if self.has_gate:
            with jax.named_scope("attn_gate"):
                extra.append(F.sigmoid(self.head_gate(x)))
        if indexer is not None:
            extra.extend(self._index(c_q, x))

        def take(rest):
            """The optional operands, in the order they were added."""
            rest = list(rest)
            g = rest.pop(0) if self.has_gate else None
            idx = tuple(rest[:3]) if indexer is not None else None
            return g, idx

        def gated(out, g):
            if g is None:
                return out
            with jax.named_scope("attn_gate"):
                return out * g[..., None].astype(out.dtype)

        def split(qv, kvav, posv):
            qv = qv.reshape(b, s, heads, qk)
            positions = self._positions(b, s, posv)
            q_pe = self._turn(qv[..., nope:], cos, sin, positions)
            k_pe = self._turn(kvav[:, :, rank:], cos, sin, positions)
            return qv[..., :nope], q_pe, k_pe, positions

        def w_kvb(wv):
            w3 = wv.reshape(rank, heads, nope + vd)
            return w3[..., :nope], w3[..., nope:]

        def counts(n_valid, positions, live, by_kernel=False):
            """[tokens scored, tokens selected, rows selected, rows the
            kernel selected] over the live rows: ``live (b, 1 | s)``."""
            live = live.astype(jnp.int32)
            rows = jnp.sum(jnp.broadcast_to(live, positions.shape))
            return jnp.stack([jnp.sum((positions + 1) * live),
                              jnp.sum(n_valid * live), rows,
                              rows if by_kernel else 0]).astype(jnp.int32)

        if cache is None:
            def expanded(qv, kvav, ckv, wv, *rest):
                g, idx = take(rest)
                q_nope, q_pe, k_pe, positions = split(qv, kvav, None)
                wk, wvv = w_kvb(wv)
                k_nope = jnp.einsum("btr,rhd->bthd", ckv, wk)
                v = jnp.einsum("btr,rhd->bthd", ckv, wvv)
                f32 = jnp.float32
                seen = jnp.tril(jnp.ones((s, s), bool))
                if window is not None:
                    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
                    seen = seen & (i - j < window)
                sel = None
                if idx is not None:
                    from ..ops.pallas.paged_attention import _index_scores
                    with jax.named_scope("dsa_index"):
                        qi, ki = self._turn_index(idx[0], idx[1], cos, sin,
                                                  positions)
                        score = _index_scores(qi, idx[2], ki, key_block=1024)
                        score = jnp.where(seen[None], score, -jnp.inf)
                    with jax.named_scope("dsa_select"):
                        k = min(indexer["topk"], s)
                        _, ids = jax.lax.top_k(score, k)
                        n_valid = jnp.minimum(positions + 1, k)
                        valid = jnp.arange(k)[None, None] < n_valid[..., None]
                        picked = jnp.zeros((b, s, s), bool).at[
                            jnp.arange(b)[:, None, None],
                            jnp.arange(s)[None, :, None], ids].max(valid)
                    seen = (seen[None] & picked)[:, None]       # (b,1,s,s)
                    sel = (ids, n_valid,
                           counts(n_valid, positions, jnp.ones((b, 1), bool)))

                def rows(lo, n):
                    """Query rows lo..lo+n against every key."""
                    qn = jax.lax.dynamic_slice_in_dim(q_nope, lo, n, 1)
                    qp = jax.lax.dynamic_slice_in_dim(q_pe, lo, n, 1)
                    scores = (jnp.einsum("bshd,bthd->bhst", qn.astype(f32),
                                         k_nope.astype(f32))
                              + jnp.einsum("bshd,btd->bhst", qp.astype(f32),
                                           k_pe.astype(f32))) * scale
                    vis = jax.lax.dynamic_slice_in_dim(seen, lo, n,
                                                       seen.ndim - 2)
                    probs = jax.nn.softmax(
                        jnp.where(vis, scores, jnp.float32(-1e30)), axis=-1)
                    return jnp.einsum("bhst,bthd->bshd",
                                      probs.astype(v.dtype), v)

                blk = 256
                if s <= 2 * blk or s % blk:
                    out = rows(0, s)
                else:           # a long sequence: blocks of query rows
                    out = jax.lax.map(lambda lo: rows(lo, blk),
                                      jnp.arange(0, s, blk))
                    out = jnp.moveaxis(out, 0, 1).reshape(b, s, heads, vd)
                out = gated(out, g).reshape(b, s, heads * vd)
                return out if sel is None else (out,) + sel
            got = apply_op(expanded, q, kva, c_kv, self.kv_b_proj.weight,
                           *extra)
            if indexer is None:
                return self.o_proj(got), None
            return self.o_proj(got[0]), None, got[1:]

        def select_rows(score, k, valid):
            """The exact top-k of every row's scores, the ids in ascending
            position order (``dsa_select_topk``); of a chunk, the row
            blocks that hold no real column are skipped."""
            live = None if valid is None else jnp.broadcast_to(
                jnp.arange(s)[None, :] < valid, (b, s)).reshape(-1)
            return dsa_select_topk(score.reshape(b * s, -1), k,
                                   live).reshape(b, s, k)

        def absorbed(qv, kvav, ckv, wv, arena, posv, table, *rest):
            rest, valid = (rest[:-1], rest[-1]) if valid_len is not None \
                else (rest, None)
            g, idx = take(rest)
            q_nope, q_pe, k_pe, positions = split(qv, kvav, posv)
            wk, wvv = w_kvb(wv)
            q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, wk)
            q_lat = jnp.concatenate([q_abs, q_pe], axis=-1)
            lat = jnp.concatenate([ckv, k_pe], axis=-1)
            sel = None
            if idx is None:
                kw = {} if window is None else {"window": window}
                o_lat, arena = latent_cached_attention(
                    q_lat, lat, arena, posv, table, scale=scale, rank=rank,
                    **kw)
            else:
                keys = rest[-1]
                with jax.named_scope("dsa_index"):
                    qi, ki = self._turn_index(idx[0], idx[1], cos, sin,
                                              positions)
                    score, keys = latent_index_scores(qi, idx[2], ki, keys,
                                                      posv, table)
                with jax.named_scope("dsa_select"):
                    k = min(indexer["topk"], score.shape[-1])
                    ids = select_rows(score, k, valid)
                    n_valid = jnp.minimum(positions + 1, k)
                with jax.named_scope("dsa_read"):
                    o_lat, arena = latent_cached_attention(
                        q_lat, lat, arena, posv, table, scale=scale,
                        rank=rank, select=(ids, n_valid), valid_len=valid)
                live = (table[:, 0] > 0)[:, None]
                if valid is not None:       # a chunk's real columns only
                    live = live & (jnp.arange(s)[None, :] < valid)
                sel = (keys, ids, n_valid,
                       counts(n_valid, positions, live, select_kernel_ok()))
            out = jnp.einsum("bshr,rhd->bshd", o_lat, wvv)
            out = gated(out, g).reshape(b, s, heads * vd)
            return (out, arena) if sel is None else (out, arena) + sel
        if indexer is None:
            valid_len = None            # only the indexer's rows are dear
            out, arena = apply_op(absorbed, q, kva, c_kv,
                                  self.kv_b_proj.weight, cache, pos,
                                  block_table, *extra)
            return self.o_proj(out), arena
        tail = (cache[1],) if valid_len is None else (cache[1], valid_len)
        out, arena, keys, *sel = apply_op(
            absorbed, q, kva, c_kv, self.kv_b_proj.weight, cache[0], pos,
            block_table, *extra, *tail)
        return self.o_proj(out), (arena, keys), tuple(sel)


class DeepseekV3DecoderLayer(nn.Layer):
    def __init__(self, config: DeepseekV3Config, layer_idx: int):
        super().__init__()
        c = config
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = DeepseekV3Attention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.is_moe = layer_idx >= c.first_k_dense_replace
        if self.is_moe:
            self.mlp = DroplessMoE(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, experts=c.experts_held,
                scoring=c.scoring_func, n_group=c.n_group,
                topk_group=c.topk_group, norm_topk_prob=c.norm_topk_prob,
                scaling=c.routed_scaling_factor)
            self.shared_experts = DeepseekV3MLP(
                c.hidden_size, c.moe_intermediate_size * c.n_shared_experts)
        else:
            self.mlp = DeepseekV3MLP(c.hidden_size, c.intermediate_size)

    def forward(self, x, cos, sin, cache=None, pos=None, block_table=None,
                forced_idx=None):
        """Returns ``(x, arena, picks, stats)``; the last two are None on
        a dense layer."""
        with jax.named_scope("attn"):
            a, arena = self.self_attn(self.input_layernorm(x), cos, sin,
                                      cache=cache, pos=pos,
                                      block_table=block_table)
            h = x + a
        y = self.post_attention_layernorm(h)
        if not self.is_moe:
            with jax.named_scope("mlp"):
                return h + self.mlp(y), arena, None, None
        routed, picks, stats = self.mlp(y, forced_idx)
        with jax.named_scope("moe_shared"):
            out = h + routed + self.shared_experts(y)
        return out, arena, picks, stats


class DeepseekV3Model(nn.Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [DeepseekV3DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = _rope_cache(config)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, cache=None, pos=None, block_table=None,
                forced_picks=None):
        """Returns ``(hidden, new_cache, picks)``: ``picks`` the routed
        choice of every expert layer, ``(moe_layers, tokens, k)``.
        ``forced_picks`` of that shape replaces the routers' choice."""
        x = self.embed_tokens(input_ids)
        cos, sin = self.rope_cos._value, self.rope_sin._value
        arenas, picks, stats = [], [], []
        for i, layer in enumerate(self.layers):
            forced = None if forced_picks is None or not layer.is_moe \
                else forced_picks[len(picks)]
            x, arena, p, st = layer(
                x, cos, sin, cache=None if cache is None
                else cache["layers"][i], pos=pos, block_table=block_table,
                forced_idx=forced)
            arenas.append(arena)
            if layer.is_moe:
                picks.append(p)
                stats.append(st)
        picks = apply_op(lambda *p: jnp.stack(p), *picks) if picks else None
        if cache is None:
            return self.norm(x), None, picks
        new_cache = {"layers": arenas, "moe_counters": cache["moe_counters"]}
        if stats:
            row = 0 if int(input_ids.shape[1]) == 1 else 1

            def count(counters, *st):
                st = jnp.stack(st)                       # (moe_layers, 3)
                counters = counters.at[row, :2].add(jnp.sum(st[:, :2], 0))
                return counters.at[row, 2].max(jnp.max(st[:, 2]))
            new_cache["moe_counters"] = apply_op(count, cache["moe_counters"],
                                             *stats)
        return self.norm(x), new_cache, picks


class DeepseekV3ForCausalLM(nn.Layer, GenerationMixin):
    # what the programs count into the paged cache's last leaf, an int32
    # (2, 3) array: row 0 by s = 1 calls (decode steps), row 1 by s > 1
    # calls (prefill chunks). name -> how a reader folds it over time
    cache_counters = {"moe_picks": "sum", "moe_expert_hits": "sum",
                      "moe_max_load": "max"}

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.model = DeepseekV3Model(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def init_paged_kv_cache(self, num_blocks: int, block_size: int,
                            kv_int8: bool = False, dtype=None):
        """Per layer ONE latent arena ``(num_blocks, block_size,
        latent_row)`` (block 0 the trash block), and the counters."""
        if kv_int8:
            raise NotImplementedError("the latent arena has no int8 form")
        c = self.config
        dt = jnp.dtype(dtype or c.dtype)
        shape = (num_blocks, block_size, c.latent_row)
        return {"layers": [Tensor(jnp.zeros(shape, dt))
                           for _ in range(c.num_hidden_layers)],
                "moe_counters": Tensor(jnp.zeros((2, len(self.cache_counters)),
                                             jnp.int32))}

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        """``generate()``'s cache: the paged layout with one block a row
        (``forward`` then reads row r through table ``[[r]]``)."""
        return self.init_paged_kv_cache(batch, max_len, dtype=dtype)

    def forward(self, input_ids, labels=None, cache=None, pos=None,
                pad=None, block_table=None, forced_picks=None,
                output_router_picks=False):
        """Causal LM forward: logits, or ``(loss, logits)`` with labels,
        or ``(logits, new_cache)`` with a cache. ``output_router_picks``
        adds the routers' choice as a last element."""
        if cache is not None and block_table is None:
            if pad is not None:
                raise NotImplementedError(
                    "left-padded ragged prompts have no latent-cache path; "
                    "serve ragged batches through the paged engine")
            block_table = Tensor(jnp.arange(
                int(input_ids.shape[0]), dtype=jnp.int32)[:, None])
        h, new_cache, picks = self.model(
            input_ids, cache=cache, pos=pos, block_table=block_table,
            forced_picks=forced_picks)
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
