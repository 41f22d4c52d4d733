"""dots3-note-class decoder: latent attention (MLA) of two geometries in one
model, learned sparse attention over the full layers' latent cache, a
headwise output gate, and dropless sparse experts beside one shared expert.

The published block (``model_type: dots3_note``), pre-norm residual,
RMSNorm before each half, no bias on any projection, SwiGLU.
``layer_types[l]`` says ``full_attention`` or ``sliding_attention``; layers
below ``first_k_dense_replace`` have a dense SwiGLU of ``intermediate_size``,
the others routed experts and ``n_shared_experts`` shared ones.

- Both kinds of layer are ``models.deepseek_v3.DeepseekV3Attention`` at
  their own sizes ``(heads, q rank, kv rank, nope, rope, v, theta)``: full
  ``(num_attention_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
  qk_rope_head_dim, v_head_dim, rope_theta)``, sliding the ``swa_*`` keys.
  ``c_q = a_q RMSNorm(y W_qa)``, ``c_kv <- a_kv RMSNorm(c_kv)`` with ``a =
  sqrt(hidden / rank)`` under ``apply_mla_qkv_lora_rescale`` (`assumed`: the
  config gives the flag, not the constant); RoPE half-split at the layer's
  own base on ``q_r`` and the ONE ``k_r``; ``o_h <- sigmoid(y W_g)_h o_h``
  (``attention_gate_type: headwise``; `assumed`: the gate reads the
  layer's normed input through its own ``(hidden, heads)`` matrix).
- A sliding layer's query t sees ``{s : t - sliding_window_size < s <= t}``
  (`assumed`: the window counts the token itself).
- A full layer's query sees the ``index_topk`` keys of largest indexer
  score (``DeepseekV3Attention``'s ``indexer``), all of them while ``t + 1
  <= index_topk``; the selection is an exact ``lax.top_k``.
- Experts: the router of ``incubate.distributed.models.moe.route_topk``
  (sigmoid scores, the choice by ``score + e_score_correction_bias``, no
  groups), ``y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)``.

Not built: the vision and audio towers and the multi-token-prediction head
of the family's description (the language model's config has no key for
them); the indexer in 8-bit floats with a Hadamard rotation (the rotation
is orthogonal and cancels in ``q . k``; this model states bfloat16).

Served, the cache has TWO GROUPS of layers (``kv_cache_groups``), as
``models.mimo_v2`` has, here of latent arenas: a full layer's leaf is the
PAIR ``(latent rows (blocks, bs, 640), index keys (blocks, bs, 128))`` under
one block id, a sliding layer's ONE arena ``(window_blocks, bs, 1152)``
whose table cycles over a ring (``serving.hybrid.HybridPagedEngine``);
``forward``'s ``block_table (b, 2 * max_blocks)`` is the full group's row
followed by the window group's. ``experts_held`` tells the expert layers
which global experts they hold. The paged cache's last leaf is the int32
counter array of ``models.deepseek_v3`` with four more columns
(``cache_counters``): the tokens the indexers scored and selected and the
rows selected, all and by the selection kernel, summed over the live rows
and the full layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..incubate.distributed.models.moe import DroplessMoE
from ..nn import functional as F
from ..tensor import Tensor, apply_op
from .deepseek_v3 import DeepseekV3Attention, DeepseekV3MLP
from .generation import GenerationMixin

__all__ = ["Dots3NoteConfig", "Dots3NoteModel", "Dots3NoteForCausalLM",
           "dots3_note_tiny_config"]

_LANES = 128
FULL, WINDOW = 0, 1             # a cache leaf's group (serving.hybrid's)
_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}


def _published_types(n: int) -> Tuple[str, ...]:
    """Layer 0 full, then (F S S S) repeated: the published 46 layers."""
    period = ["full_attention"] + ["sliding_attention"] * 3
    return tuple((["full_attention"] + period * n)[:n])


@dataclass
class Dots3NoteConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    first_k_dense_replace: int = 1
    # per layer "full_attention" | "sliding_attention"; a longer list (the
    # published 46) is cut to ``num_hidden_layers``
    layer_types: Optional[Sequence[str]] = None
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window_size: int = 513
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    apply_mla_qkv_lora_rescale: bool = True
    attention_gate_type: Optional[str] = "headwise"
    swa_attention_gate_type: Optional[str] = "headwise"
    n_routed_experts: int = 256          # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 524288
    tie_word_embeddings: bool = False
    # (first, count): the global routed experts this chip holds
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"

    def __post_init__(self):
        n = self.num_hidden_layers
        types = tuple(_published_types(n) if self.layer_types is None
                      else self.layer_types)[:n]
        if len(types) != n or set(types) - set(_KINDS):
            raise ValueError(f"layer_types must name {sorted(_KINDS)} for "
                             f"each of the {n} layers, got {types}")
        self.layer_types = types
        if self.tie_word_embeddings:
            raise ValueError("the dots3-note head is untied")
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if getattr(self, key) not in (None, "headwise"):
                raise ValueError(f"{key}={getattr(self, key)!r}: only the "
                                 "headwise gate is built")

    def kind(self, layer: int) -> int:
        return _KINDS[self.layer_types[layer]]

    def attention_sizes(self, kind: int) -> dict:
        """``DeepseekV3Attention``'s arguments for a layer of ``kind``."""
        p = "swa_" if kind == WINDOW else ""
        q_rank = getattr(self, p + "q_lora_rank")
        kv_rank = getattr(self, p + "kv_lora_rank")
        scaled = self.apply_mla_qkv_lora_rescale
        return dict(
            heads=getattr(self, p + "num_attention_heads"),
            q_lora_rank=q_rank, kv_lora_rank=kv_rank,
            qk_nope_head_dim=getattr(self, p + "qk_nope_head_dim"),
            qk_rope_head_dim=getattr(self, p + "qk_rope_head_dim"),
            v_head_dim=getattr(self, p + "v_head_dim"),
            rope_theta=float(getattr(self, p + "rope_theta")),
            rope_interleave=False,
            q_rescale=math.sqrt(self.hidden_size / q_rank) if scaled else 1.0,
            kv_rescale=math.sqrt(self.hidden_size / kv_rank) if scaled
            else 1.0,
            gate=getattr(self, p + "attention_gate_type") == "headwise",
            window=self.sliding_window_size if kind == WINDOW else None,
            indexer=None if kind == WINDOW else {
                "n_heads": self.index_n_heads,
                "head_dim": self.index_head_dim, "topk": self.index_topk})

    def latent_width(self, kind: int) -> int:
        """Values a layer's cache REQUIRES a token (its index key apart)."""
        p = "swa_" if kind == WINDOW else ""
        return getattr(self, p + "kv_lora_rank") \
            + getattr(self, p + "qk_rope_head_dim")

    def latent_row(self, kind: int) -> int:
        """Values the arena STORES a token: whole lane tiles."""
        return -(-self.latent_width(kind) // _LANES) * _LANES


def dots3_note_tiny_config(**kw):
    """Dense + full, then one period F S S S of expert layers; contexts of a
    few dozen tokens pass both the window and ``index_topk``."""
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_hidden_layers=5,
                num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                swa_num_attention_heads=2, swa_q_lora_rank=24,
                swa_kv_lora_rank=40, swa_qk_nope_head_dim=24,
                swa_qk_rope_head_dim=8, swa_v_head_dim=16,
                sliding_window_size=9, index_n_heads=4, index_head_dim=16,
                index_topk=16, n_routed_experts=8, num_experts_per_tok=3,
                rope_theta=10000.0, swa_rope_theta=20.0,
                max_position_embeddings=512)
    base.update(kw)
    return Dots3NoteConfig(**base)


class Dots3NoteDecoderLayer(nn.Layer):
    def __init__(self, config: Dots3NoteConfig, layer_idx: int):
        super().__init__()
        c = config
        self.kind = c.kind(layer_idx)
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = DeepseekV3Attention(c, **c.attention_sizes(self.kind))
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.is_moe = layer_idx >= c.first_k_dense_replace
        if self.is_moe:
            self.mlp = DroplessMoE(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, experts=c.experts_held,
                scoring=c.scoring_func, norm_topk_prob=c.norm_topk_prob,
                scaling=c.routed_scaling_factor)
            self.shared_experts = DeepseekV3MLP(
                c.hidden_size, c.moe_intermediate_size * c.n_shared_experts)
        else:
            self.mlp = DeepseekV3MLP(c.hidden_size, c.intermediate_size)

    def forward(self, x, cache=None, pos=None, block_table=None,
                forced_idx=None, valid_len=None):
        """Returns ``(x, cache leaf, picks, stats, selection)``: picks and
        stats None on a dense layer, the selection ``(ids, n_valid,
        counts)`` None on a sliding layer."""
        with jax.named_scope("attn_swa" if self.kind == WINDOW else "attn"):
            a, leaf, *sel = self.self_attn(
                self.input_layernorm(x), None, None, cache=cache, pos=pos,
                block_table=block_table, valid_len=valid_len)
            h = x + a
        sel = sel[0] if sel else None
        y = self.post_attention_layernorm(h)
        if not self.is_moe:
            with jax.named_scope("mlp"):
                return h + self.mlp(y), leaf, None, None, sel
        routed, picks, stats = self.mlp(y, forced_idx)
        with jax.named_scope("moe_shared"):
            out = h + routed + self.shared_experts(y)
        return out, leaf, picks, stats, sel


class Dots3NoteModel(nn.Layer):
    def __init__(self, config: Dots3NoteConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [Dots3NoteDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, cache=None, pos=None, block_table=None,
                forced_picks=None, valid_len=None):
        """Returns ``(hidden, new_cache, picks, selections)``: ``picks``
        the routed choice of every expert layer ``(moe_layers, tokens, k)``
        (``forced_picks`` of that shape replaces it), ``selections`` every
        full layer's ``(ids (b, s, k), n_valid (b, s))``. ``block_table (b,
        2 * max_blocks)``: the full group's row, then the window group's."""
        x = self.embed_tokens(input_ids)
        tables = (None, None)
        if block_table is not None:
            mb = int(block_table.shape[1]) // 2
            tables = (block_table[:, :mb], block_table[:, mb:])
        leaves, picks, stats, sels = [], [], [], []
        for i, layer in enumerate(self.layers):
            forced = None if forced_picks is None or not layer.is_moe \
                else forced_picks[len(picks)]
            x, leaf, p, st, sel = layer(
                x, cache=None if cache is None else cache["layers"][i],
                pos=pos, block_table=tables[layer.kind], forced_idx=forced,
                valid_len=valid_len if cache is not None else None)
            leaves.append(leaf)
            if layer.is_moe:
                picks.append(p)
                stats.append(st)
            if sel is not None:
                sels.append(sel)
        picks = apply_op(lambda *p: jnp.stack(p), *picks) if picks else None
        selections = [s[:2] for s in sels]
        if cache is None:
            return self.norm(x), None, picks, selections
        row = 0 if int(input_ids.shape[1]) == 1 else 1

        def count(counters, *st):
            moe, dsa = st[:len(stats)], st[len(stats):]
            if moe:
                moe = jnp.stack(moe)                     # (moe_layers, 3)
                counters = counters.at[row, :2].add(jnp.sum(moe[:, :2], 0))
                counters = counters.at[row, 2].max(jnp.max(moe[:, 2]))
            if dsa:                                      # (full layers, 4)
                counters = counters.at[row, 3:7].add(
                    jnp.sum(jnp.stack(dsa), 0))
            return counters
        counters = apply_op(count, cache["moe_counters"], *stats,
                            *[s[2] for s in sels])
        return self.norm(x), {"layers": leaves, "moe_counters": counters}, \
            picks, selections


class Dots3NoteForCausalLM(nn.Layer, GenerationMixin):
    # as ``DeepseekV3ForCausalLM``: what the programs count into the paged
    # cache's last leaf, an int32 (2, 7) array, row 0 by s = 1 calls
    # (decode steps), row 1 by s > 1 calls (prefill chunks). The dsa
    # columns, summed over the full layers: tokens the indexers scored
    # (each live row's context), tokens the reads attended to, live rows
    # whose top-k was selected, and of those the rows the kernel
    # ``dsa_select_topk`` selected (all of them on a TPU, none off it)
    cache_counters = {"moe_picks": "sum", "moe_expert_hits": "sum",
                      "moe_max_load": "max", "dsa_tokens_scored": "sum",
                      "dsa_tokens_selected": "sum",
                      "dsa_rows_selected": "sum",
                      "dsa_rows_kernel_selected": "sum"}

    def __init__(self, config: Dots3NoteConfig):
        super().__init__()
        self.config = config
        self.model = Dots3NoteModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    @property
    def kv_cache_groups(self) -> dict:
        """The two groups of layers the paged cache keeps apart
        (``MiMoV2ForCausalLM.kv_cache_groups``): ``window`` tokens a
        sliding layer ever reads back, and each layer's cache leaf's group
        (a full layer's leaf is a PAIR of arenas of one group, sharing a
        block id), the counters' leaf None."""
        c = self.config
        return {"window": c.sliding_window_size,
                "leaf_group": tuple(c.kind(i)
                                    for i in range(c.num_hidden_layers))
                + (None,)}

    def init_paged_kv_cache(self, num_blocks: int, block_size: int,
                            kv_int8: bool = False, dtype=None,
                            window_blocks: Optional[int] = None):
        """A full layer: ``(latent rows (num_blocks, block_size, row),
        index keys (num_blocks, block_size, index_head_dim))``; a sliding
        layer: ``(window_blocks, block_size, row)``; block 0 the trash
        block of every arena; and the counters."""
        if kv_int8:
            raise NotImplementedError("the latent arena has no int8 form")
        if window_blocks is None:
            raise NotImplementedError(
                f"{type(self).__name__} keeps two groups of cache layers "
                "(kv_cache_groups): serve it through "
                "ContinuousBatchingEngine(model, paged=True), which builds "
                "serving.hybrid.HybridPagedEngine; tensor-parallel, "
                "speculative, fleet and exported backends cannot hold it "
                "yet")
        c = self.config
        dt = jnp.dtype(dtype or c.dtype)

        def arena(blocks, width):
            return Tensor(jnp.zeros((blocks, block_size, width), dt))
        layers = []
        for i in range(c.num_hidden_layers):
            if c.kind(i) == WINDOW:
                layers.append(arena(window_blocks, c.latent_row(WINDOW)))
            else:
                layers.append((arena(num_blocks, c.latent_row(FULL)),
                               arena(num_blocks, c.index_head_dim)))
        return {"layers": layers, "moe_counters": Tensor(jnp.zeros(
            (2, len(self.cache_counters)), jnp.int32))}

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        """``generate()``'s cache: the paged layout with one block a row in
        BOTH groups (``forward`` then reads row r through ``[[r, r]]``);
        the window is in the mask there, not in the storage."""
        return self.init_paged_kv_cache(batch, max_len, dtype=dtype,
                                        window_blocks=batch)

    def forward(self, input_ids, labels=None, cache=None, pos=None,
                pad=None, block_table=None, forced_picks=None,
                output_router_picks=False, output_selections=False,
                valid_len=None):
        """Causal LM forward: logits, or ``(loss, logits)`` with labels,
        or ``(logits, new_cache)`` with a cache. ``output_router_picks``
        adds the routers' choice, ``output_selections`` every full layer's
        ``(ids, n_valid)``, as last elements. ``valid_len`` (a scalar): the
        columns of a right-padded prefill chunk that hold a prompt token;
        the full layers skip the selection and the selected read of the
        row blocks past it (their rows are padding: nothing reads them)."""
        if cache is not None and block_table is None:
            if pad is not None:
                raise NotImplementedError(
                    "left-padded ragged prompts have no latent-cache path; "
                    "serve ragged batches through the paged engine")
            rows = jnp.arange(int(input_ids.shape[0]), dtype=jnp.int32)
            block_table = Tensor(jnp.stack([rows, rows], axis=1))
        h, new_cache, picks, selections = self.model(
            input_ids, cache=cache, pos=pos, block_table=block_table,
            forced_picks=forced_picks, valid_len=valid_len)
        with jax.named_scope("lm_head"):
            logits = self.lm_head(h)
        extra = ((picks,) if output_router_picks else ()) \
            + ((selections,) if output_selections else ())
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
