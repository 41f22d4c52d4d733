"""Ouro-class looped decoder: ONE stack of layers run ``total_ut_steps``
times over the same weights, with a key/value cache for every pass.

The published block (``model_type: ouro``; "Scaling Latent Reasoning via
Looped Language Models", arXiv 2510.25741), RMSNorm eps ``rms_norm_eps``,
no bias on any projection, a bias on the gate:

- ``x <- E[ids]``. For ``u`` in ``0..total_ut_steps - 1``, for every layer
  ``l``:  ``x <- x + RMSNorm(Attn_l(RMSNorm(x; a1_l)); a2_l)``;
  ``x <- x + RMSNorm(SwiGLU_l(RMSNorm(x; m1_l)); m2_l)`` (a "sandwich": each
  half's OUTPUT is normed before it joins the residual). After the last
  layer of every pass ``x <- RMSNorm(x; g)``, the model's one final norm:
  ``h_u = x``, and this ``x`` enters pass ``u + 1``. Layer ``l``'s weights
  are the same in every pass.
- ``Attn_l``: ``models.llama.LlamaAttention`` as it is (one query head a KV
  head at the published sizes; RoPE, half-split, on all of a head's dims at
  the token's index, the same in every pass; causal, full). In pass ``u``
  the keys and values are those pass ``u`` wrote: ``total_ut_steps x
  num_hidden_layers`` cache layers over ``num_hidden_layers`` weight layers.
- Exit gate: ``lambda_u = sigmoid(h_u . w + b)`` in float32; ``p_0 =
  lambda_0``, ``p_u = lambda_u prod_{j<u} (1 - lambda_j)``, the last pass
  takes what is left. A token's logits are those of the first pass whose
  cumulative ``p`` reaches ``early_exit_threshold``, else the last. At the
  published threshold 1 that is the last pass for every token, and that is
  all this class builds: every pass runs for every token, ``lm_head`` reads
  the last, the gate decides nothing and feeds a counter. A threshold below
  1 (a step that skips passes for some rows) refuses by name.

Served, the cache keeps ONE geometry: a weight layer's ``(k, v)`` arenas
hold ``total_ut_steps x num_blocks`` blocks, pass ``u``'s pages in slice
``u``, and pass ``u`` reads and writes through the slot's block table plus
``u x num_blocks``. One block id of the engine's ``BlockManager`` then
stands for a token range in every cache layer; table entry 0 (the trash
block) lands on block 0 of slice ``u``, which is trash in every slice. The
passes are a ``lax.scan`` IN the program (the arenas carried, the weights
closed over once): the decode block and the chunk program hold each layer's
body, and its Pallas call, once.

The paged cache's last leaf is an int32 ``(2, 1)`` counter array
(``cache_counters``, as ``models.deepseek_v3`` keeps its own): row 0 by
s = 1 calls, row 1 by s > 1 calls, ``ut_exit_step_milli`` = the sum over
live rows (a row whose table is not all trash) and the call's columns of
``round(1000 x sum_u u p_u)``, the pass at which the model's own gate
expects to stop.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import framework, nn
from ..nn import functional as F
from ..tensor import Tensor, apply_op
from .generation import GenerationMixin
from .llama import LlamaAttention, LlamaConfig, LlamaMLP, _rope_cache

__all__ = ["OuroConfig", "OuroModel", "OuroForCausalLM", "ouro_tiny_config"]


@dataclass
class OuroConfig(LlamaConfig):
    """``LlamaConfig``'s fields (``LlamaAttention`` and ``LlamaMLP`` read
    them) at the published Ouro-2.6B values, and the loop's two."""
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: Optional[int] = None      # hidden_size // heads, if given
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    tensor_parallel: bool = False
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        own = self.hidden_size // self.num_attention_heads
        if self.head_dim not in (None, own):
            raise ValueError(
                f"head_dim={self.head_dim}: the attention of this class has "
                f"hidden_size // num_attention_heads = {own}")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps={self.total_ut_steps}; the "
                             "stack runs at least once")
        if self.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold={self.early_exit_threshold}: an "
                "adaptive exit below 1 needs a step that skips passes for "
                "some rows; every pass runs for every token here")
        if self.tie_word_embeddings:
            raise ValueError("the Ouro head is untied")
        if self.pipeline_parallel or self.scan_layers:
            raise NotImplementedError(
                "the looped stack has no stacked (scan / pipeline) trunk")


def ouro_tiny_config(**kw):
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=160,
                num_hidden_layers=3, num_attention_heads=4,
                num_key_value_heads=4, max_position_embeddings=256,
                total_ut_steps=4)
    base.update(kw)
    return OuroConfig(**base)


class OuroDecoderLayer(nn.Layer):
    """A sandwich layer: a norm before and after each half."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.self_attn = LlamaAttention(config)
        self.input_layernorm_2 = nn.RMSNorm(h, eps)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.mlp = LlamaMLP(config)
        self.post_attention_layernorm_2 = nn.RMSNorm(h, eps)

    def forward(self, x, cos, sin, cache=None, pos=None, block_table=None):
        """Returns ``(x, new_cache)``; ``new_cache`` is None without one."""
        new_cache = None
        with jax.named_scope("attn"):
            y = self.input_layernorm(x)
            if cache is None:
                a = self.self_attn(y, cos, sin)
            else:
                a, new_cache = self.self_attn(y, cos, sin, cache=cache,
                                              pos=pos,
                                              block_table=block_table)
            h = x + self.input_layernorm_2(a)
        with jax.named_scope("mlp"):
            out = h + self.post_attention_layernorm_2(
                self.mlp(self.post_attention_layernorm(h)))
        return out, new_cache


def exit_distribution(lam):
    """Gate values ``lam (U, ...)`` -> the exit distribution ``p (U, ...)``:
    ``p_u = lam_u prod_{j<u} (1 - lam_j)``, the last pass takes the rest."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


class OuroModel(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [OuroDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.early_exit_gate = nn.Linear(config.hidden_size, 1)
        cos, sin = _rope_cache(config)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def _one_pass(self, x, arenas, pos, table):
        """The stack once on values: ``(h_u, lambda_u (b, s) float32,
        arenas)``; ``arenas`` is a flat tuple ``(k_0, v_0, k_1, ...)`` or
        empty without a cache."""
        cos, sin = self.rope_cos._value, self.rope_sin._value
        new = []
        with framework.functional_mode():
            x = Tensor(x)
            with jax.named_scope("ut_step"):
                for i, layer in enumerate(self.layers):
                    if arenas:
                        x, (k, v) = layer(
                            x, cos, sin,
                            cache=(Tensor(arenas[2 * i]),
                                   Tensor(arenas[2 * i + 1])),
                            pos=Tensor(pos), block_table=Tensor(table))
                        new += [k._value, v._value]
                    else:
                        x, _ = layer(x, cos, sin)
            with jax.named_scope("exit_gate"):
                x = self.norm(x)._value
                gate = self.early_exit_gate
                lam = jax.nn.sigmoid(
                    x.astype(jnp.float32)
                    @ gate.weight._value.astype(jnp.float32)[:, 0]
                    + gate.bias._value.astype(jnp.float32)[0])
        return x, lam, tuple(new)

    def forward(self, input_ids, cache=None, pos=None, block_table=None):
        """Returns ``(h, exit_p, new_cache)``: the last pass's output, the
        exit distribution ``(b, s, total_ut_steps)`` float32, and the cache
        (None without one). ``block_table`` is the slots' table of ONE
        pass; pass ``u`` goes through it plus ``u x num_blocks``."""
        c = self.config
        x = self.embed_tokens(input_ids)
        # every parameter the passes read is an argument of the one op, so
        # that the eager tape sees it; inside, the scan closes over them
        params = [p for layer in self.layers for p in layer.parameters()] \
            + list(self.norm.parameters()) \
            + list(self.early_exit_gate.parameters())
        layers = [] if cache is None else \
            [a for kv in cache["layers"] for a in kv]
        n_arenas = len(layers)
        cached = (pos, block_table) if cache is not None else ()

        def passes(xv, *rest):
            arenas, rest = tuple(rest[:n_arenas]), rest[n_arenas:]
            posv, table = rest[:2] if cached else (None, None)
            values = rest[len(cached):]
            saved = [(p, p._value) for p in params]
            try:
                for p, v in zip(params, values):
                    p._value = v
                blocks = arenas[0].shape[0] // c.total_ut_steps \
                    if arenas else 0

                def body(carry, u):
                    xv, arenas = carry
                    xv, lam, arenas = self._one_pass(
                        xv, arenas, posv,
                        None if table is None else table + u * blocks)
                    return (xv, arenas), lam
                (xv, arenas), lam = jax.lax.scan(
                    body, (xv, arenas),
                    jnp.arange(c.total_ut_steps, dtype=jnp.int32))
            finally:
                for p, v in saved:
                    p._value = v
            return (xv, jnp.moveaxis(exit_distribution(lam), 0, -1)) + arenas

        out = apply_op(passes, x, *layers, *cached, *params)
        h, exit_p, arenas = out[0], out[1], out[2:]
        if cache is None:
            return h, exit_p, None
        new_cache = {"layers": [(arenas[2 * i], arenas[2 * i + 1])
                                for i in range(len(self.layers))],
                     "ut_counters": cache["ut_counters"]}
        row = 0 if int(input_ids.shape[1]) == 1 else 1

        def count(counters, p, table):
            steps = jnp.arange(c.total_ut_steps, dtype=jnp.float32)
            milli = jnp.round(1000.0 * jnp.sum(p * steps, axis=-1))
            live = jnp.any(table != 0, axis=1)[:, None]
            return counters.at[row, 0].add(
                jnp.sum(jnp.where(live, milli, 0.0)).astype(jnp.int32))
        new_cache["ut_counters"] = apply_op(count, cache["ut_counters"],
                                            exit_p, block_table)
        return h, exit_p, new_cache


class OuroForCausalLM(nn.Layer, GenerationMixin):
    # what the programs count into the paged cache's last leaf, an int32
    # (2, 1) array: row 0 by s = 1 calls (decode steps), row 1 by s > 1
    # calls (prefill chunks). name -> how a reader folds it over time
    cache_counters = {"ut_exit_step_milli": "sum"}

    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.model = OuroModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    @property
    def kv_cache_passes(self) -> int:
        """How many times a step runs each weight layer, each time over a
        slice of the layer's arenas of its own: the serving engine reads
        it for ``attn_sites`` and ``ut_steps``, and the paths that move
        ONE block of ``num_blocks`` (hand-off, the fleet's prefix tier,
        tensor parallelism, speculation, export) to refuse by name."""
        return self.config.total_ut_steps

    def init_paged_kv_cache(self, num_blocks: int, block_size: int,
                            kv_int8: bool = False, dtype=None):
        """Per weight layer a ``(k, v)`` pair of ``(total_ut_steps x
        num_blocks, block_size, kv_heads, head_dim)`` arenas — pass ``u``'s
        pages are blocks ``u x num_blocks ..``, block 0 of every slice the
        trash block — and the counters."""
        if kv_int8:
            raise NotImplementedError(
                "kv_int8 over the looped cache: the int8 arena's scale "
                "leaves have no per-pass slices yet")
        c = self.config
        dt = jnp.dtype(dtype or c.dtype)
        shape = (c.total_ut_steps * num_blocks, block_size,
                 c.num_key_value_heads,
                 c.hidden_size // c.num_attention_heads)
        return {"layers": [(Tensor(jnp.zeros(shape, dt)),
                            Tensor(jnp.zeros(shape, dt)))
                           for _ in range(c.num_hidden_layers)],
                "ut_counters": Tensor(jnp.zeros(
                    (2, len(self.cache_counters)), jnp.int32))}

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        """``generate()``'s cache: the paged layout with one block a row
        (``forward`` then reads row r through table ``[[r]]``)."""
        return self.init_paged_kv_cache(batch, max_len, dtype=dtype)

    def forward(self, input_ids, labels=None, cache=None, pos=None,
                pad=None, block_table=None, output_exit_distribution=False):
        """Causal LM forward: logits, or ``(loss, logits)`` with labels, or
        ``(logits, new_cache)`` with a cache. ``output_exit_distribution``
        adds the gate's ``p (b, s, total_ut_steps)`` as a last element."""
        generate_cache = cache is not None and block_table is None
        if generate_cache:
            if pad is not None:
                raise NotImplementedError(
                    "left-padded ragged prompts have no looped-cache path; "
                    "serve ragged batches through the paged engine")
            # row r's one block is block r of every slice (no trash block
            # here; the counters, which take row 0 for dead, are not read)
            block_table = Tensor(jnp.arange(
                int(input_ids.shape[0]), dtype=jnp.int32)[:, None])
        h, exit_p, new_cache = self.model(input_ids, cache=cache, pos=pos,
                                          block_table=block_table)
        with jax.named_scope("lm_head"):
            logits = self.lm_head(h)
        extra = (exit_p,) if output_exit_distribution else ()
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
