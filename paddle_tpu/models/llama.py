"""Llama-2 family — the flagship pretrain model.

Reference parity: PaddleNLP's LlamaForCausalLM trained via Fleet TP×PP
(the BASELINE "Llama-2 7B/13B" config; model lives in the ecosystem repo
— SURVEY §1 requires an in-repo equivalent).

TPU-native design: attention in bshd layout through
scaled_dot_product_attention (Pallas flash kernel on TPU), RoPE precomputed
as buffers, RMSNorm in fp32, SwiGLU MLP. Tensor parallelism = partition
specs on weights (Column/Row pattern over "mp"), sequence parallelism =
constraints over "sep" on the seq dim; the pipeline axis is applied by the
trainer splitting `layers` into stages."""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..nn import functional as F
from ..ops.creation import arange, zeros
from ..ops.manipulation import concat, reshape, transpose
from ..utils import tp_hooks as serving_tp
from ..tensor import Tensor, apply_op
from .generation import GenerationMixin

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaDecoderStack", "llama_tiny_config", "llama_7b_config",
           "llama_13b_config"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    tensor_parallel: bool = True        # attach "mp" partition specs
    sequence_parallel: bool = False     # constrain activations over "sep"
    # "megatron": seq-sharded activations via constraints (GSPMD gathers);
    # "ring": ring flash attention over the sep axis (KV ppermute ring);
    # "ulysses": all-to-all seq<->head swap around attention
    sequence_parallel_mode: str = "megatron"
    pipeline_parallel: bool = False     # stacked trunk + scan/ppermute PP
    pp_num_microbatches: int = 4
    # interleaved (VPP) schedule: each pp stage owns V strided layer
    # chunks, cutting the bubble to (S-1)/(M·V+S-1) — reference
    # PipelineParallelWithInterleave (SURVEY §2.3 PP row). The stacked
    # trunk parameters are stored in VPP chunk order when V > 1 (device-
    # contiguous), so checkpoints are layout-compatible only at equal V.
    virtual_pp: int = 1
    scan_layers: bool = False           # stacked trunk, scan over layers
    recompute: bool = False             # per-layer activation checkpointing
    # "full": save only layer boundaries (min memory, recompute all);
    # "selective": save matmul outputs, recompute elementwise (the
    # standard MFU/memory trade — reference: selective recompute,
    # fleet/recompute refined_recompute — verify)
    recompute_granularity: str = "full"
    # Mistral-class sliding-window causal attention (None = full causal)
    sliding_window: int | None = None
    # chunked fused lm-head + CE for training (never materializes the
    # (tokens, vocab) logits — see incubate/nn/fused_ce.py). Applied on
    # the labels-given path; under an active "mp" mesh axis the
    # vocab-sharded parallel variant runs (ParallelCrossEntropy parity).
    fused_head_ce: bool = True
    fused_head_ce_chunks: int = 16
    dtype: str = "float32"

    def __post_init__(self):
        if self.sequence_parallel_mode not in ("megatron", "ring",
                                               "ulysses"):
            raise ValueError(
                f"unknown sequence_parallel_mode="
                f"{self.sequence_parallel_mode!r}; expected 'megatron', "
                f"'ring', or 'ulysses'")
        if self.sliding_window is not None and self.sliding_window <= 0:
            raise ValueError(
                f"sliding_window={self.sliding_window}; expected a "
                "positive window size or None (disabled)")
        if self.sliding_window is not None and self.sequence_parallel \
                and self.sequence_parallel_mode in ("ring", "ulysses"):
            raise ValueError(
                "sliding_window is not yet supported with ring/ulysses "
                "context parallelism (the CP kernels compute full causal "
                "attention); use sequence_parallel_mode='megatron' or "
                "disable the window")
        if self.recompute_granularity not in ("full", "selective"):
            raise ValueError(
                f"recompute_granularity="
                f"{self.recompute_granularity!r}; expected 'full' or "
                "'selective'")
        if self.pipeline_parallel and \
                self.sequence_parallel_mode in ("ring", "ulysses"):
            raise ValueError(
                "ring/ulysses attention runs its own shard_map and cannot "
                "nest inside the pipeline's manual pp region; use "
                "sequence_parallel_mode='megatron' with pipeline_parallel")
        if self.virtual_pp < 1:
            raise ValueError(f"virtual_pp={self.virtual_pp}; must be >= 1")
        if self.virtual_pp > 1 and not self.pipeline_parallel:
            raise ValueError("virtual_pp > 1 requires pipeline_parallel")
        if self.virtual_pp > 1 and \
                self.num_hidden_layers % self.virtual_pp != 0:
            raise ValueError(
                f"num_hidden_layers={self.num_hidden_layers} not "
                f"divisible by virtual_pp={self.virtual_pp}")


def llama_tiny_config(**kw):
    base = dict(vocab_size=512, hidden_size=128, intermediate_size=384,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=4, max_position_embeddings=256)
    base.update(kw)
    return LlamaConfig(**base)


def llama_7b_config(**kw):
    return LlamaConfig(**kw)


def llama_13b_config(**kw):
    return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                       num_hidden_layers=40, num_attention_heads=40,
                       num_key_value_heads=40, **kw)


def _rope_cache(config: LlamaConfig):
    head_dim = config.hidden_size // config.num_attention_heads
    inv = 1.0 / (config.rope_theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(config.max_position_embeddings, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                       # (S, D/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # (S, D)
    return jnp.cos(emb), jnp.sin(emb)


def _apply_rope(q, k, cos, sin, offset=0):
    """q/k: (b, s, h, d); neox-style rotate-half. One fused Pallas
    launch for q and k on TPU (ops.pallas.fused.fused_rope)."""
    from ..ops.pallas.fused import fused_rope
    s = q.shape[1]
    c = cos[offset:offset + s].astype(q.dtype)
    sn = sin[offset:offset + s].astype(q.dtype)
    return fused_rope(q, k, c, sn)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = nn.Linear(h, h, bias_attr=False)
        self.k_proj = nn.Linear(h, kv, bias_attr=False)
        self.v_proj = nn.Linear(h, kv, bias_attr=False)
        self.o_proj = nn.Linear(h, h, bias_attr=False)
        if config.tensor_parallel:
            for l in (self.q_proj, self.k_proj, self.v_proj):
                l.weight._sharding_spec = P(None, "mp")
            self.o_proj.weight._sharding_spec = P("mp", None)

    def forward(self, x, cos, sin, attn_mask=None, cache=None, pos=None,
                pad=None, block_table=None):
        """cache=(k_cache, v_cache) of (b, max_len, kv_heads, head_dim)
        with ``pos`` the write offset → returns (out, new_cache): the
        autoregressive decode path (reference: fused_multi_transformer's
        cache_kv / PaddleNLP gen_cache — verify). ``pad`` (b,): per-row
        left-pad counts for ragged batched decode. ``block_table``
        (b, max_blocks): paged-KV mode — ``cache`` is then the shared
        block arenas, 2-tuple (k, v) or 4-tuple (k, v, k_scales,
        v_scales) for the int8 arena."""
        b, s, _ = x.shape
        # head counts come from the projection widths (-1), not the
        # config: under tensor-parallel serving (serving/tp.py) the
        # q/k/v weights are column-sharded and each device sees only
        # its contiguous group of heads
        q = reshape(self.q_proj(x), (b, s, -1, self.head_dim))
        k = reshape(self.k_proj(x), (b, s, -1, self.head_dim))
        v = reshape(self.v_proj(x), (b, s, -1, self.head_dim))
        if cache is not None:
            if attn_mask is not None:
                raise ValueError(
                    "pass left-padded prompts via generate("
                    "attention_mask=...) — the KV-cache path takes "
                    "per-row pad counts, not a dense attn_mask")
            from .generation import cached_attention
            fn = functools.partial(
                cached_attention, cos=cos, sin=sin,
                scale=1.0 / math.sqrt(self.head_dim),
                window=self.config.sliding_window)
            if block_table is not None:
                if len(cache) == 4:         # int8 arena + scales
                    ck, cv, sk, sv = cache
                    out, nck, ncv, nsk, nsv = apply_op(
                        lambda qv, kv_, vv, ckv, cvv, skv, svv, posv, \
                        btv: fn(qv, kv_, vv, ckv, cvv, posv,
                                block_table=btv, kv_scales=(skv, svv)),
                        q, k, v, ck, cv, sk, sv, pos, block_table)
                    new_cache = (nck, ncv, nsk, nsv)
                else:
                    ck, cv = cache
                    out, nck, ncv = apply_op(
                        lambda qv, kv_, vv, ckv, cvv, posv, btv: fn(
                            qv, kv_, vv, ckv, cvv, posv,
                            block_table=btv),
                        q, k, v, ck, cv, pos, block_table)
                    new_cache = (nck, ncv)
                out = reshape(out, (b, s, -1))
                out = serving_tp.maybe_gather(
                    out, self.num_heads * self.head_dim)
                out = serving_tp.maybe_reduce(self.o_proj(out))
                return out, new_cache
            ck, cv = cache
            if pad is not None:
                out, nck, ncv = apply_op(
                    lambda qv, kv_, vv, ckv, cvv, posv, padv: fn(
                        qv, kv_, vv, ckv, cvv, posv, pad=padv),
                    q, k, v, ck, cv, pos, pad)
            else:
                out, nck, ncv = apply_op(fn, q, k, v, ck, cv, pos)
            out = reshape(out, (b, s, -1))
            out = serving_tp.maybe_gather(out,
                                          self.num_heads * self.head_dim)
            out = serving_tp.maybe_reduce(self.o_proj(out))
            return out, (nck, ncv)
        q, k = apply_op(lambda qv, kv_: _apply_rope(qv, kv_, cos, sin), q, k)
        out = None
        cfg = self.config
        if (cfg.sequence_parallel
                and cfg.sequence_parallel_mode in ("ring", "ulysses")
                and attn_mask is None):
            from ..distributed.context_parallel import (
                ring_attention_spmd, ulysses_attention_spmd, sep_degree)
            from ..distributed.mesh import get_current_mesh
            mesh = get_current_mesh()
            if sep_degree(mesh) > 1:
                fn = ring_attention_spmd \
                    if cfg.sequence_parallel_mode == "ring" \
                    else ulysses_attention_spmd
                out = apply_op(
                    lambda qv, kv_, vv: fn(qv, kv_, vv, mesh=mesh,
                                           causal=True), q, k, v)
        if out is None:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask, is_causal=attn_mask is None,
                sliding_window=cfg.sliding_window)
        out = reshape(out, (b, s, self.num_heads * self.head_dim))
        return self.o_proj(out)


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, ff = config.hidden_size, config.intermediate_size
        self._ff = ff
        self.gate_proj = nn.Linear(h, ff, bias_attr=False)
        self.up_proj = nn.Linear(h, ff, bias_attr=False)
        self.down_proj = nn.Linear(ff, h, bias_attr=False)
        if config.tensor_parallel:
            self.gate_proj.weight._sharding_spec = P(None, "mp")
            self.up_proj.weight._sharding_spec = P(None, "mp")
            self.down_proj.weight._sharding_spec = P("mp", None)

    def forward(self, x):
        act = F.silu(self.gate_proj(x)) * self.up_proj(x)
        # tensor-parallel serving hooks (no-ops outside a sharded
        # serving trace): exact mode gathers the column-sharded
        # activation in front of the replicated down_proj; psum mode
        # all-reduces the row-parallel partial sums instead
        act = serving_tp.maybe_gather(act, self._ff)
        return serving_tp.maybe_reduce(self.down_proj(act))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.mlp = LlamaMLP(config)
        self._seq_parallel = config.sequence_parallel

    def forward(self, x, cos, sin, attn_mask=None, cache=None, pos=None,
                pad=None, block_table=None):
        # named scopes are metadata: the trace names a layer's halves,
        # the compiled program is the same program (test-pinned)
        if cache is not None:
            with jax.named_scope("attn"):
                a, new_cache = self.self_attn(self.input_layernorm(x), cos,
                                              sin, attn_mask, cache=cache,
                                              pos=pos, pad=pad,
                                              block_table=block_table)
                h = x + a
            with jax.named_scope("mlp"):
                out = h + self.mlp(self.post_attention_layernorm(h))
            return out, new_cache
        with jax.named_scope("attn"):
            h = x + self.self_attn(self.input_layernorm(x), cos, sin,
                                   attn_mask)
        with jax.named_scope("mlp"):
            out = h + self.mlp(self.post_attention_layernorm(h))
        if self._seq_parallel:
            from ..distributed.fleet.meta_parallel import _constrain
            out = _constrain(out, P(None, "sep", None))
        return out


class LlamaDecoderStack(nn.Layer):
    """Stacked decoder trunk: ONE prototype layer supplies the structure;
    parameters are stacked (L, ...) Parameters so the trunk runs as a
    ``lax.scan`` over layers (faster compiles than an unrolled python
    loop) and — when a "pp" mesh axis is active — as the scan+ppermute
    pipeline of paddle_tpu.distributed.pipeline (reference:
    fleet/meta_parallel/pipeline_parallel.py — verify)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        L = config.num_hidden_layers
        proto = LlamaDecoderLayer(config)
        # structure donor only — bypass registration so its (per-layer
        # shaped) params never appear in named_parameters
        object.__setattr__(self, "_proto", proto)
        names, stacks, specs = [], {}, {}
        for i in range(L):
            layer = proto if i == 0 else LlamaDecoderLayer(config)
            for n, p in layer.named_parameters():
                if i == 0:
                    names.append(n)
                    stacks[n] = []
                    specs[n] = getattr(p, "_sharding_spec", None)
                stacks[n].append(p._value)
        self._pnames = names
        lead = "pp" if config.pipeline_parallel else None
        V = config.virtual_pp
        for n in names:
            from ..tensor import Parameter
            vals = stacks[n]
            if isinstance(vals[0], jax.ShapeDtypeStruct):
                # abstract construction (utils/scale.py AOT scale check)
                if V > 1:
                    stacked = jax.ShapeDtypeStruct(
                        (V, L // V, *vals[0].shape), vals[0].dtype)
                else:
                    stacked = jax.ShapeDtypeStruct(
                        (len(vals), *vals[0].shape), vals[0].dtype)
            else:
                stacked = jnp.stack(vals)
                if V > 1:
                    # VPP storage layout (V, L/V, ...): sharding dim 1
                    # over "pp" into S blocks of U = L/(S·V) rows gives
                    # each stage exactly its interleaved chunks
                    # {s, S+s, ...} with NO per-step weight movement
                    stacked = stacked.reshape(V, L // V,
                                              *stacked.shape[1:])
            p = Parameter(stacked)
            base = specs[n]
            if V > 1:
                p._sharding_spec = P(None, lead, *tuple(base or ()))
            elif base is not None:
                p._sharding_spec = P(lead, *tuple(base))
            elif lead is not None:
                p._sharding_spec = P(lead)
            self.add_parameter(n.replace(".", "__"), p)
            stacks[n] = None

    def forward(self, x, cos, sin, attn_mask=None):
        leaves = [self._parameters[n.replace(".", "__")]
                  for n in self._pnames]
        mask_val = attn_mask._value if isinstance(attn_mask, Tensor) \
            else attn_mask

        def pure(xv, *leafvals):
            return self._pure_forward(leafvals, xv, cos, sin, mask_val)
        return apply_op(pure, x, *leaves)

    def _layer_fwd(self, proto_params, slices, hv, cos, sin, mask):
        from .. import framework
        names = self._pnames
        saved = [(proto_params[n], proto_params[n]._value) for n in names]
        try:
            for n, v in zip(names, slices):
                proto_params[n]._value = v
            with framework.functional_mode():
                out = self._proto(
                    Tensor(hv), cos, sin,
                    Tensor(mask) if mask is not None else None)
            return out._value
        finally:
            for t, v in saved:
                t._value = v

    def _pure_forward(self, leafvals, xv, cos, sin, mask):
        from ..distributed.mesh import get_current_mesh
        from ..distributed.pipeline import (num_pipeline_stages,
                                            pipeline_spmd,
                                            pipeline_spmd_interleaved,
                                            split_microbatches,
                                            merge_microbatches)
        cfg = self.config
        V = cfg.virtual_pp
        proto_params = dict(self._proto.named_parameters())
        fwd = functools.partial(self._layer_fwd, proto_params)
        if cfg.recompute:
            if cfg.recompute_granularity == "selective":
                policy = jax.checkpoint_policies \
                    .dots_with_no_batch_dims_saveable
                fwd = jax.checkpoint(fwd, policy=policy)
            else:
                fwd = jax.checkpoint(fwd)

        mesh = get_current_mesh()
        S = num_pipeline_stages(mesh) if cfg.pipeline_parallel else 1
        if S > 1:
            L = cfg.num_hidden_layers
            if L % (S * V) != 0:
                raise ValueError(f"num_hidden_layers={L} not divisible by "
                                 f"pp degree {S} x virtual_pp {V}")
            x_mb = split_microbatches(xv, cfg.pp_num_microbatches)
            has_mask = mask is not None
            if V > 1:
                if has_mask:
                    raise ValueError(
                        "attn_mask is not supported with virtual_pp > 1 "
                        "(the interleaved schedule carries no per-"
                        "microbatch extras); use virtual_pp=1 or drop "
                        "the mask")
                # storage (V, L/V, ...) -> (S, V, U, ...): stage s's
                # rows are already local (dim 1 sharded over pp)
                U = L // (S * V)
                stacked = tuple(
                    jnp.moveaxis(v.reshape(V, S, U, *v.shape[2:]), 0, 1)
                    for v in leafvals)

                def chunk_fn(local, h, *rest):
                    c, s_ = rest[-2], rest[-1]

                    def body(hh, sl):
                        return fwd(sl, hh, c, s_, None), None
                    out, _ = jax.lax.scan(body, h, local)
                    return out

                y_mb = pipeline_spmd_interleaved(
                    chunk_fn, stacked, x_mb, mesh=mesh, extras=(cos, sin))
                return merge_microbatches(y_mb)
            stacked = tuple(v.reshape(S, L // S, *v.shape[1:])
                            for v in leafvals)
            mb_extras = ()
            if has_mask:
                mb_extras = (split_microbatches(mask,
                                                x_mb.shape[0]),)

            def stage_fn(local, h, *rest):
                mk = rest[0] if has_mask else None
                c, s_ = rest[-2], rest[-1]

                def body(hh, sl):
                    return fwd(sl, hh, c, s_, mk), None
                out, _ = jax.lax.scan(body, h, local)
                return out

            y_mb = pipeline_spmd(stage_fn, stacked, x_mb, mesh=mesh,
                                 mb_extras=mb_extras, extras=(cos, sin))
            return merge_microbatches(y_mb)

        if V > 1:      # no active pp axis: flatten VPP storage back to
            leafvals = tuple(v.reshape(-1, *v.shape[2:])   # layer order
                             for v in leafvals)

        def body(hh, sl):
            return fwd(sl, hh, cos, sin, mask), None
        out, _ = jax.lax.scan(body, xv, tuple(leafvals))
        return out


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        if config.tensor_parallel:
            self.embed_tokens.weight._sharding_spec = P("mp", None)
        if config.pipeline_parallel or config.scan_layers:
            self.layers = LlamaDecoderStack(config)
        else:
            self.layers = nn.LayerList(
                [LlamaDecoderLayer(config)
                 for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = _rope_cache(config)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None, cache=None, pos=None,
                pad=None, block_table=None):
        x = self.embed_tokens(input_ids)
        cos, sin = self.rope_cos._value, self.rope_sin._value
        if cache is not None:
            if isinstance(self.layers, LlamaDecoderStack):
                raise ValueError(
                    "KV-cache decode is not supported with the stacked "
                    "pipeline/scan trunk; build the model with "
                    "pipeline_parallel=False, scan_layers=False for "
                    "generation")
            new_cache = []
            for layer, layer_cache in zip(self.layers, cache):
                x, nc = layer(x, cos, sin, attn_mask, cache=layer_cache,
                              pos=pos, pad=pad, block_table=block_table)
                new_cache.append(nc)
            return self.norm(x), new_cache
        if isinstance(self.layers, LlamaDecoderStack):
            x = self.layers(x, cos, sin, attn_mask)
        else:
            for layer in self.layers:
                x = layer(x, cos, sin, attn_mask)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)
            if config.tensor_parallel:
                self.lm_head.weight._sharding_spec = P(None, "mp")

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        """Preallocated per-layer (k, v) cache pytree for generate()."""
        c = self.config
        head_dim = c.hidden_size // c.num_attention_heads
        dt = jnp.dtype(dtype or c.dtype)
        shape = (batch, max_len, c.num_key_value_heads, head_dim)
        return [(Tensor(jnp.zeros(shape, dt)), Tensor(jnp.zeros(shape, dt)))
                for _ in range(c.num_hidden_layers)]

    def init_paged_kv_cache(self, num_blocks: int, block_size: int,
                            kv_int8: bool = False, dtype=None):
        """Paged-KV arenas for the serving engine: per layer a shared
        ``(num_blocks, block_size, kv_heads, head_dim)`` (k, v) pair —
        block 0 is the reserved trash block — or, with ``kv_int8``, the
        int8 code arenas plus ``(num_blocks, block_size, kv_heads)``
        fp32 per-vector absmax scales (4-tuple per layer)."""
        c = self.config
        head_dim = c.hidden_size // c.num_attention_heads
        shape = (num_blocks, block_size, c.num_key_value_heads, head_dim)
        if kv_int8:
            sshape = shape[:-1]
            return [(Tensor(jnp.zeros(shape, jnp.int8)),
                     Tensor(jnp.zeros(shape, jnp.int8)),
                     Tensor(jnp.zeros(sshape, jnp.float32)),
                     Tensor(jnp.zeros(sshape, jnp.float32)))
                    for _ in range(c.num_hidden_layers)]
        dt = jnp.dtype(dtype or c.dtype)
        return [(Tensor(jnp.zeros(shape, dt)), Tensor(jnp.zeros(shape, dt)))
                for _ in range(c.num_hidden_layers)]

    def forward(self, input_ids, labels=None, attn_mask=None, cache=None,
                pos=None, pad=None, block_table=None):
        """Causal LM forward. labels given → (loss, logits); NOTE: with
        ``config.fused_head_ce`` (default) the logits slot is ``None`` —
        the fused head never materializes them. Set
        ``fused_head_ce=False`` if the training path must also return
        logits. labels=None (eval/generate) always returns real logits.
        ``pad`` (b,): per-row left-pad counts on the KV-cache path."""
        if cache is not None:
            h, new_cache = self.llama(input_ids, attn_mask, cache=cache,
                                      pos=pos, pad=pad,
                                      block_table=block_table)
        else:
            h = self.llama(input_ids, attn_mask)
        c = self.config
        if cache is None and labels is not None and c.fused_head_ce:
            # training fast path: chunked fused head+CE — the full
            # (tokens, vocab) logits tensor never exists. Under tensor
            # parallelism the vocab-sharded variant runs (each mp rank
            # scans its own shard; one psum/pmax lse merge — VERDICT r2
            # missing #5); otherwise the single-shard kernel.
            from ..incubate.nn.functional import (
                fused_linear_cross_entropy,
                parallel_fused_linear_cross_entropy)
            w = self.lm_head.weight if self.lm_head is not None \
                else self.llama.embed_tokens.weight
            if self.lm_head is not None:
                # nn.Linear stores (in, out); the kernel wants (V, D)
                from ..ops.manipulation import transpose
                w = transpose(w, (1, 0))
            with jax.named_scope("lm_head"):
                if c.tensor_parallel:
                    # resolves to the single-shard kernel when no mp
                    # mesh axis is active
                    loss = parallel_fused_linear_cross_entropy(
                        h, w, labels, axis="mp",
                        num_chunks=c.fused_head_ce_chunks)
                else:
                    loss = fused_linear_cross_entropy(
                        h, w, labels, num_chunks=c.fused_head_ce_chunks)
            return loss, None
        with jax.named_scope("lm_head"):
            if self.lm_head is not None:
                logits = self.lm_head(h)
            else:
                from ..ops.math import matmul
                logits = matmul(h, self.llama.embed_tokens.weight,
                                transpose_y=True)
        if cache is not None:
            # tensor-parallel serving: the vocab-sharded lm_head shards
            # gather into full logits through the collectives all-gather
            # path (no-op outside a sharded serving trace / tied-embed)
            logits = serving_tp.maybe_gather_logits(logits,
                                                    c.vocab_size)
            return logits, new_cache
        if labels is None:
            return logits
        # unfused-head loss: flatten to (tokens, vocab) so the CE sees
        # one row axis; with PT_FUSION_PASSES=1 (default off)
        # F.cross_entropy routes these rows through the one-pass
        # softmax-xent kernel (ops/pallas/xent) — the (tokens, vocab)
        # log-prob/one-hot intermediates are never materialized
        from ..ops.manipulation import reshape
        vocab = logits.shape[-1]
        loss = F.cross_entropy(reshape(logits, (-1, vocab)),
                               reshape(labels, (-1,)), reduction="mean")
        return loss, logits

    def num_params(self):
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len):
        """~6N + attention flops per token (for MFU accounting)."""
        n = self.num_params()
        c = self.config
        attn = 12 * c.num_hidden_layers * c.hidden_size * seq_len
        return 6 * n + attn
