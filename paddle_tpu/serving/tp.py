"""Tensor-parallel serving: the ONE compiled decode block sharded over
a TPU mesh.

The slot-pool engine (engine.py / paging.py) runs its single compiled
decode program on one chip, so the max servable model is one chip's HBM
and decode bandwidth is one chip's. This module shards that same
program — and the chunked-prefill program — over a
``jax.sharding.Mesh`` via ``shard_map``:

- the KV cache is sharded on the **kv-head axis**: the dense
  ``(S, max_len, kvh, d)`` per-slot rows AND the paged
  ``(num_blocks, block_size, kvh, d)`` arena (plus its int8 scale
  arrays) split dim 2 across the TP axes, so per-chip KV HBM shrinks by
  the TP degree — the single-chip ceiling the ROADMAP names;
- attention weights are column-sharded (q/k/v out dims — each device
  owns a contiguous group of heads aligned with its kv-head shard),
  MLP gate/up column-sharded, lm_head vocab-sharded; per-slot state
  (pos/live/keys/sampling params/block tables) is replicated;
- the final logits are produced through the
  ``distributed/collectives`` all-gather path: the hierarchical plan is
  auto-selected from the mesh topology (``plan_hierarchy``), so a
  reduction spanning two mesh levels rides the HiCCL inner/outer
  decomposition.

Two weight layouts, selected by ``TPConfig.mode``:

- ``"exact"`` (default): o_proj / down_proj / embedding stay
  REPLICATED and the sharded activations are all-gathered in front of
  them. Every cross-device collective is then pure data movement
  (gather of independent head/column slices), so sharded greedy AND
  seeded-sampled streams are **bit-identical** to the 1-chip engine —
  the serving bit-identity harness is the verifier.
- ``"psum"``: the Megatron row-parallel layout — o_proj / down_proj
  are row-sharded and the hidden state is all-reduced per layer.
  Sums reassociate, so this mode is *not* bit-identical; in exchange
  every large weight is sharded. ``TPConfig.int8`` compresses the
  hidden-state all-reduce with the EQuARX wire format
  (``collectives.quantized``); the worst-case error is
  runtime-queryable via :meth:`engine.tp_int8_error_bound` and gated
  by ``TPConfig.int8_max_error`` — the first decode block probes the
  bound against the live cache/state and refuses to run over budget.

Everything is default-off: pass ``tp=TPConfig(...)`` (or ``tp=True``)
to ``ContinuousBatchingEngine`` / the paged engine, or set
``PT_SERVING_TP=1`` (axes via ``PT_SERVING_TP_AXES``, comma-separated
mesh axis names, default ``"mp"``; ``PT_SERVING_TP_MODE`` /
``PT_SERVING_TP_INT8`` select the layout). An explicitly passed
backend is never rerouted by the env flags.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.collectives.hierarchical import plan_hierarchy
from ..distributed.mesh import get_current_mesh
from ..observability import metrics as _om
from ..utils import tp_hooks
# the trace-time hooks the model's forward calls live in
# utils/tp_hooks.py (dependency-light on purpose: models must not
# import the serving package at module level — see that docstring);
# re-exported here so TP users find them next to the backends
from ..utils.tp_hooks import (current_tp, maybe_gather,  # noqa: F401
                              maybe_gather_logits, maybe_reduce)
from ..utils.flags import env_bool, env_str
from .engine import (ModelStepBackend, build_slot_block_fn,
                     build_slot_prefill_fn)
from .paging import PagedModelStepBackend, refuse_looped_cache

__all__ = ["TPConfig", "resolve_tp_config", "ShardedModelStepBackend",
           "ShardedPagedStepBackend"]

# mesh-shape gauges (no-ops until metrics.enable()/PT_METRICS): the
# observability satellite — per-collective bytes/calls already ride
# pt_collectives_* (noted per dispatched block below); these record the
# topology the decode block is sharded over
_M_TP_DEVICES = _om.gauge(
    "pt_serving_tp_devices",
    "devices the serving decode block is sharded over (1 = TP off)")
_M_TP_AXIS = _om.gauge(
    "pt_serving_tp_mesh_axis_size",
    "mesh axis sizes of the serving TP mesh", labels=("axis",))


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TPConfig:
    """How to shard the serving decode block.

    ``axes``: mesh axis names the weights/KV heads split over (the
    hierarchical collective plan is derived from their mesh order;
    degree-1 axes are dropped). ``mode``: ``"exact"`` | ``"psum"`` (see
    module docstring). ``int8``: compress the psum-mode hidden-state
    all-reduce; ``int8_max_error`` arms the runtime gate on the
    queryable EQuARX bound. ``mesh``: defaults to the process-current
    mesh (``distributed.mesh.get_current_mesh``)."""
    axes: Tuple[str, ...] = ("mp",)
    mode: str = "exact"
    int8: bool = False
    int8_max_error: Optional[float] = None
    mesh: Optional[Mesh] = None

    def __post_init__(self):
        if self.mode not in ("exact", "psum"):
            raise ValueError(f"TPConfig.mode={self.mode!r}; expected "
                             "'exact' or 'psum'")
        if self.int8 and self.mode != "psum":
            raise ValueError(
                "TPConfig.int8 compresses the hidden-state all-reduce, "
                "which only exists in mode='psum' (exact mode has no "
                "reduction to compress)")
        if isinstance(self.axes, str):
            object.__setattr__(self, "axes", (self.axes,))
        else:
            object.__setattr__(self, "axes", tuple(self.axes))


def resolve_tp_config(tp) -> Optional[TPConfig]:
    """Normalize the engine's ``tp`` argument: TPConfig pass-through,
    ``True`` -> defaults, ``False`` -> off, ``None`` -> the
    ``PT_SERVING_TP`` env knobs (routed through the flags helpers)."""
    if isinstance(tp, TPConfig):
        return tp
    if tp is True:
        return TPConfig()
    if tp is False:
        return None
    if tp is not None:
        raise ValueError(f"tp={tp!r}: pass a TPConfig, True/False, or "
                         "None (env-controlled)")
    if not env_bool("PT_SERVING_TP"):
        return None
    axes = tuple(a.strip() for a in
                 env_str("PT_SERVING_TP_AXES", "mp").split(",")
                 if a.strip())
    return TPConfig(axes=axes or ("mp",),
                    mode=env_str("PT_SERVING_TP_MODE", "exact"),
                    int8=env_bool("PT_SERVING_TP_INT8"))


# ---------------------------------------------------------------------------
# backend mixin: spec derivation + shard_map wrapping
# ---------------------------------------------------------------------------

def _param_pspec(name: str, sharding_spec, mode: str,
                 axes: Tuple[str, ...]) -> P:
    """Serving partition spec for one parameter, derived from the
    training-time ``_sharding_spec`` the model already attaches
    (llama's Column/Row pattern over "mp"):

    - out-dim ("column") shards stay sharded in both modes — their
      gathers are exact;
    - in-dim ("row") shards (o_proj/down_proj) replicate in exact mode
      and stay row-sharded in psum mode;
    - the embedding table always replicates (a sharded-vocab lookup
      needs mask+psum semantics the decode block does not carry).
    """
    if sharding_spec is None:
        return P()
    dims = tuple(sharding_spec)
    idx = [i for i, d in enumerate(dims)
           if d == "mp" or (isinstance(d, (tuple, list)) and "mp" in d)]
    if not idx:
        return P()
    if "embed_tokens" in name or "embedding" in name:
        return P()
    i = idx[0]
    if mode == "exact" and i == 0:
        return P()                    # row-parallel weight: replicate
    return P(*[axes if j == i else None for j in range(len(dims))])


class _TPBackendMixin:
    """Shared TP plumbing for the dense and paged sharded backends."""

    def _setup_tp(self, model, tp: TPConfig):
        mesh = tp.mesh if tp.mesh is not None else get_current_mesh()
        if mesh is None:
            raise ValueError(
                "tensor-parallel serving needs a mesh: build one via "
                "HybridCommunicateGroup/build_device_mesh (sets the "
                "current mesh) or pass TPConfig(mesh=...)")
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        for a in tp.axes:
            if a not in sizes:
                raise ValueError(f"TP axis {a!r} not in mesh axes "
                                 f"{tuple(sizes)}")
        plan = plan_hierarchy(tp.axes, mesh)
        if plan.total_size < 2:
            raise ValueError(
                f"TP axes {tp.axes} have total degree "
                f"{plan.total_size} on this mesh — nothing to shard "
                "(drop tp= or grow the mesh)")
        self.tp = tp
        self.tp_mesh = mesh
        self.tp_plan = plan
        self.tp_degree = plan.total_size
        self._tp_spec = tp_hooks.TPSpec(plan=plan,
                                        degree=plan.total_size,
                                        mode=tp.mode, int8=tp.int8)
        # parameter specs, aligned with self._pv construction order
        named = list(model.named_parameters())
        self._pv_pspecs = [
            _param_pspec(n, getattr(p, "_sharding_spec", None),
                         tp.mode, plan.axes) for n, p in named]
        self._bv_pspecs = [P() for _ in self._bv]
        sharded = [(n, s, p) for (n, p), s in zip(named, self._pv_pspecs)
                   if s != P()]
        if not sharded:
            raise ValueError(
                f"{type(model).__name__} carries no 'mp' partition "
                "specs — build it with tensor_parallel=True (or attach "
                "_sharding_spec to its weights) before sharding the "
                "decode block")
        d = self.tp_degree
        for n, s, p in sharded:
            dim = next(i for i, e in enumerate(tuple(s)) if e)
            if p._value.shape[dim] % d:
                raise ValueError(
                    f"parameter {n} dim {dim} ({p._value.shape[dim]}) "
                    f"is not divisible by the TP degree {d}")
        cfg = getattr(model, "config", None)
        for attr in ("num_attention_heads", "num_key_value_heads"):
            hv = getattr(cfg, attr, None)
            if hv is not None and hv % d:
                raise ValueError(
                    f"{attr}={hv} not divisible by TP degree {d} — "
                    "head-axis sharding needs whole heads per device")
        if self._qmeta:
            # weight-only quant composes with the exact layout only:
            # per-shard scales ride the weight's out-dim axes (a
            # row-sharded psum weight would split int4 nibble packing
            # and group boundaries on the in dim — refused, not
            # silently de-quantized)
            if tp.mode != "exact":
                raise NotImplementedError(
                    "weight-only serving quant composes with tp "
                    "mode='exact' only — row-parallel (psum) shards "
                    "split the quantized in dim; drop quant= or use "
                    "mode='exact'")
            from .quant import scale_pspec
            for i in self._qmeta:
                scales = self._pv[i][1]
                self._pv_pspecs[i] = (self._pv_pspecs[i],
                                      scale_pspec(self._pv_pspecs[i],
                                                  scales))
        # the KV cache shards its kv-head dim (dim 2 of every pool leaf,
        # 4D arenas/rows and 3D int8 scale arrays alike)
        for shape, _ in self.pool_specs:
            if shape[2] % d:
                raise ValueError(
                    f"KV cache kv-head dim ({shape[2]}) not divisible "
                    f"by TP degree {d}")
        self._cache_pspecs = tuple(
            P(None, None, plan.axes) if len(shape) == 3
            else P(None, None, plan.axes, None)
            for shape, _ in self.pool_specs)
        self._state_pspecs = jax.tree.map(lambda _: P(),
                                          super().init_state())
        # shard-commit the weights once (uncommitted arrays would be
        # re-laid-out on every dispatch; quantized entries are
        # (codes, scales) tuples with matching spec tuples)
        def _commit(v, s):
            if isinstance(s, tuple) and not isinstance(s, P):
                return tuple(jax.device_put(a, NamedSharding(mesh, ps))
                             for a, ps in zip(v, s))
            return jax.device_put(v, NamedSharding(mesh, s))
        self._pv = [_commit(v, s)
                    for v, s in zip(self._pv, self._pv_pspecs)]
        self._bv = [jax.device_put(v, NamedSharding(mesh, P()))
                    for v in self._bv]
        self._int8_gate_pending = tp.int8 and \
            tp.int8_max_error is not None
        self._bound_jit = None
        self._note_mesh_metrics()
        self._setup_collective_accounting(model)

    # -- observability ----------------------------------------------------
    def _note_mesh_metrics(self):
        if not _om.enabled():
            return
        _M_TP_DEVICES.set(self.tp_degree)
        sizes = dict(zip(self.tp_mesh.axis_names,
                         self.tp_mesh.devices.shape))
        for a in self.tp_plan.axes:
            _M_TP_AXIS.set(sizes[a], axis=a)

    def _setup_collective_accounting(self, model):
        """Static per-TOKEN collective payloads. The in-graph gathers
        never cross the host-level ``collectives`` wrappers (where the
        pt_collectives_* families are normally noted), so the backend
        accounts them here, derived from the model dims: a decode step
        moves S tokens (one per slot), a dense prefill bucket_len
        tokens, a prefill chunk prefill_chunk tokens — each compiled
        dispatch fires 2L+1 collectives regardless of token count.
        Noted under mode="tp_graph" with op="tp_block" (decode) vs
        op="tp_prefill", so per-decode-step rates never mix in
        prefill traffic."""
        cfg = getattr(model, "config", None)
        self._tp_bytes_tok = 0
        self._tp_calls_dispatch = 0
        if cfg is None:
            return
        h = cfg.hidden_size
        ff = cfg.intermediate_size
        V = cfg.vocab_size
        L = cfg.num_hidden_layers
        if self.tp.mode == "exact":
            # per token: L head-gathers (h) + L act-gathers (ff) + the
            # logits gather (V), fp32
            self._tp_bytes_tok = 4 * (L * (h + ff) + V)
        else:
            # psum: L attention + L mlp all-reduces (h) per token +
            # logits gather; int8 hops carry ~(1 + 4/bucket) B/element
            per_el = 1.03 if self.tp.int8 else 4
            self._tp_bytes_tok = int(2 * L * h * per_el + 4 * V)
        self._tp_calls_dispatch = 2 * L + 1

    def _note_collectives(self, op: str, dispatches: int, tokens: int):
        if not _om.enabled() or not self._tp_calls_dispatch:
            return
        mode = "tp_graph" + (",int8" if self.tp.int8 else "")
        _om.counter("pt_collectives_calls_total",
                    "host-level collective dispatches",
                    labels=("op", "mode")).inc(
            self._tp_calls_dispatch * dispatches, op=op, mode=mode)
        _om.counter("pt_collectives_bytes_total",
                    "payload bytes handed to collectives",
                    labels=("op", "mode")).inc(
            self._tp_bytes_tok * tokens, op=op, mode=mode)
        self._note_mesh_metrics()

    # -- shard_map plumbing -----------------------------------------------
    def _shard_jit(self, fn, in_specs, out_specs, donate=()):
        spec = self._tp_spec

        # the sharded program keeps ``fn``'s name: the benchmark reads
        # device time by ``engine.DECODE_PROGRAM`` whatever the backend
        @functools.wraps(fn)
        def tp_fn(*args):
            with tp_hooks.active(spec):
                return fn(*args)

        return jax.jit(jax.shard_map(tp_fn, mesh=self.tp_mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs,
                                     check_vma=False),
                       donate_argnums=donate)

    def _replicate(self, tree):
        sh = NamedSharding(self.tp_mesh, P())
        return jax.tree.map(lambda a: jax.device_put(a, sh), tree)

    def pool_cache(self):
        return tuple(
            jax.device_put(jnp.zeros(shape, dtype),
                           NamedSharding(self.tp_mesh, s))
            for (shape, dtype), s in zip(self.pool_specs,
                                         self._cache_pspecs))

    def init_state(self):
        return self._replicate(super().init_state())

    def commit_arrays(self, cache_flat, state):
        """Re-commit restored host arrays onto the mesh (snapshot
        restore hands plain ``jnp.asarray`` values)."""
        cache = tuple(
            jax.device_put(c, NamedSharding(self.tp_mesh, s))
            for c, s in zip(cache_flat, self._cache_pspecs))
        return cache, self._replicate(state)

    # -- int8 bound probe + gate ------------------------------------------
    def _int8_bound_fn(self):
        """One decode STEP (not a block) with the bound sink armed:
        returns the worst runtime EQuARX bound over every int8 hop of
        the live cache/state. A separate tiny program — the decode
        block itself stays unchanged and its compile count stays 1."""
        spec = self._tp_spec
        pure, paged = self._pure, isinstance(self,
                                             PagedModelStepBackend)

        def probe(pv, bv, cache_flat, state):
            sink: list = []
            tp_hooks._BOUND_SINK = sink
            try:
                with tp_hooks.active(spec):
                    if paged:
                        tbl = jnp.where(state["live"][:, None],
                                        state["table"], 0)
                        logp, _ = pure(pv, bv, state["tok"][:, None],
                                       cache_flat, state["pos"], None,
                                       None, tbl)
                    else:
                        logp, _ = pure(pv, bv, state["tok"][:, None],
                                       cache_flat, state["pos"], None,
                                       state["pad"])
            finally:
                tp_hooks._BOUND_SINK = None
            del logp
            if not sink:
                return jnp.float32(0.0)
            return jnp.max(jnp.stack(sink))

        return self._shard_jit(
            probe,
            in_specs=(self._pv_pspecs, self._bv_pspecs,
                      self._cache_pspecs, self._state_pspecs),
            out_specs=P())

    def tp_int8_error_bound(self, cache_flat, state) -> float:
        """Runtime worst-case elementwise |int8 all-reduce - fp32| over
        the decode step's hidden-state hops, from the LIVE cache/state
        (0.0 when the int8 hop is off)."""
        if not self.tp.int8:
            return 0.0
        if self._bound_jit is None:
            self._bound_jit = self._int8_bound_fn()
        return float(self._bound_jit(self._pv, self._bv, cache_flat,
                                     state))

    def _check_int8_gate(self, cache_flat, state):
        if not self._int8_gate_pending:
            return
        bound = self.tp_int8_error_bound(cache_flat, state)
        limit = self.tp.int8_max_error
        if bound > limit:
            # the gate stays ARMED: a caller that catches this and
            # re-drives the engine gets refused again, not silently
            # served over budget
            raise RuntimeError(
                f"int8 hidden-state all-reduce error bound {bound:.3e} "
                f"exceeds TPConfig.int8_max_error={limit:.3e} — run "
                "fp32 (int8=False) or raise the budget")
        self._int8_gate_pending = False


# ---------------------------------------------------------------------------
# sharded backends
# ---------------------------------------------------------------------------

class ShardedModelStepBackend(_TPBackendMixin, ModelStepBackend):
    """Dense slot-pool backend with the decode block and per-bucket
    prefills sharded over the TP mesh. Exact-mode streams are
    bit-identical to :class:`ModelStepBackend` on one chip."""

    def __init__(self, model, num_slots: int, max_len: int,
                 decode_block: int, tp: TPConfig, quant=None):
        super().__init__(model, num_slots, max_len, decode_block,
                         quant=quant)
        self._setup_tp(model, tp)
        # local-shape row specs: the prefill program zero-fills its
        # fresh cache row INSIDE shard_map, where shapes are per-device
        d = self.tp_degree
        self._row_specs_local = tuple(
            (shape[:2] + (shape[2] // d,) + shape[3:], dtype)
            for shape, dtype in self.row_specs)
        self._row_out_pspecs = tuple(
            P(None, None, self.tp_plan.axes) if len(shape) == 3
            else P(None, None, self.tp_plan.axes, None)
            for shape, _ in self.row_specs)
        self._block_jit = self._shard_jit(
            build_slot_block_fn(self._pure, self.block_size,
                                self.decode_traces),
            in_specs=(self._pv_pspecs, self._bv_pspecs,
                      self._cache_pspecs, self._state_pspecs),
            out_specs=(self._cache_pspecs, self._state_pspecs,
                       P(), P(), P()),
            donate=(2, 3))
        self._prefill_jits = {}

    def decode_block(self, cache_flat, state):
        self._check_int8_gate(cache_flat, state)
        out = self._block_jit(self._pv, self._bv, cache_flat, state)
        self._note_collectives("tp_block", self.block_size,
                               self.block_size * self.num_slots)
        return out

    def prefill(self, bucket_len, ids, pad, key, temp, topk, topp):
        fn = self._prefill_jits.get(bucket_len)
        if fn is None:
            fn = self._shard_jit(
                build_slot_prefill_fn(self._pure,
                                      self._row_specs_local),
                in_specs=(self._pv_pspecs, self._bv_pspecs,
                          P(), P(), P(), P(), P(), P()),
                out_specs=(P(), self._row_out_pspecs))
            self._prefill_jits[bucket_len] = fn
        out = fn(self._pv, self._bv, ids, pad, key, temp, topk, topp)
        self._note_collectives("tp_prefill", 1, bucket_len)
        return out


class ShardedPagedStepBackend(_TPBackendMixin, PagedModelStepBackend):
    """Paged twin: the shared KV arena (fp32 or int8 codes + scales)
    shards its kv-head dim, block tables stay replicated in-state, and
    both the decode block and the ONE chunked-prefill program run under
    ``shard_map``. Exact-mode paged streams are bit-identical to the
    1-chip paged engine (and therefore to dense / ``generate()``)."""

    def __init__(self, model, num_slots: int, max_len: int,
                 decode_block: int, block_size: int, num_blocks: int,
                 kv_int8: bool, prefill_chunk: int, tp: TPConfig,
                 quant=None):
        from .engine import build_paged_chunk_fn
        refuse_looped_cache(model, "tensor-parallel serving")
        super().__init__(model, num_slots, max_len, decode_block,
                         block_size, num_blocks, kv_int8, prefill_chunk,
                         quant=quant)
        self._setup_tp(model, tp)
        self._block_jit = self._shard_jit(
            build_slot_block_fn(self._pure, self.block_size,
                                self.decode_traces, paged=True),
            in_specs=(self._pv_pspecs, self._bv_pspecs,
                      self._cache_pspecs, self._state_pspecs),
            out_specs=(self._cache_pspecs, self._state_pspecs,
                       P(), P(), P()),
            donate=(2, 3))
        self._chunk_jit = self._shard_jit(
            build_paged_chunk_fn(self._pure, prefill_chunk,
                                 self.prefill_traces),
            in_specs=(self._pv_pspecs, self._bv_pspecs, P(),
                      self._cache_pspecs, P(), P(), P(), P(), P(), P(),
                      P()),
            out_specs=(P(), self._cache_pspecs),
            donate=(3,))

    def decode_block(self, cache_flat, state):
        self._check_int8_gate(cache_flat, state)
        out = self._block_jit(self._pv, self._bv, cache_flat, state)
        self._note_collectives("tp_block", self.block_size,
                               self.block_size * self.num_slots)
        return out

    def prefill_chunk(self, ids, cache_flat, table_row, start_pos,
                      n_valid, key, temp, topk, topp):
        out = self._chunk_jit(self._pv, self._bv, ids, cache_flat,
                              table_row, start_pos, n_valid, key, temp,
                              topk, topp)
        self._note_collectives("tp_prefill", 1, self.prefill_chunk_len)
        return out
