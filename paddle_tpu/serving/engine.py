"""Continuous-batching decode engine: a fixed pool of S sequence slots
kept alive inside ONE jitted step.

Reference parity: the reference serving stack's fused_multi_transformer
decode loop + PaddleNLP's dynamic-batching inference server (SURVEY §2.1
Inference, §3.5 AnalysisPredictor — verify); the design is the
vLLM-style continuous batching discipline restated under the repo's
static-shape rules.

TPU-native design: the KV cache is preallocated at
``(S, max_len, kv_heads, head_dim)`` and never reshapes — a retiring
request frees its SLOT, not its memory. Per-slot ``pos``/``pad``/
``live``/``eos``/``remaining``/rng-key/sampling-param state rides
in-graph as (S,) arrays, so ONE compiled program (a ``lax.scan`` of the
shared decode step over ``decode_block`` tokens) serves every mix of
request depths, greedy/sampled traffic, and admission pattern — zero
recompiles across the stream. Admission reuses the existing shared
prefill/decode step from ``models/generation`` at batch 1 (prompt
left-padded to a bucket length), then splices the prefilled row into
the pool with ``lax.dynamic_update_slice`` on the batch dim while the
other slots' cache rows stay untouched (prefill-insert). The defining
invariant: a continuously-batched stream of ragged greedy requests is
bit-identical to per-request ``generate()`` calls.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import metrics as _om
from ..observability.tracing import (StallWatch, named_program,
                                     now_us as _trace_now, span as _span)
from ..utils import faults

# engine metric families (no-ops until metrics.enable()/PT_METRICS)
_M_STEPS = _om.counter("pt_engine_decode_steps_total",
                       "decode-block steps executed")
_M_TOKENS = _om.counter("pt_engine_tokens_emitted_total",
                        "useful tokens emitted (prefill + decode)")
_M_DECODE_TOKENS = _om.counter("pt_engine_decode_tokens_total",
                               "live-slot decode tokens emitted")
_M_COMPILES = _om.gauge("pt_engine_decode_compiles",
                        "times the decode-block program was traced "
                        "(static-shape invariant: stays 1)")
_M_PREFILLS = _om.counter("pt_engine_prefills_total",
                          "prefill dispatches (whole-prompt or chunk)")
_M_BYTES = _om.counter(
    "pt_serving_decode_bytes_read_total",
    "estimated HBM bytes read by decode steps (weights + buffers + "
    "KV pool, capacity-based — the quant-vs-fp32 A/B numerator)")
_M_W_BYTES = _om.gauge(
    "pt_serving_decode_weight_bytes",
    "weight + buffer bytes one decode step reads (codes + scales "
    "under weight-only quant)")
_M_KV_BYTES = _om.gauge(
    "pt_serving_decode_kv_bytes",
    "KV pool bytes resident per decode step (codes + scales under "
    "the int8 arena)")

__all__ = ["ContinuousBatchingEngine", "ModelStepBackend",
           "ArtifactStepBackend", "slot_sample_logits", "init_slot_state",
           "build_slot_block_fn", "build_slot_prefill_fn",
           "build_paged_chunk_fn"]


def slot_sample_logits(logits, keys, temperature, top_k, top_p, live=None):
    """Per-slot sampling over (S, V) logits (or log-probs — per-row
    shifts cancel in every branch): ``temperature``/``top_k``/``top_p``
    are (S,) arrays so one compiled program serves mixed greedy/sampled
    traffic. Greedy rows (temperature <= 0) take argmax; sampled rows
    share ONE descending sort for both the top-k threshold and the
    top-p cutoff, then draw categorically with per-row keys.

    The sampled path (scaling, sort, softmax, cumsum, the two filters,
    the draw) sits under a ``lax.cond`` on the program's own input: it
    runs only in a call where some row that counts has ``temperature >
    0``; otherwise the argmax is the whole answer. ``live`` is an
    optional (S,) mask of the rows whose pick is used (default: all) —
    a retired slot that still holds a sampled request's temperature
    does not bring the sort back. Tokens are those of the unconditional
    form bit for bit: a greedy row's pick is the same argmax of the
    same fp32 values, a sampled row runs the same operations with the
    same key (keys are split by the caller whether or not the branch
    runs). A ``vmap`` over the predicate would turn the ``cond`` into a
    ``select`` that runs both sides: keep this call un-vmapped."""
    S, V = logits.shape
    logits = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_path():
        t = jnp.where(greedy, jnp.float32(1.0),
                      temperature.astype(jnp.float32))
        scaled = logits / t[:, None]
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        k = jnp.clip(top_k.astype(jnp.int32), 0, V)
        use_k = (k > 0) & (k < V)
        kth = jnp.take_along_axis(sorted_desc,
                                  jnp.maximum(k - 1, 0)[:, None], axis=-1)
        kth = jnp.where(use_k[:, None], kth, -jnp.inf)
        filt = jnp.where(scaled < kth, -jnp.inf, scaled)
        # masking below-kth values inside the sorted array == re-sorting
        # the filtered row (kept prefix unchanged, dropped tail -> -inf)
        sorted_f = jnp.where(sorted_desc < kth, -jnp.inf, sorted_desc)
        probs = jax.nn.softmax(sorted_f, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.clip(
            jnp.sum(cum < top_p[:, None], axis=-1, keepdims=True), 0, V - 1)
        cutoff = jnp.take_along_axis(sorted_f, cutoff_idx, axis=-1)
        cutoff = jnp.where((top_p < 1.0)[:, None], cutoff, -jnp.inf)
        filt = jnp.where(filt < cutoff, -jnp.inf, filt)
        sampled = jax.vmap(
            lambda kk, row: jax.random.categorical(kk, row))(keys, filt)
        return jnp.where(greedy, greedy_tok, sampled.astype(jnp.int32))

    samples = ~greedy if live is None else ~greedy & live
    return jax.lax.cond(jnp.any(samples), sampled_path, lambda: greedy_tok)


# The compiled programs' names as a device trace shows them ("XLA
# Modules" line). benchmark/kinds/serve.py reads device time by these
# names, so they are set on purpose where the functions are built
# (``tracing.named_program``) and pinned in tests/test_trace_names.py.
DECODE_PROGRAM = "jit_block_fn"
PREFILL_CHUNK_PROGRAM = "jit_chunk_fn"


def init_slot_state(num_slots: int) -> Dict[str, jnp.ndarray]:
    """Fresh all-slots-free in-graph state pytree."""
    S = num_slots
    return {
        "tok": jnp.zeros((S,), jnp.int32),
        "pos": jnp.zeros((S,), jnp.int32),
        "pad": jnp.zeros((S,), jnp.int32),
        "live": jnp.zeros((S,), bool),
        "eos": jnp.full((S,), -1, jnp.int32),
        "remaining": jnp.zeros((S,), jnp.int32),
        "key": jnp.zeros((S, 2), jnp.uint32),
        "temp": jnp.zeros((S,), jnp.float32),
        "topk": jnp.zeros((S,), jnp.int32),
        "topp": jnp.ones((S,), jnp.float32),
    }


def build_slot_block_fn(pure, block: int, trace_counter=None,
                        paged: bool = False):
    """The engine's ONE decode program: ``lax.scan`` of the shared step
    over ``block`` tokens with per-slot positions. Each scan iteration:
    per-slot key split -> forward (vector ``pos``, per-slot ``pad``) ->
    per-slot sampling -> in-graph eos/budget retirement (a finished
    slot's ``live`` drops and its pos/tok freeze — it is masked junk
    until the host refills it between blocks). Emits the (block, S)
    token matrix plus per-step live-slot counts (the occupancy/tok-s
    numerators), so the host syncs ONCE per block.

    ``paged``: the state carries a per-slot block ``table`` and the
    cache is the shared block arena; dead slots' tables are redirected
    to the trash block 0 IN-GRAPH, so a retired slot whose blocks the
    host has already handed to another request can never scatter junk
    into them mid-block.

    Besides tokens and live masks the block also emits per-step (S,)
    ``ok`` flags — True iff the row's log-probs held no NaN (the logit
    sentinel the resilience layer uses to quarantine a poisoned slot
    without touching its neighbours). The flags are a side output of
    the SAME single compiled program; healthy streams are bit-identical
    with or without the sentinel reading them."""

    def block_fn(pv, bv, cache_flat, state):
        if trace_counter is not None:       # runs only while tracing
            trace_counter[0] += 1

        def body(carry, _):
            cf, st = carry
            sp = jax.vmap(jax.random.split)(st["key"])     # (S, 2, 2)
            new_key, sub = sp[:, 0], sp[:, 1]
            if paged:
                tbl = jnp.where(st["live"][:, None], st["table"], 0)
                logp, cf = pure(pv, bv, st["tok"][:, None], cf,
                                st["pos"], None, None, tbl)
            else:
                logp, cf = pure(pv, bv, st["tok"][:, None], cf,
                                st["pos"], None, st["pad"])
            # NaN (not -inf: log-probs legitimately underflow) marks a
            # poisoned row — numerically impossible from finite
            # weights/cache, so a False flag means corrupted state
            ok = ~jnp.any(jnp.isnan(logp), axis=-1)
            with jax.named_scope("sample"):
                nxt = slot_sample_logits(logp, sub, st["temp"],
                                         st["topk"], st["topp"],
                                         st["live"])
            live = st["live"]
            hit = live & (st["eos"] >= 0) & (nxt == st["eos"])
            rem = jnp.where(live, st["remaining"] - 1, st["remaining"])
            rem = jnp.where(hit, 0, rem)
            st2 = dict(st, tok=jnp.where(live, nxt, st["tok"]),
                       pos=st["pos"] + live.astype(jnp.int32),
                       remaining=rem, key=new_key,
                       live=live & (rem > 0))
            # ``live`` (the start-of-step mask) marks which rows of the
            # token matrix are real emissions — an eos retirement zeroes
            # ``remaining``, so the host must count emissions from this
            # mask, not from remaining deltas
            return (cf, st2), (nxt, live, ok)

        (cache_flat, state), (toks, lives, oks) = jax.lax.scan(
            body, (cache_flat, state), None, length=block)
        return cache_flat, state, toks, lives, oks

    return named_program(block_fn, DECODE_PROGRAM)


def build_slot_prefill_fn(pure, row_specs):
    """Batch-1 prefill of a prompt bucket into a fresh full-length cache
    row (the row is spliced into the pool by the admit program). Reuses
    the SAME shared step as ``generate()`` — prompt left-padded to the
    bucket length, per-row pad counts mask the filler — so slot decode
    is bit-identical to a standalone ``generate()`` call. The first
    token is sampled in-graph with the request's own params (one
    dispatch per admission, not two)."""

    def prefill_fn(pv, bv, ids, pad, key, temp, topk, topp):
        zero = tuple(jnp.zeros(shape, dtype) for shape, dtype in row_specs)
        logp, row = pure(pv, bv, ids, zero, jnp.asarray(0, jnp.int32),
                         None, pad)
        tok0 = slot_sample_logits(logp, key[None], temp[None],
                                  topk[None], topp[None])[0]
        return tok0, row

    return prefill_fn


def build_paged_chunk_fn(pure, chunk: int, trace_counter=None):
    """ONE chunked-prefill program for every prompt of every length:
    a fixed ``(1, chunk)`` right-padded token window written straight
    into the paged arena through the request's block table (pad columns
    carry junk K/V that decode overwrites before it can ever be
    attended — writes past the table width land in the trash block).
    The candidate first token is sampled in-graph from the last REAL
    column with the request's own params; the host uses it only on the
    final chunk. Unlike the dense engine's per-bucket prefill jits,
    this compiles exactly once. Every call streams all the weights
    whatever ``chunk`` is, so the chunk a paged engine picks by itself
    (``paging.default_prefill_chunk``) is the ridge where the chunk's
    matmuls catch up with that read."""

    def chunk_fn(pv, bv, ids, cache_flat, table, start_pos, n_valid,
                 key, temp, topk, topp):
        if trace_counter is not None:       # runs only while tracing
            trace_counter[0] += 1
        logp, cache_flat = pure(
            pv, bv, ids, cache_flat, jnp.reshape(start_pos, (1,)),
            None, None, table, n_valid - 1)
        with jax.named_scope("sample"):
            tok0 = slot_sample_logits(logp, key[None], temp[None],
                                      topk[None], topp[None])[0]
        return tok0, cache_flat

    return named_program(chunk_fn, PREFILL_CHUNK_PROGRAM)


def _cancel_fn(state, slot):
    """Kill one slot in-graph (deadline/poison cancellation): ``live``
    drops and ``remaining`` zeroes, so the next decode block treats the
    row as retired junk (and, paged, redirects its table to the trash
    block). One compiled program serves every cancellation."""
    return dict(state,
                live=state["live"].at[slot].set(False),
                remaining=state["remaining"].at[slot].set(0))


def _admit_fn(cache_flat, state, row_flat, slot, tok0, pos0, pad0, rem0,
              eos0, temp0, topk0, topp0, key0):
    """Splice a prefilled row into the pool (dynamic_update_slice on the
    batch dim — other slots' rows untouched) and arm the slot's state.
    ``slot`` is traced, so ONE compiled program serves every admission."""
    new_cache = tuple(
        jax.lax.dynamic_update_slice(c, r.astype(c.dtype),
                                     (slot,) + (0,) * (c.ndim - 1))
        for c, r in zip(cache_flat, row_flat))

    def set1(a, v):
        return a.at[slot].set(jnp.asarray(v, a.dtype))

    new_state = dict(
        state, tok=set1(state["tok"], tok0),
        pos=set1(state["pos"], pos0), pad=set1(state["pad"], pad0),
        live=set1(state["live"], rem0 > 0),
        eos=set1(state["eos"], eos0),
        remaining=set1(state["remaining"], rem0),
        key=state["key"].at[slot].set(key0),
        temp=set1(state["temp"], temp0),
        topk=set1(state["topk"], topk0),
        topp=set1(state["topp"], topp0))
    return new_cache, new_state


class _StepBackendCommon:
    """Shared slot-state/accounting helpers for every step backend
    (in-process, paged, AOT) — keyed off ``num_slots``/``pool_specs``
    which each backend sets up."""

    # weight-only quantization state (serving/quant.py): None/empty on
    # fp32 backends, so every hot path stays one falsy check
    quant_cfg = None
    _qmeta = None
    _weight_bound = 0.0

    def init_state(self):
        return init_slot_state(self.num_slots)

    def kv_bytes_per_slot(self) -> int:
        """HBM bytes of KV cache per slot (the paged backend's arena is
        shared, so its per-slot figure shrinks with block count)."""
        total = sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
                    for shape, dtype in self.pool_specs)
        return total // self.num_slots

    def _setup_weight_quant(self, model, quant):
        """Quantize the serving weight set in-place (model backends
        call this between pv construction and program building; see
        serving/quant.py). No-op when ``quant`` is None."""
        if quant is None:
            return
        from .quant import quantize_backend_params
        self.quant_cfg = quant
        self._pv, self._qmeta, self._weight_bound = \
            quantize_backend_params(model, self._pv, quant)

    def _maybe_quant_pure(self, pure):
        """Wrap a pure step with the in-graph dequant when this backend
        holds quantized weights — EVERY program (decode block, prefill,
        chunk, spec verify) must be built from the wrapped step."""
        if not self._qmeta:
            return pure
        from .quant import wrap_pure_with_dequant
        return wrap_pure_with_dequant(pure, self._qmeta)

    def param_bytes(self) -> int:
        """HBM bytes of weights + buffers one decode step reads (codes
        AND scales under weight-only quant — the wire footprint, which
        is the point)."""
        return sum(int(v.nbytes) for v in jax.tree.leaves(self._pv)) \
            + sum(int(v.nbytes) for v in jax.tree.leaves(self._bv))


class ModelStepBackend(_StepBackendCommon):
    """In-process backend: jits the slot block + per-bucket prefills
    over a live model (the same pure step ``generate()`` uses)."""

    def __init__(self, model, num_slots: int, max_len: int,
                 decode_block: int, quant=None):
        from ..models.generation import (build_decode_step,
                                         forward_accepts_pad)
        from ..tensor import Tensor
        if not forward_accepts_pad(type(model)):
            raise ValueError(
                f"{type(model).__name__}.forward does not accept per-row "
                "pad counts — the slot pool needs ragged decode support")
        self.num_slots, self.max_len = num_slots, max_len
        self.block_size = decode_block
        tree_holder = {"tree": None}
        self._tree_holder = tree_holder    # spec backends reuse it
        self._pure = build_decode_step(model, None, tree_holder)
        cache0 = model.init_kv_cache(num_slots, max_len)
        flat, tree = jax.tree.flatten(
            cache0, is_leaf=lambda x: isinstance(x, Tensor))
        tree_holder["tree"] = tree
        self.pool_specs = tuple((c._value.shape, c._value.dtype)
                                for c in flat)
        self.row_specs = tuple(((1,) + shape[1:], dtype)
                               for shape, dtype in self.pool_specs)
        self._pv = [p._value for _, p in model.named_parameters()]
        self._bv = [b._value for _, b in model.named_buffers()]
        # weight-only quant happens BEFORE any program is built so the
        # decode block, prefills (and subclasses' chunk/verify programs)
        # all trace against codes + in-graph dequant
        self._setup_weight_quant(model, quant)
        self._pure = self._maybe_quant_pure(self._pure)
        self.decode_traces = [0]
        self._block_jit = jax.jit(
            build_slot_block_fn(self._pure, decode_block,
                                self.decode_traces),
            donate_argnums=(2, 3))
        self._prefill_jits: Dict[int, callable] = {}

    def pool_cache(self):
        return tuple(jnp.zeros(shape, dtype)
                     for shape, dtype in self.pool_specs)

    def decode_block(self, cache_flat, state):
        return self._block_jit(self._pv, self._bv, cache_flat, state)

    def prefill(self, bucket_len, ids, pad, key, temp, topk, topp):
        fn = self._prefill_jits.get(bucket_len)
        if fn is None:
            fn = jax.jit(build_slot_prefill_fn(self._pure, self.row_specs))
            self._prefill_jits[bucket_len] = fn
        return fn(self._pv, self._bv, ids, pad, key, temp, topk, topp)


def artifact_fingerprint(cfgs: dict, *programs: bytes) -> str:
    """Artifact identity: sha1 over the recorded config + the
    serialized programs. Recorded into engine snapshots so a restore
    onto a DIFFERENT artifact is refused — the ONE recipe shared by the
    dense and paged artifact backends (changing it in one place cannot
    silently de-gate the other)."""
    import hashlib
    h = hashlib.sha1(repr(sorted(
        (k, str(v)) for k, v in cfgs.items())).encode())
    for prog in programs:
        h.update(prog)
    return h.hexdigest()


class ArtifactStepBackend(_StepBackendCommon):
    """AOT backend: the SAME engine programs, deserialized from an
    ``export_decoder(..., engine_slots=...)`` artifact — no model code
    or tracing needed on the serving host (reference: AnalysisPredictor
    serving from the saved program alone)."""

    def __init__(self, blob):
        eng = blob["engine"]
        cfgs = eng["config"]
        self.artifact_fingerprint = artifact_fingerprint(
            cfgs, eng["block"],
            *(eng["prefill"][lb] for lb in sorted(eng["prefill"])))
        self.num_slots = cfgs["num_slots"]
        self.max_len = cfgs["max_len"]
        self.block_size = cfgs["decode_block"]
        # pre-NaN-sentinel artifacts exported a 4-output decode block
        # (no per-step ok flags); the engine pads the missing flags
        # with None so both generations serve — new exports record
        # block_outputs=5
        self.carries_nan_flags = cfgs.get("block_outputs", 4) >= 5
        self.pool_specs = tuple((tuple(shape), np.dtype(dtype))
                                for shape, dtype in eng["pool_specs"])
        self._block = jax.export.deserialize(eng["block"])
        self._prefills = {int(k): jax.export.deserialize(v)
                          for k, v in eng["prefill"].items()}
        self._pv = [jnp.asarray(v) for v in blob["params"]]
        self._bv = [jnp.asarray(v) for v in blob["buffers"]]
        self.decode_traces = [1]     # one AOT-compiled decode program

    def pool_cache(self):
        return tuple(jnp.zeros(shape, dtype)
                     for shape, dtype in self.pool_specs)

    def decode_block(self, cache_flat, state):
        return self._block.call(self._pv, self._bv, cache_flat, state)

    def prefill(self, bucket_len, ids, pad, key, temp, topk, topp):
        fn = self._prefills.get(int(bucket_len))
        if fn is None:
            raise ValueError(
                f"prompt bucket {bucket_len} was not exported; available: "
                f"{sorted(self._prefills)} — re-export with it in "
                "engine_prompt_buckets")
        return fn.call(self._pv, self._bv, ids, pad, key, temp, topk,
                       topp)


@dataclass
class _SlotRun:
    """Host-side bookkeeping for one in-flight request. ``t_admit`` is
    the moment the first token existed (prefill completion) — the TTFT
    timestamp. ``block_ids``: the paged engine's arena blocks to
    release at retirement (None on the dense engine);
    ``window``: what the hybrid engine's run holds in its window pool;
    ``chain``: ``(digest, tokens)`` of the leading blocks of what the run
    writes (prompt, then history) that the paged engine's block manager
    has hashed so far — a cache it extends, never serialized."""
    request: object
    tokens: List[int] = field(default_factory=list)
    t_admit: float = 0.0
    t_done: float = 0.0
    block_ids: Optional[List[int]] = None
    window: Optional[object] = None
    chain: list = field(default_factory=list)
    # set when the request was cancelled/quarantined instead of
    # completing ("timeout", "poisoned", "circuit_open", ...); the
    # Server records a RequestFailure in results instead of tokens
    failure: Optional[str] = None


class ContinuousBatchingEngine:
    """Slot-pool decode engine over a step backend. The host syncs with
    the device once per ``decode_block`` tokens: it reads the (block, S)
    token matrix plus the post-block ``remaining`` counters, harvests
    retired requests, and refills free slots — the decode program itself
    is compiled exactly once for the engine's lifetime.

    ``paged=True`` (or ``PT_SERVING_PAGED=1``) constructs the
    block-paged variant (``serving.paging.PagedEngine``): shared KV
    arena + per-slot block tables, ref-counted prefix reuse, chunked
    prefill — see that module for the paged-only knobs."""

    def __new__(cls, *args, **kw):
        if cls is ContinuousBatchingEngine:
            paged = kw.get("paged")
            backend = kw.get("backend") if len(args) < 6 else args[5]
            if paged is None:
                from ..utils.flags import env_flag
                if getattr(backend, "is_paged", False):
                    paged = True     # a paged backend IS the decision
                elif backend is None:
                    paged = env_flag("PT_SERVING_PAGED")
                # an explicit non-paged backend (e.g. the AOT
                # ArtifactStepBackend in GenerationPredictor) is never
                # rerouted by the env flag
            from .spec import spec_requested
            spec = spec_requested(kw.get("spec"), backend)
            if paged:
                from .paging import PagedEngine
                from .spec import SpecPagedEngine
                model = args[0] if args else kw.get("model")
                if backend is None and not spec and \
                        getattr(model, "kv_cache_groups", None):
                    # a model whose cache keeps two groups of layers
                    # (full and sliding-window): its own engine
                    from .hybrid import HybridPagedEngine
                    return object.__new__(HybridPagedEngine)
                return object.__new__(
                    SpecPagedEngine if spec else PagedEngine)
            if spec:
                from .spec import SpecEngine
                return object.__new__(SpecEngine)
        return object.__new__(cls)

    def __init__(self, model=None, num_slots: int = 4, max_len: int = 256,
                 decode_block: int = 8,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 backend=None, *, paged: Optional[bool] = None,
                 spec=None, tp=None, quant=None):
        if backend is None:
            if model is None:
                raise ValueError("pass a model or a step backend")
            from .quant import resolve_quant_config
            from .tp import resolve_tp_config
            tp_cfg = resolve_tp_config(tp)
            q_cfg = resolve_quant_config(quant)
            if tp_cfg is not None:
                # tensor-parallel serving: the SAME decode/prefill
                # programs, sharded over a mesh (serving/tp.py). An
                # explicitly passed backend is never rerouted by the
                # PT_SERVING_TP env flag — same contract as paged.
                from .tp import ShardedModelStepBackend
                backend = ShardedModelStepBackend(
                    model, num_slots, max_len, decode_block, tp_cfg,
                    quant=q_cfg)
            else:
                # subclass hook: the speculative engine swaps in the
                # verify-capable backend here (serving/spec.py)
                backend = self._build_backend(model, num_slots, max_len,
                                              decode_block, q_cfg)
        elif quant is not None:
            # same contract as kv_int8/num_blocks on the paged engine:
            # the quantization is baked into the backend at construction
            # — a silently ignored quant= (INCLUDING quant=False against
            # a quantized backend, which cannot be de-quantized) would
            # be a misconfiguration, not a preference (and the env knob
            # never reroutes an explicit backend either: resolution
            # only runs above)
            raise ValueError(
                "quant= cannot be set alongside an explicit backend — "
                "weight-only quantization is baked into the backend at "
                "construction")
        if spec and not hasattr(self, "spec_k"):
            # only the factory (ContinuousBatchingEngine(...)) routes
            # spec= to the speculative engine classes; a direct
            # subclass constructor silently ignoring it would be a
            # misconfiguration, not a preference
            raise ValueError(
                "spec= is only honored through the "
                "ContinuousBatchingEngine factory (or construct "
                "serving.spec.SpecEngine/SpecPagedEngine directly)")
        self.backend = backend
        self.num_slots = backend.num_slots
        self.max_len = backend.max_len
        self.decode_block = backend.block_size
        self.prompt_buckets = tuple(sorted(prompt_buckets)) \
            if prompt_buckets else None
        self._admit_jit = jax.jit(_admit_fn, donate_argnums=(0, 1))
        self._cancel_jit = jax.jit(_cancel_fn, donate_argnums=(0,))
        # host-side gate on the in-graph NaN flags (the flags are
        # always computed — same single compiled program either way)
        self.nan_sentinel = True
        # set by the Server iff request tracing is armed (None keeps
        # the hot paths at one `is None` check)
        self.tracer = None
        self.reset()

    def _build_backend(self, model, num_slots, max_len, decode_block,
                       quant=None):
        return ModelStepBackend(model, num_slots, max_len, decode_block,
                                quant=quant)

    # -- lifecycle ---------------------------------------------------------
    def reset(self):
        """Free every slot and zero the counters (compiled programs are
        kept — repeat streams never recompile)."""
        self._cache = self.backend.pool_cache()
        self._state = self.backend.init_state()
        self._slots: List[Optional[_SlotRun]] = [None] * self.num_slots
        self._prefill_slots: set = set()   # paged: mid-prefill slots
        self._remaining_host = np.zeros((self.num_slots,), np.int64)
        self._finished: List[_SlotRun] = []
        self._pending_block = None     # dispatched, not yet harvested
        self._block_span = None        # its ``serving.decode_block`` span
        self._bytes_step = None        # decode_bytes_per_step memo
        self.steps = 0                # engine decode steps executed
        self.sampled_steps = 0        # of those, a live slot sampled
        self.tokens_emitted = 0       # useful tokens (incl. prefill's)
        self.decode_tokens = 0        # live-slot decode steps only
        self.slot_steps = 0           # S * steps (occupancy denominator)
        # time the chip was left with an empty queue while work was held
        # (:meth:`_enqueued`), and when a sync last said it had drained
        self.device_starved_ns = 0
        self._drained_ns = None
        # syncs that lasted far beyond their kind's median (S8)
        self._stall_watch = StallWatch()
        self.sync_stalls = 0
        self.sync_stall_ns = 0        # what they lasted beyond it
        self._stalls_untold: List[dict] = []   # for the Server's flight ring

    # -- the chip's empty queue, and syncs that stall -------------------------
    def _enqueued(self, sp):
        """A chunk program or a decode block has just been enqueued
        (``sp``: the open span around the enqueue). If a sync had said
        that everything enqueued before it was finished, the chip sat
        with an empty queue from then to now: that is counted in
        ``device_starved_ns`` and written on ``sp`` as ``starved_ns``.
        Measured from when the host LEARNS the device is drained to when
        an enqueue RETURNS, so never more than the device's idle time; the
        small programs in between (the admission's uploads, arming a
        slot) neither start nor end an interval, and an engine left
        without a live slot forgets the stamp (:meth:`_free_slot`): idle
        for want of traffic is not starvation. The dense engine's
        whole-prompt prefill ends an interval and, its first token being
        fetched outside any sync span, starts none."""
        if self._drained_ns is not None:
            ns = time.perf_counter_ns() - self._drained_ns
            self._drained_ns = None
            self.device_starved_ns += ns
            sp.ids["starved_ns"] = ns

    def _free_slot(self, slot: int):
        self._slots[slot] = None
        if self._drained_ns is not None and not self.has_live():
            self._drained_ns = None

    def _sync_ended(self, sp, began):
        """After a blocking sync's span ``sp`` closed (``began``: the stall
        watch's reading from before it opened): count a stall."""
        stall = self._stall_watch.end(sp, began)
        if stall is not None:
            self.sync_stalls += 1
            self.sync_stall_ns += stall["over_ns"]
            self._stalls_untold.append(dict(
                stall, sync=sp.name, dur_ms=round(sp.dur / 1e6, 3)))

    def take_sync_stalls(self) -> List[dict]:
        """The stalls since the last call, for the caller's flight ring."""
        told, self._stalls_untold = self._stalls_untold, []
        return told

    # -- introspection -----------------------------------------------------
    def free_slot_count(self) -> int:
        return sum(1 for s in self._slots if s is None)

    def has_live(self) -> bool:
        return any(s is not None for s in self._slots)

    def has_decoding(self) -> bool:
        """Any slot past prefill (worth running a decode block for) —
        differs from :meth:`has_live` only on the paged engine, where a
        slot can be occupied but still mid-chunked-prefill."""
        return any(s is not None and i not in self._prefill_slots
                   for i, s in enumerate(self._slots))

    def occupancy(self) -> float:
        """Fraction of decode-block slot-steps that emitted a token
        (prefill tokens live outside the pool and don't count here)."""
        return self.decode_tokens / self.slot_steps if self.slot_steps \
            else 0.0

    def decode_compile_count(self) -> int:
        """Number of times the decode-block program was traced/compiled
        — the static-shape invariant holds iff this stays 1."""
        return self.backend.decode_traces[0]

    def tp_degree(self) -> int:
        """Devices the decode block is sharded over (1 = TP off)."""
        return getattr(self.backend, "tp_degree", 1)

    def tp_int8_error_bound(self) -> float:
        """Runtime worst-case elementwise error of the tensor-parallel
        int8 hidden-state all-reduce, probed against the LIVE cache and
        slot state (0.0 unless a psum-mode TP backend with the int8 hop
        is armed — see serving/tp.py)."""
        fn = getattr(self.backend, "tp_int8_error_bound", None)
        if fn is None:
            return 0.0
        return fn(self._cache, self._state)

    def kv_error_bound(self) -> float:
        """Runtime worst-case |dequant - fp32| over the KV cache — 0.0
        on the dense engine (fp32 rows); the paged engine's int8 arena
        overrides this with the EQuARX bound."""
        return 0.0

    def weight_error_bound(self) -> float:
        """Build-time worst-case elementwise |dequant - fp32| over the
        weight-only-quantized decode weights (half the largest
        quantization step; 0.0 when quant is off)."""
        return float(getattr(self.backend, "_weight_bound", 0.0))

    def quant_error_bound(self) -> dict:
        """Both quantization error components of the decode path, from
        the live engine: ``{"kv": ..., "weights": ...}`` (each 0.0 when
        that half is off). Also refreshes the
        ``pt_serving_{kv,weight}_error_bound`` gauges, so a scrape
        after any call carries the current bounds."""
        kv, w = self.kv_error_bound(), self.weight_error_bound()
        if _om.enabled():
            from .quant import _M_KV_BOUND, _M_W_BOUND
            _M_KV_BOUND.set(kv)
            _M_W_BOUND.set(w)
        return {"kv": kv, "weights": w}

    def decode_bytes_per_step(self) -> dict:
        """Estimated HBM bytes ONE decode step reads:
        ``{"weights": ..., "kv": ..., "total": ...}`` — every
        weight/buffer byte (codes + scales under weight-only quant)
        plus the KV pool's resident bytes (codes + scales under the
        int8 arena). Capacity-based: the paged read only touches live
        blocks, so the kv term is an upper bound — but it is the term
        quantization shrinks, which is what the A/B measures."""
        if self._bytes_step is None:
            w = self.backend.param_bytes() \
                if hasattr(self.backend, "param_bytes") else 0
            kv = sum(int(c.nbytes) for c in self._cache)
            self._bytes_step = {"weights": w, "kv": kv,
                                "total": w + kv}
        return self._bytes_step

    def _note_decode_bytes(self, steps: int):
        """Metrics hook on the decode dispatch path (one enabled-check
        when metrics are off)."""
        if not _om.enabled():
            return
        b = self.decode_bytes_per_step()
        _M_BYTES.inc(b["total"] * steps)
        _M_W_BYTES.set(b["weights"])
        _M_KV_BYTES.set(b["kv"])

    def bucket_len(self, prompt_len: int) -> int:
        if self.prompt_buckets is None:
            return prompt_len
        for b in self.prompt_buckets:
            if b >= prompt_len:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest bucket "
            f"{self.prompt_buckets[-1]}")

    def validate_request(self, prompt_len: int, max_new_tokens: int):
        """Raise ValueError if the request can never fit a slot — run
        at submit time so a bad request is rejected at the door instead
        of aborting the serving loop mid-stream at admission."""
        if prompt_len <= 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens={max_new_tokens}; must be >= 1")
        lb = self.bucket_len(prompt_len)
        if lb + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt bucket ({lb}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the slot capacity "
                f"({self.max_len}); raise max_len or shorten the request")

    # -- admission ---------------------------------------------------------
    def admit(self, request) -> bool:
        """Prefill the request's prompt (batch-1, left-padded to its
        bucket) and splice the row into a free slot. Returns True if the
        request already finished at admission (max_new==1 or eos on the
        first token) — it then never occupies a slot. A request carrying
        preemption ``resume`` state re-prefills its generated history
        instead (see :meth:`_admit_resume`)."""
        from ..profiler import RecordEvent
        resume = getattr(request, "resume", None)
        if resume is not None and resume.tokens:
            return self._admit_resume(request, resume)
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        L = int(prompt.shape[0])
        self.validate_request(L, request.max_new_tokens)
        Lb = self.bucket_len(L)
        slot = next((i for i, s in enumerate(self._slots) if s is None),
                    None)
        if slot is None:
            raise RuntimeError("no free slot (scheduler bug)")
        tr = self.tracer
        if tr is not None:
            tr.span_end(request.request_id, "queue_wait")
            t_prefill = _trace_now()
        ids = np.zeros((1, Lb), np.int32)
        ids[0, Lb - L:] = prompt
        pad0 = Lb - L
        key = jax.random.PRNGKey(request.seed)
        key, sub = jax.random.split(key)      # generate()'s key schedule
        temp = jnp.float32(request.temperature)   # <= 0 means greedy
        topk = jnp.int32(request.top_k)
        topp = jnp.float32(request.top_p)
        with RecordEvent("serving.prefill") as ev:
            tok0_dev, row = self.backend.prefill(
                Lb, jnp.asarray(ids), jnp.asarray([pad0], jnp.int32),
                sub, temp, topk, topp)
            self._enqueued(ev._span)
        tok0 = int(tok0_dev)
        if tr is not None:
            tr.span_at(request.request_id, "prefill", t_prefill,
                       tokens=L, bucket=Lb)
        _M_PREFILLS.inc()
        _M_TOKENS.inc()
        run = _SlotRun(request, tokens=[tok0], t_admit=time.perf_counter())
        self.tokens_emitted += 1
        eos = request.eos_token_id
        rem0 = request.max_new_tokens - 1
        if eos is not None and tok0 == eos:
            rem0 = 0
        if rem0 <= 0:
            run.t_done = time.perf_counter()
            self._finished.append(run)
            return True
        with RecordEvent("serving.admit"):
            self._cache, self._state = self._admit_jit(
                self._cache, self._state, row, jnp.int32(slot),
                jnp.int32(tok0), jnp.int32(Lb), jnp.int32(pad0),
                jnp.int32(rem0),
                jnp.int32(-1 if eos is None else eos),
                temp, topk, topp, key)
        if tr is not None:
            tr.span_begin(request.request_id, "decode", slot=slot)
        self._slots[slot] = run
        self._remaining_host[slot] = rem0
        return False

    def _admit_resume(self, request, resume) -> bool:
        """Re-admit a preempted request: re-prefill prompt + generated
        history — the KV the eviction dropped — into a fresh row, then
        arm the slot with the CARRIED stream state (``tokens[-1]`` as
        the in-hand next token, the saved rng key, the remaining token
        budget). The re-prefill's in-graph sample is DISCARDED (the
        stream already owns its next token, and the saved key must not
        be advanced), so the resumed greedy AND seeded-sampled streams
        are bit-identical to an uninterrupted run. Padding shifts are
        invisible by construction: RoPE positions are pad-corrected and
        masked slots contribute exact zeros, the same property that
        makes bucket-padded serving equal generate()."""
        from ..profiler import RecordEvent
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        toks = list(resume.tokens)
        full = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        pl = int(full.shape[0])
        rem0 = request.max_new_tokens - len(toks)
        self.validate_request(pl, rem0 + 1)
        Lb = self.bucket_len(pl)
        slot = next((i for i, s in enumerate(self._slots) if s is None),
                    None)
        if slot is None:
            raise RuntimeError("no free slot (scheduler bug)")
        tr = self.tracer
        if tr is not None:
            tr.span_end(request.request_id, "queue_wait", resumed=True)
            t_prefill = _trace_now()
        ids = np.zeros((1, Lb), np.int32)
        ids[0, Lb - pl:] = full
        pad0 = Lb - pl
        with RecordEvent("serving.prefill") as ev:
            _discard, row = self.backend.prefill(
                Lb, jnp.asarray(ids), jnp.asarray([pad0], jnp.int32),
                jax.random.PRNGKey(0), jnp.float32(0.0), jnp.int32(0),
                jnp.float32(1.0))
            self._enqueued(ev._span)
        if tr is not None:
            tr.span_at(request.request_id, "prefill", t_prefill,
                       tokens=pl, bucket=Lb, resumed=True)
        _M_PREFILLS.inc()
        # t_admit carries over: the first token existed before the
        # eviction, so TTFT keeps measuring the first admission
        run = _SlotRun(request, tokens=toks, t_admit=resume.t_admit)
        eos = request.eos_token_id
        with RecordEvent("serving.admit"):
            self._cache, self._state = self._admit_jit(
                self._cache, self._state, row, jnp.int32(slot),
                jnp.int32(toks[-1]), jnp.int32(Lb), jnp.int32(pad0),
                jnp.int32(rem0),
                jnp.int32(-1 if eos is None else eos),
                jnp.float32(request.temperature),
                jnp.int32(request.top_k), jnp.float32(request.top_p),
                jnp.asarray(np.asarray(resume.key, np.uint32)))
        if tr is not None:
            tr.instant(request.request_id, "resume", slot=slot,
                       reused_tokens=len(toks))
            tr.span_begin(request.request_id, "decode", slot=slot)
        self._slots[slot] = run
        self._remaining_host[slot] = rem0
        request.resume = None       # consumed; a later preemption
        return False                # rebuilds it from the live run

    def try_admit(self, request) -> bool:
        """Admit if resources allow; False means "retry later" (the
        paged engine's block pool can be exhausted even with a free
        slot — the dense engine always admits into a free slot)."""
        self.admit(request)
        return True

    # -- preemption --------------------------------------------------------
    def can_resume(self, run: "_SlotRun") -> bool:
        """Whether a preempted ``run`` could later be re-admitted: its
        prompt + generated history must still fit the engine (dense: a
        prompt bucket; paged: the block pool). The preemption policy
        checks this BEFORE evicting — a victim that could never come
        back would be a silent kill, not a preemption."""
        if not run.tokens:
            return True          # mid-prefill: requeues as submitted
        req = run.request
        pl = int(np.asarray(req.prompt).reshape(-1).shape[0]) \
            + len(run.tokens) - 1
        mnt = req.max_new_tokens - len(run.tokens) + 1
        try:
            self.validate_request(pl, mnt)
        except ValueError:
            return False
        return True

    def preempt_slot(self, slot: int):
        """Evict the request in ``slot`` mid-flight WITHOUT failing it:
        the slot is killed in-graph through the same ``_cancel_fn``
        program deadlines use, its resources release (paged blocks at
        exact refcounts — the prefix-index entries are retained, which
        is what makes the later re-prefill mostly cache hits), and the
        run is handed back to the caller with the slot's rng key so the
        request can requeue carrying :class:`~.scheduler.ResumeState`.
        Returns ``(run, key)``; ``key`` is None for a mid-prefill
        victim (nothing armed yet — it requeues as-submitted). Only
        legal at a tick boundary, like snapshots."""
        run = self._slots[slot]
        if run is None:
            raise RuntimeError(f"slot {slot} is empty")
        if self._pending_block is not None:
            raise RuntimeError(
                "preempt only at a tick boundary — a dispatched decode "
                "block is awaiting harvest (call step_block first)")
        key = None
        if slot in self._prefill_slots:
            self._prefill_slots.discard(slot)
            self._abort_prefill(slot)
        else:
            key = np.asarray(self._state["key"])[slot].copy()
            self._state = self._cancel_jit(self._state, jnp.int32(slot))
        if self.tracer is not None:
            rid = run.request.request_id
            self.tracer.span_end(rid, "decode", preempted=True)
            self.tracer.instant(rid, "preempt", slot=slot,
                                tokens=len(run.tokens))
        self._free_slot(slot)
        self._remaining_host[slot] = 0
        self._release_slot_resources(run)
        return run, key

    def _release_slot_resources(self, run: "_SlotRun"):
        """Free everything a preempted run held besides the slot
        itself — dense rows are pool-owned, nothing to do (the paged
        engine releases the run's arena blocks here)."""

    # -- decode ------------------------------------------------------------
    def has_pending_harvest(self) -> bool:
        """A decode block was dispatched but its host transfer failed —
        the next :meth:`step_block` retries just the harvest."""
        return self._pending_block is not None

    def step_block(self):
        """Run one compiled decode block over the pool, then sync ONCE:
        pull the token matrix + remaining counters, credit each live
        slot its emitted tokens, retire finished slots.

        Failure semantics (fault sites / resilience): the
        ``serving.step_block`` site raises BEFORE the device dispatch
        (state untouched — a retry re-runs the identical block), and
        ``serving.harvest`` raises between dispatch and the host
        transfer; the dispatched outputs park in ``_pending_block`` so
        a retry harvests them without re-stepping (no token is ever
        decoded twice or dropped). A slot whose log-probs went NaN is
        quarantined alone via :meth:`cancel_slot` — the other rows'
        streams are untouched (bit-identical, pinned in tests)."""
        if self._pending_block is None:
            if not self.has_decoding():
                return
            if faults.should_fire("serving.poison"):
                self._poison_live_slot()
            faults.fault_point("serving.step_block")
            with _span("serving.decode_block",
                       **self._decode_block_counters()) as self._block_span:
                out = self.backend.decode_block(self._cache, self._state)
                self._enqueued(self._block_span)
            self._cache, self._state = out[0], out[1]
            # old AOT artifacts predate the ok flags: pad with None
            self._pending_block = tuple(out[2:]) \
                if len(out) > 4 else (out[2], out[3], None)
            self.steps += self.decode_block
            self.slot_steps += self.decode_block * self.num_slots
            _M_STEPS.inc(self.decode_block)
            _M_COMPILES.set(self.backend.decode_traces[0])
            self._note_decode_bytes(self.decode_block)
        faults.fault_point("serving.harvest")
        toks, lives, oks = self._pending_block
        began = self._stall_watch.begin()
        with _span("serving.decode_sync") as sp:   # host blocked on the device
            toks_np = np.asarray(toks)              # ONE host sync/block
            # the block is done and nothing else is queued: what follows
            # in this span are round trips to a drained device
            self._drained_ns = sp.mark("first")
            lives_np = np.asarray(lives)            # (block, S)
            oks_np = None if oks is None else np.asarray(oks)
            rem_np = np.asarray(self._state["remaining"])
            counts_np = self._read_program_counters()
            sp.ids["fetches"] = 3 + (oks is not None) \
                + (counts_np is not None)
        self._sync_ended(sp, began)
        self._pending_block = None
        with _span("serving.harvest"):
            self._credit_block(toks_np, lives_np, oks_np, rem_np)
            if counts_np is not None:
                self._credit_program_counters(counts_np)

    def _decode_block_counters(self) -> dict:
        """Counters the ``serving.decode_block`` span carries besides the
        engine's own (``steps``, ``slot_steps``): ``sampled_steps``; the
        paged engine adds its kernel's page walk."""
        return {"sampled_steps":
                self._count_sampled_steps(self.decode_block)}

    def _count_sampled_steps(self, steps: int) -> int:
        """Of the ``steps`` decode steps about to be dispatched, those in
        which ``slot_sample_logits`` takes its sampled branch: a live
        slot's request has ``temperature > 0``. From the host's mirrors
        (each slot's request, ``_remaining_host``), no device fetch; a
        slot stays live ``min(remaining, steps)`` steps, so one that
        meets its EOS inside the block is counted to the block's end."""
        n = max((min(int(self._remaining_host[slot]), steps)
                 for slot, run in enumerate(self._slots)
                 if run is not None and slot not in self._prefill_slots
                 and run.request.temperature > 0), default=0)
        self.sampled_steps += n
        return n

    def _read_program_counters(self):
        """What the model's programs counted into the cache (the paged
        engine of a model that declares ``cache_counters``), fetched with
        the block's other transfers; None where nothing is counted."""
        return None

    def _credit_block(self, toks_np, lives_np, oks_np, rem_np):
        """The host half of a decode block: credit each live slot its
        emitted tokens, quarantine poisoned rows, retire finished
        slots."""
        emitted = int(lives_np.sum())
        self.decode_tokens += emitted
        self.tokens_emitted += emitted
        _M_DECODE_TOKENS.inc(emitted)
        _M_TOKENS.inc(emitted)
        now = time.perf_counter()
        for slot, run in enumerate(self._slots):
            if run is None or slot in self._prefill_slots:
                continue     # mid-prefill slots are not decoding yet
            # live is monotone within a block (True rows are a prefix)
            n = int(lives_np[:, slot].sum())
            if n > 0:
                run.tokens.extend(int(t) for t in toks_np[:n, slot])
            if self.nan_sentinel and oks_np is not None and n > 0 \
                    and not bool(oks_np[:n, slot].all()):
                self.cancel_slot(slot, "poisoned")
                continue
            self._remaining_host[slot] = rem_np[slot]
            if rem_np[slot] == 0:
                self._retire(slot, run, now)

    # -- cancellation / quarantine ----------------------------------------
    def live_runs(self):
        """Host bookkeeping of every occupied slot: [(slot, _SlotRun)]
        (mid-prefill slots included) — the resilience layer's deadline
        scan."""
        return [(i, r) for i, r in enumerate(self._slots)
                if r is not None]

    def cancel_slot(self, slot: int, reason: str) -> bool:
        """Cancel the request in ``slot`` mid-flight: kill the slot
        in-graph (live drops before the next decode block), release its
        resources (paged: arena blocks at correct refcounts, pending
        prefill job dropped), and surface the run through
        ``drain_finished`` with ``failure=reason`` so the Server records
        a RequestFailure instead of hanging the stream."""
        run = self._slots[slot]
        if run is None:
            return False
        run.failure = reason
        if slot in self._prefill_slots:
            self._prefill_slots.discard(slot)
            self._abort_prefill(slot)   # paged: drop the pending job
        else:
            self._state = self._cancel_jit(self._state, jnp.int32(slot))
        self._retire(slot, run, time.perf_counter())
        self._remaining_host[slot] = 0
        return True

    def _abort_prefill(self, slot):
        """Dense admission is synchronous — nothing to abort."""

    def _poison_live_slot(self):
        """Fault action for the ``serving.poison`` site: corrupt the
        FIRST decoding slot's KV cache row with NaN so its next logits
        trip the sentinel. Only that slot's row is touched — the
        quarantine-blast-radius invariant the chaos tests pin."""
        for slot, run in enumerate(self._slots):
            if run is not None and slot not in self._prefill_slots:
                self._cache = tuple(
                    c.at[slot].set(jnp.nan)
                    if jnp.issubdtype(c.dtype, jnp.floating) else c
                    for c in self._cache)
                return slot
        return None

    def _retire(self, slot, run, now):
        """Move a finished slot to the harvest list (the paged engine
        also releases the slot's arena blocks here)."""
        run.t_done = now
        if self.tracer is not None:
            self.tracer.span_end(run.request.request_id, "decode",
                                 tokens=len(run.tokens))
        self._finished.append(run)
        self._free_slot(slot)

    def drain_finished(self) -> List[_SlotRun]:
        done, self._finished = self._finished, []
        return done

    # -- crash-safe snapshot / restore -------------------------------------
    def _run_meta(self, run: _SlotRun) -> dict:
        from .resilience import request_to_meta
        return {"request": request_to_meta(run.request),
                "tokens": [int(t) for t in run.tokens],
                "t_admit": run.t_admit, "t_done": run.t_done,
                "failure": run.failure,
                "block_ids": None if run.block_ids is None
                else [int(b) for b in run.block_ids]}

    def _run_from_meta(self, meta: dict, prompt) -> _SlotRun:
        from .resilience import request_from_meta
        return _SlotRun(request=request_from_meta(meta["request"], prompt),
                        tokens=list(meta["tokens"]),
                        t_admit=meta["t_admit"], t_done=meta["t_done"],
                        failure=meta["failure"],
                        block_ids=None if meta["block_ids"] is None
                        else list(meta["block_ids"]))

    def snapshot_state(self):
        """(meta dict, host-array dict) capturing everything needed to
        resume every in-flight stream: the KV cache, the in-graph slot
        state (positions, rng keys, sampling params — and the paged
        block tables riding it), and the host bookkeeping. Taken at a
        tick boundary (the only host-consistent point); a restored
        engine finishes each stream bit-identical to an uninterrupted
        run because the decode program is a pure function of exactly
        this state."""
        if self._pending_block is not None:
            raise RuntimeError(
                "snapshot only at a tick boundary — a dispatched decode "
                "block is awaiting harvest (call step_block first)")
        arrays = {}
        for i, c in enumerate(self._cache):
            arrays[f"cache_{i}"] = np.asarray(c)
        for k, v in self._state.items():
            arrays[f"state_{k}"] = np.asarray(v)
        slots_meta = []
        for i, run in enumerate(self._slots):
            if run is None:
                slots_meta.append(None)
                continue
            arrays[f"slot{i}_prompt"] = np.asarray(
                run.request.prompt, np.int32).reshape(-1)
            slots_meta.append(self._run_meta(run))
        fin_meta = []
        for j, run in enumerate(self._finished):
            arrays[f"fin{j}_prompt"] = np.asarray(
                run.request.prompt, np.int32).reshape(-1)
            fin_meta.append(self._run_meta(run))
        meta = {
            "engine_class": type(self).__name__,
            # artifact-backed engines record which programs produced
            # this state; model-backed engines record None (either side
            # None -> compatibility is left to the pool_specs check)
            "backend_artifact": getattr(self.backend,
                                        "artifact_fingerprint", None),
            "num_slots": self.num_slots, "max_len": self.max_len,
            "decode_block": self.decode_block,
            "pool_specs": [[list(s), str(np.dtype(d))]
                           for s, d in self.backend.pool_specs],
            "remaining": [int(r) for r in self._remaining_host],
            "prefill_slots": sorted(self._prefill_slots),
            "slots": slots_meta, "finished": fin_meta,
            "counters": {"steps": self.steps,
                         "sampled_steps": self.sampled_steps,
                         "tokens_emitted": self.tokens_emitted,
                         "decode_tokens": self.decode_tokens,
                         "slot_steps": self.slot_steps},
        }
        return meta, arrays

    def restore_state(self, meta: dict, arrays: dict):
        """Inverse of :meth:`snapshot_state`, into a freshly
        constructed engine of the SAME configuration (same model/
        backend shapes — validated against ``pool_specs``). Compiled
        programs are rebuilt lazily by the new process; only state is
        restored."""
        want = [[list(s), str(np.dtype(d))]
                for s, d in self.backend.pool_specs]
        if meta["pool_specs"] != want:
            raise ValueError(
                "snapshot pool_specs do not match this engine — restore "
                "needs the same model config / slots / max_len / paging "
                f"layout (saved {meta['pool_specs'][:2]}..., engine "
                f"{want[:2]}...)")
        if meta["engine_class"] != type(self).__name__:
            raise ValueError(
                f"snapshot was taken by {meta['engine_class']}, this "
                f"engine is {type(self).__name__} (dense/paged mismatch)")
        saved_fp = meta.get("backend_artifact")
        cur_fp = getattr(self.backend, "artifact_fingerprint", None)
        if saved_fp is not None and cur_fp is not None \
                and saved_fp != cur_fp:
            raise ValueError(
                "snapshot was taken on a different AOT artifact "
                f"(saved {saved_fp[:12]}..., this backend "
                f"{cur_fp[:12]}...) — restore with the artifact that "
                "produced the snapshot")
        self.reset()
        self._cache = tuple(jnp.asarray(arrays[f"cache_{i}"])
                            for i in range(len(self.backend.pool_specs)))
        self._state = {k: jnp.asarray(arrays[f"state_{k}"])
                       for k in self.backend.init_state()}
        commit = getattr(self.backend, "commit_arrays", None)
        if commit is not None:        # TP backends re-shard onto the mesh
            self._cache, self._state = commit(self._cache, self._state)
        self._slots = [
            None if m is None
            else self._run_from_meta(m, arrays[f"slot{i}_prompt"])
            for i, m in enumerate(meta["slots"])]
        self._finished = [
            self._run_from_meta(m, arrays[f"fin{j}_prompt"])
            for j, m in enumerate(meta["finished"])]
        self._prefill_slots = set(meta["prefill_slots"])
        self._remaining_host = np.asarray(meta["remaining"], np.int64)
        c = meta["counters"]
        self.steps = c["steps"]
        self.sampled_steps = c.get("sampled_steps", 0)
        self.tokens_emitted = c["tokens_emitted"]
        self.decode_tokens = c["decode_tokens"]
        self.slot_steps = c["slot_steps"]

    def snapshot(self, path: str):
        """Write a crash-safe engine snapshot (single npz file, atomic
        tmp+rename via the checkpoint write helpers)."""
        from .resilience import save_snapshot
        meta, arrays = self.snapshot_state()
        save_snapshot(path, {"engine": meta}, arrays)

    def restore(self, path: str):
        from .resilience import load_snapshot
        meta, arrays = load_snapshot(path)
        self.restore_state(meta["engine"], arrays)
