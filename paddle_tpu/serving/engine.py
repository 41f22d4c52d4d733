"""Continuous-batching decode engine over a block-paged KV arena: a fixed
pool of S sequence slots kept alive inside ONE jitted decode program.

Reference parity: the reference serving stack's fused_multi_transformer
decode loop + PaddleNLP's dynamic-batching inference server (SURVEY §2.1
Inference, §3.5 AnalysisPredictor — verify); the design is the
vLLM-style continuous batching discipline restated under the repo's
static-shape rules.

TPU-native design: the KV cache is ONE ``(num_blocks, block_size, kvh,
d)`` arena per layer (``serving/paging.py``: the block manager and the
step backends) that never reshapes, read and written by every slot
through its row of an in-graph ``(S, max_blocks)`` block table (vLLM's
PagedAttention: the table is state, the arena a fixed shape). Per-slot
``pos``/``live``/``eos``/``remaining``/rng-key/sampling-param state and
the tables ride in-graph as (S,)-leading arrays, so ONE compiled program
(a ``lax.scan`` of the shared decode step over ``decode_block`` tokens)
serves every mix of request depths, greedy/sampled traffic, and
admission pattern — zero recompiles across the stream. Prompts are
unpadded and stream into the arena through ONE fixed-shape chunk
program, interleaved with decode blocks and paced by the scheduler's
per-tick prefill token budget; whole prompt blocks are shared across
requests through the block manager's rolling-hash prefix index. The
defining invariant: a continuously-batched stream of ragged greedy
requests is bit-identical to per-request ``generate()`` calls.
"""
from __future__ import annotations

import functools
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

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

__all__ = ["ContinuousBatchingEngine", "slot_sample_logits",
           "init_slot_state", "build_slot_block_fn", "build_paged_chunk_fn"]


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


def build_slot_block_fn(pure, block: int, trace_counter=None):
    """The engine's ONE decode program: ``lax.scan`` of the shared step
    over ``block`` tokens with per-slot positions. Each scan iteration:
    per-slot key split -> forward (vector ``pos``, the slot's block
    table) -> per-slot sampling -> in-graph eos/budget retirement (a
    finished slot's ``live`` drops and its pos/tok freeze — it is masked
    junk until the host refills it between blocks). Emits the (block, S)
    token matrix plus per-step live-slot counts (the occupancy/tok-s
    numerators), so the host syncs ONCE per block.

    The state carries a per-slot block ``table`` and the cache is the
    shared block arena; dead slots' tables are redirected to the trash
    block 0 IN-GRAPH, so a retired slot whose blocks the host has
    already handed to another request can never scatter junk into them
    mid-block.

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
            tbl = jnp.where(st["live"][:, None], st["table"], 0)
            logp, cf = pure(pv, bv, st["tok"][:, None], cf,
                            st["pos"], None, None, tbl)
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


def build_paged_chunk_fn(pure, chunk: int, trace_counter=None):
    """ONE chunked-prefill program for every prompt of every length:
    a fixed ``(1, chunk)`` right-padded token window written straight
    into the paged arena through the request's block table (pad columns
    carry junk K/V that decode overwrites before it can ever be
    attended — writes past the table width land in the trash block).
    The candidate first token is sampled in-graph from the last REAL
    column with the request's own params; the host uses it only on the
    final chunk. It compiles exactly once. Every call streams all the
    weights whatever ``chunk`` is, so the chunk an engine picks by
    itself (``paging.default_prefill_chunk``) is the ridge where the
    chunk's matmuls catch up with that read."""

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


@functools.lru_cache(maxsize=1024)
def _key_schedule(seed: int):
    """``generate()``'s key schedule for a request's seed: ``(key, sub)``,
    the key the slot is armed with and the prefill chunk's draw. Cached:
    made eagerly, PRNGKey, split and the unpacking are three dispatches an
    admission, about 1.6 ms of host time on a TPU host while the chip
    waits (the arrays are never donated)."""
    key, sub = jax.random.split(jax.random.PRNGKey(seed))
    return key, sub


def _cancel_fn(state, slot):
    """Kill one slot in-graph (deadline/poison cancellation): ``live``
    drops and ``remaining`` zeroes, so the next decode block treats the
    row as retired junk and redirects its table to the trash block. One
    compiled program serves every cancellation."""
    return dict(state,
                live=state["live"].at[slot].set(False),
                remaining=state["remaining"].at[slot].set(0))


def _arm_fn(state, slot, table_row, tok0, pos0, rem0, eos0, temp0,
            topk0, topp0, key0):
    """Turn a slot live after its chunked prefill finished: the arena
    already holds the prompt's K/V, so arming is a pure state update.
    ``slot`` is traced — one compiled program serves every arming."""

    def set1(a, v):
        return a.at[slot].set(jnp.asarray(v, a.dtype))

    return dict(
        state, tok=set1(state["tok"], tok0),
        pos=set1(state["pos"], pos0),
        pad=set1(state["pad"], 0),        # prompts are unpadded
        live=set1(state["live"], rem0 > 0),
        eos=set1(state["eos"], eos0),
        remaining=set1(state["remaining"], rem0),
        key=state["key"].at[slot].set(key0),
        temp=set1(state["temp"], temp0),
        topk=set1(state["topk"], topk0),
        topp=set1(state["topp"], topp0),
        table=state["table"].at[slot].set(table_row))


@dataclass
class _SlotRun:
    """Host-side bookkeeping for one in-flight request. ``t_admit`` is
    the moment the first token existed (prefill completion) — the TTFT
    timestamp. ``block_ids``: the run's arena blocks to release at
    retirement (None once released);
    ``window``: what the hybrid engine's run holds in its window pool;
    ``chain``: ``(digest, tokens)`` of the leading blocks of what the run
    writes (prompt, then history) that the block manager has hashed so
    far — a cache it extends, never serialized."""
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


@dataclass
class _PrefillJob:
    """One admitted request still streaming its prompt into the arena
    (``done`` counts tokens already resident, including the shared
    prefix it skipped). For a preemption resume, ``prompt`` is the
    original prompt plus the generated history being re-prefilled and
    ``resume_tok`` is the carried in-hand next token — the chunk
    programs' in-graph samples are discarded and the slot arms with it
    instead."""
    run: _SlotRun
    slot: int
    prompt: np.ndarray
    done: int
    table_row: np.ndarray          # (max_blocks,) int32
    key: jnp.ndarray               # post-split state key
    sub: jnp.ndarray               # prefill sampling key
    temp: jnp.ndarray
    topk: jnp.ndarray
    topp: jnp.ndarray
    tok0: Optional[int] = None
    resume_tok: Optional[int] = None


@dataclass
class _Reservation:
    """What an admission holds once its blocks are reserved: the run's
    blocks (shared prefix first), its table row, how many leading prompt
    blocks were served from the prefix index, what the ``serving.admit``
    span says of it, and the digests the lookup computed (``chain``, for
    the run to carry). An engine with a second pool adds what the run
    holds there (``window``)."""
    block_ids: List[int]
    table_row: np.ndarray
    shared_blocks: int
    span_ids: dict
    chain: list
    window: Optional[object] = None


class ContinuousBatchingEngine:
    """Paged-KV continuous batching over a step backend. The host syncs
    with the device once per ``decode_block`` tokens: it reads the
    (block, S) token matrix plus the post-block ``remaining`` counters,
    harvests retired requests, and refills free slots — the decode
    program itself is compiled exactly once for the engine's lifetime.

    - ``try_admit()`` reserves the request's blocks (sharing a matched
      prefix) and queues a prefill job; it returns False when the block
      pool cannot hold it yet — the Server re-queues and retries after
      retirements free blocks. The prompt streams into the arena via
      :meth:`prefill_tick` (chunk programs), and the slot arms when its
      last chunk lands.
    - prompts are UNPADDED: position 0 is token 0, which is what makes
      whole prefix blocks shareable across requests.
    - ``prefill_chunk`` (else ``PT_SERVING_PREFILL_CHUNK``) is the chunk
      program's length in tokens; when neither is given it is
      :func:`~.paging.default_prefill_chunk` — the program's ridge,
      where a chunk's matmuls take as long as the weight read every
      chunk pays, so a prompt re-reads the weights once per ~256 tokens.
      :meth:`chunk_fill_share` says how much of the chunks was prompt.
    - ``kv_int8=True`` (else ``PT_SERVING_KV_INT8``) stores the arena as
      int8 codes + per-vector absmax scales; :meth:`kv_error_bound`
      says the worst-case dequant error.

    Constructing this class is the factory: a model whose cache keeps
    two groups of layers (``kv_cache_groups``) gets
    :class:`~.hybrid.HybridPagedEngine`, and ``spec=`` (or a
    verify-capable backend) gets :class:`~.spec.SpecPagedEngine`."""

    def __new__(cls, model=None, *args, backend=None, spec=None, **kw):
        if cls is ContinuousBatchingEngine:
            if backend is None and getattr(model, "kv_cache_groups", None):
                # a model whose cache keeps two groups of layers (full
                # and sliding-window): its own engine
                from .hybrid import HybridPagedEngine
                return object.__new__(HybridPagedEngine)
            from .spec import SpecPagedEngine, spec_requested
            if spec_requested(spec, backend):
                return object.__new__(SpecPagedEngine)
        return object.__new__(cls)

    def __init__(self, model=None, num_slots: int = 4, max_len: int = 256,
                 decode_block: int = 8, *, backend=None, paged: bool = True,
                 spec=None, block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 kv_int8: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 hash_fn=None, tp=None, quant=None):
        from .paging import BlockManager
        if not paged:
            raise ValueError(
                "paged=False: the dense slot-pool engine was removed — "
                "the block-paged arena is the serving stack's one KV "
                "layout (drop paged=)")
        if backend is not None:
            # the backend already baked these in — a silently ignored
            # kv_int8=True (fp32 arena, bound 0.0), num_blocks or
            # quant= (including quant=False against a quantized backend,
            # which cannot be de-quantized) would be a misconfiguration,
            # not a preference
            given = {k: v for k, v in (("block_size", block_size),
                                       ("num_blocks", num_blocks),
                                       ("kv_int8", kv_int8),
                                       ("prefill_chunk", prefill_chunk),
                                       ("quant", quant))
                     if v is not None}
            if given:
                raise ValueError(
                    f"{sorted(given)} cannot be set alongside an "
                    "explicit backend — they are baked into it at "
                    "construction")
        else:
            if model is None:
                raise ValueError("pass a model or a step backend")
            backend = self._resolve_backend(
                model, num_slots, max_len, decode_block, block_size,
                num_blocks, kv_int8, prefill_chunk, tp, quant)
        if spec and not hasattr(self, "spec_k"):
            # only the factory (ContinuousBatchingEngine(...)) routes
            # spec= to the speculative engine; a direct subclass
            # constructor silently ignoring it would be a
            # misconfiguration, not a preference
            raise ValueError(
                "spec= is only honored through the "
                "ContinuousBatchingEngine factory (or construct "
                "serving.spec.SpecPagedEngine directly)")
        self.backend = backend
        self.num_slots = backend.num_slots
        self.max_len = backend.max_len
        self.decode_block = backend.block_size
        self.kv_block_size = backend.kv_block_size
        self.num_kv_blocks = backend.num_kv_blocks
        self.max_blocks = backend.max_blocks
        self.kv_int8 = backend.kv_int8
        self.prefill_chunk_len = backend.prefill_chunk_len
        # passes a step makes over the layers (1 unless the model loops)
        # and the cached-attention sites a decode step holds: the page
        # counters below stay ONE site's
        self.cache_passes = getattr(backend, "cache_passes", 1)
        # conv layers whose state the cache carries under the block ids:
        # an admission then starts from a shared block's state or cold
        self.conv_state_layers = getattr(backend, "conv_state_layers", 0)
        self.attn_sites = getattr(backend, "attn_sites", 0)
        self.manager = BlockManager(self.num_kv_blocks,
                                    self.kv_block_size, hash_fn)
        self._arm_jit = jax.jit(_arm_fn, donate_argnums=(0,))
        self._cancel_jit = jax.jit(_cancel_fn, donate_argnums=(0,))
        # host-side gate on the in-graph NaN flags (the flags are
        # always computed — same single compiled program either way)
        self.nan_sentinel = True
        # set by the Server iff request tracing is armed (None keeps
        # the hot paths at one `is None` check)
        self.tracer = None
        self.reset()

    def _resolve_backend(self, model, num_slots, max_len, decode_block,
                         block_size, num_blocks, kv_int8, prefill_chunk,
                         tp, quant):
        """The step backend for ``model``: unset knobs resolved (explicit
        arg > env knob > default), tensor-parallel when ``tp`` (or
        ``PT_SERVING_TP``) asks for it."""
        from ..utils.flags import env_flag, env_int
        from .paging import default_prefill_chunk
        from .quant import resolve_quant_config
        from .tp import resolve_tp_config
        if block_size is None:
            # resolution order: explicit arg > env knob > a valid
            # (stamp-matching) autotune-table winner > the documented
            # default 16 — a stale table never silently reshapes arenas
            block_size = env_int("PT_SERVING_BLOCK_SIZE", 0)
            if block_size <= 0:
                from ..ops.pallas.autotune import tuned_paged_block_size
                block_size = tuned_paged_block_size(16)
        if num_blocks is None:
            # every slot's whole table + trash by default — HBM savings
            # come from passing a smaller pool (plus sharing);
            # correctness never depends on the pool being oversized
            num_blocks = 1 + num_slots * (max_len // block_size)
        if kv_int8 is None:
            kv_int8 = env_flag("PT_SERVING_KV_INT8")
        if prefill_chunk is None:
            prefill_chunk = env_int(
                "PT_SERVING_PREFILL_CHUNK",
                default_prefill_chunk(block_size, max_len))
        tp_cfg = resolve_tp_config(tp)
        q_cfg = resolve_quant_config(quant)
        if tp_cfg is not None:
            # tensor-parallel serving: the SAME decode/chunk programs,
            # sharded over a mesh (serving/tp.py); an explicitly passed
            # backend is never rerouted by the env flag
            from .tp import ShardedPagedStepBackend
            return ShardedPagedStepBackend(
                model, num_slots, max_len, decode_block, block_size,
                num_blocks, bool(kv_int8), prefill_chunk, tp_cfg,
                quant=q_cfg)
        return self._build_backend(
            model, num_slots, max_len, decode_block, block_size,
            num_blocks, bool(kv_int8), prefill_chunk, q_cfg)

    def _build_backend(self, model, num_slots, max_len, decode_block,
                       block_size, num_blocks, kv_int8, prefill_chunk,
                       quant=None):
        """Subclass hook: the speculative and hybrid engines build their
        own step backend here."""
        from .paging import PagedModelStepBackend
        return PagedModelStepBackend(
            model, num_slots, max_len, decode_block, block_size,
            num_blocks, kv_int8, prefill_chunk, quant=quant)

    # -- lifecycle ---------------------------------------------------------
    def reset(self):
        """Free every slot and zero the counters (compiled programs are
        kept — repeat streams never recompile)."""
        self._cache = self.backend.pool_cache()
        self._state = self.backend.init_state()
        self._slots: List[Optional[_SlotRun]] = [None] * self.num_slots
        self._prefill_slots: set = set()   # mid-prefill slots
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
        self.manager.reset()
        self._jobs: List[_PrefillJob] = []
        self.prompt_tokens = 0         # all prompt tokens submitted
        self.shared_tokens = 0         # skipped via prefix reuse
        self.prefilled_tokens = 0      # actually computed
        self.prefill_chunks = 0        # chunk programs dispatched
        self.fetched_tokens = 0        # of shared: remote-fetched KV
        # the decode kernel's walk (per attention layer), and the host's
        # mirror of the in-graph per-slot ``pos`` it is counted from
        self.kv_pages_live = 0         # pages holding a slot's live KV
        self.kv_pages_copied = 0       # pages the kernel copied for them
        self.kv_pages_in_runs = 0      # of the live, pages moved in runs
        # rows a layer's decode read MUST see, summed over the decoding
        # slots and steps: every live token
        self.kv_rows_live = 0
        # each slot's table row as the walk's run copies see it
        # (``paged_attention.run_groups``), from the row it was armed with
        self._run_groups = self._table_run_groups(
            np.zeros((self.num_slots, self.max_blocks), np.int32))
        self.ut_steps = 0              # looped model: passes run by decode
        # conv-state model: admissions whose conv state starts from a
        # shared prefix block's entry, and those that start from zeros
        self.conv_warm_admissions = 0
        self.conv_cold_admissions = 0
        self._pos_host = np.zeros((self.num_slots,), np.int64)
        # what the model's programs count into the cache's last leaf
        # (``cache_counters``: name -> "sum" | "max"; a (2, n) int32 array,
        # decode steps apart from prefill chunks): the decode row becomes
        # an engine counter of that name, the chunk row ``prefill_<name>``
        self._counter_names = tuple(
            getattr(self.backend, "cache_counters", {}).items())
        self._counts_seen = np.zeros((2, len(self._counter_names)),
                                     np.int64)
        for name, _ in self._counter_names:
            setattr(self, name, 0)
            setattr(self, "prefill_" + name, 0)
        self._chunk_span = None        # the latest ``serving.prefill_chunk``
        self._chunks_unread = 0        # chunks since the counters were read
        self._arm_ns = 0               # ends of prefill since the last block

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
        for want of traffic is not starvation."""
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
        differs from :meth:`has_live` where a slot is occupied but still
        mid-chunked-prefill."""
        return any(s is not None and i not in self._prefill_slots
                   for i, s in enumerate(self._slots))

    def has_prefill(self) -> bool:
        """Any admitted prompt still streaming into the arena (a chunk
        for :meth:`prefill_tick` to run)."""
        return bool(self._jobs)

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

    def prefix_cache_hit_rate(self) -> float:
        """Fraction of submitted prompt tokens served from shared
        prefix blocks instead of recomputed."""
        return self.shared_tokens / self.prompt_tokens \
            if self.prompt_tokens else 0.0

    def chunk_fill_share(self) -> float:
        """Fraction of the chunk programs' columns that held a prompt
        token (the rest was right-padding): a longer chunk re-reads the
        weights less often and pads more."""
        columns = self.prefill_chunks * self.prefill_chunk_len
        return self.prefilled_tokens / columns if columns else 0.0

    def prefill_compile_count(self) -> int:
        return self.backend.prefill_traces[0]

    def kv_error_bound(self) -> float:
        """Runtime worst-case |dequantized - fp32| over the int8 arena
        (0.0 in fp32 mode): the EQuARX single-quantization bound from
        the largest live absmax scale."""
        if not self.kv_int8:
            return 0.0
        from ..ops.pallas.paged_attention import kv_int8_error_bound
        worst = 0.0
        for (shape, dtype), buf in zip(self.backend.pool_specs,
                                       self._cache):
            if np.dtype(dtype) == np.float32 and len(shape) == 3:
                worst = max(worst, float(jnp.max(buf)))
        return float(kv_int8_error_bound(worst))

    def weight_error_bound(self) -> float:
        """Build-time worst-case elementwise |dequant - fp32| over the
        weight-only-quantized decode weights (half the largest
        quantization step; 0.0 when quant is off)."""
        return float(self.backend._weight_bound)

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
            w = self.backend.param_bytes()
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

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        # positions written: prompt [0, L) plus generated tokens at
        # [L, L+max_new-1) — the final sampled token is never written
        return -(-(prompt_len + max(max_new_tokens - 1, 0))
                 // self.kv_block_size)

    def validate_request(self, prompt_len: int, max_new_tokens: int):
        """Raise ValueError if the request can never fit a slot — run
        at submit time so a bad request is rejected at the door instead
        of aborting the serving loop mid-stream at admission."""
        if prompt_len <= 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens={max_new_tokens}; must be >= 1")
        if prompt_len + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the slot capacity "
                f"({self.max_len}); raise max_len or shorten the request")
        need = self.blocks_needed(prompt_len, max_new_tokens)
        # the MANAGER is the source of truth, not the engine's
        # num_kv_blocks attribute: allocate() draws from the manager,
        # so validating against a stale attribute let an impossible
        # request through the door and into run_until_idle's re-queue
        # path forever (the PR-5 livelock fix; regression-pinned with a
        # tiny pool in tests/test_resilience.py)
        pool = self.manager.usable_blocks()
        if need > pool:
            raise ValueError(
                f"request needs {need} KV blocks but the arena only "
                f"has {pool}; raise num_blocks or shorten the request")

    # -- admission ---------------------------------------------------------
    def try_admit(self, request) -> bool:
        """Block allocation, prefix lookup and slot arming for one
        request (the chunks themselves run in :meth:`prefill_tick`);
        False when the block pool cannot hold it yet. The span says how
        many blocks the admission allocated (``fresh_blocks``) and how
        many of those it took by eviction (``evicted_blocks``), and how
        many block digests it computed (``hashed_blocks``); an admission
        that went through also marks where its time went: ``reserved_ns``
        when the lookup, the allocation and the table row were done,
        ``keyed_ns`` when the request's key was made and split; the rest
        to the span's end is the sampling scalars' uploads and the job."""
        with _span("serving.admit", rid=request.request_id) as sp:
            m = self.manager
            evicted, hashed = m.evictions, m.hashed_blocks
            ids = self._try_admit(request, sp)
            sp.ids.update(
                ids or {"fresh_blocks": 0},
                evicted_blocks=m.evictions - evicted,
                hashed_blocks=m.hashed_blocks - hashed)
            return ids is not None

    def _reserve(self, full, mnt) -> Optional[_Reservation]:
        """Blocks and table row for a request of prompt ``full`` and
        ``mnt`` new tokens: the matched prefix's blocks shared, the rest
        fresh; None when the pool cannot cover it yet (nothing held)."""
        chain = []
        shared = self._match_prefix_for_admission(full, chain)
        total = self.blocks_needed(len(full), mnt)
        fresh = self.manager.allocate(total - len(shared))
        if fresh is None:            # pool exhausted: retry later
            self.manager.release(shared)
            return None
        block_ids = shared + fresh
        table_row = np.zeros((self.max_blocks,), np.int32)
        table_row[:len(block_ids)] = block_ids
        return _Reservation(block_ids, table_row, len(shared),
                            {"fresh_blocks": len(fresh)}, chain)

    def _try_admit(self, request, sp) -> Optional[dict]:
        """The admission proper, marking its span ``sp``: what the span
        says of the blocks it allocated, or None when a pool cannot hold
        the request yet."""
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        resume = getattr(request, "resume", None)
        if resume is not None and resume.tokens:
            # preemption resume: the "prompt" to prefill is the original
            # prompt plus the generated history minus the in-hand next
            # token; the first full prompt blocks are usually still in
            # the prefix index (eviction retained them), so most of this
            # re-prefill is cache hits rather than recompute
            full = np.concatenate([
                prompt, np.asarray(resume.tokens[:-1], np.int32)])
            mnt = request.max_new_tokens - len(resume.tokens) + 1
        else:
            resume = None
            full, mnt = prompt, request.max_new_tokens
        L = int(full.shape[0])
        self.validate_request(L, mnt)
        slot = next((i for i, s in enumerate(self._slots) if s is None),
                    None)
        if slot is None:
            raise RuntimeError("no free slot (scheduler bug)")
        held = self._reserve(full, mnt)
        if held is None:
            return None
        sp.mark("reserved")
        block_ids, table_row = held.block_ids, held.table_row
        if self.tracer is not None:
            self.tracer.span_end(request.request_id, "queue_wait",
                                 shared_blocks=held.shared_blocks,
                                 fresh_blocks=held.span_ids["fresh_blocks"],
                                 resumed=resume is not None)
        if resume is None:
            key, sub = _key_schedule(int(request.seed))
            run = _SlotRun(request, block_ids=block_ids)
            resume_tok = None
        else:
            # the saved key IS the next step's split input — arming with
            # it (and discarding the chunk programs' in-graph samples)
            # keeps seeded-sampled resumes bit-identical
            key = jnp.asarray(np.asarray(resume.key, np.uint32))
            sub = jax.random.PRNGKey(0)            # discarded draw
            run = _SlotRun(request, tokens=list(resume.tokens),
                           t_admit=resume.t_admit, block_ids=block_ids)
            resume_tok = int(resume.tokens[-1])
        sp.mark("keyed")
        run.window, run.chain = held.window, held.chain
        self._slots[slot] = run
        self._prefill_slots.add(slot)
        n_shared = held.shared_blocks * self.kv_block_size
        if self.conv_state_layers:
            if held.shared_blocks:
                self.conv_warm_admissions += 1
            else:
                self.conv_cold_admissions += 1
        self.prompt_tokens += L
        self.shared_tokens += n_shared
        self._jobs.append(_PrefillJob(
            run=run, slot=slot, prompt=full, done=n_shared,
            table_row=table_row, key=key, sub=sub,
            temp=np.float32(request.temperature),
            topk=np.int32(request.top_k),
            topp=np.float32(request.top_p), resume_tok=resume_tok))
        return held.span_ids

    def _match_prefix_for_admission(self, full, chain: list) -> List[int]:
        """Admission-time prefix match. The base engine consults only
        its LOCAL index; the fleet's prefill engines override this to
        also fetch a longer chain another worker has registered
        (serving/prefix_cache.py) — either way the returned blocks are
        ref-acquired for the admitting request and ``done`` starts past
        them. ``chain`` receives the digests the local lookup computed."""
        return self.manager.match_prefix(full, chain)

    def admit(self, request) -> bool:
        if not self.try_admit(request):
            raise RuntimeError(
                "KV block pool exhausted; use try_admit/Server (which "
                "re-queue) or raise num_blocks")
        return False

    # -- chunked prefill ---------------------------------------------------
    def prefill_tick(self, token_budget: Optional[int] = None) -> int:
        """Advance pending prefill jobs by up to ``token_budget`` prompt
        tokens (always at least one chunk when work is pending, so a
        tiny budget still progresses). Jobs run FIFO; a finished job
        arms its slot (or retires immediately on eos/max_new==1)."""
        spent = 0
        C = self.prefill_chunk_len
        while self._jobs and (token_budget is None or spent == 0
                              or spent < token_budget):
            # fires BEFORE the chunk dispatch: the job's cursor hasn't
            # advanced, so a retry re-dispatches the identical chunk
            faults.fault_point("serving.prefill_tick")
            job = self._jobs[0]
            L = len(job.prompt)
            n = min(C, L - job.done)
            ids = np.zeros((1, C), np.int32)
            ids[0, :n] = job.prompt[job.done:job.done + n]
            tr = self.tracer
            t_chunk = _trace_now() if tr is not None else 0.0
            with _span("serving.prefill_chunk",
                       rid=job.run.request.request_id,
                       tokens=n) as self._chunk_span:
                # host values go to the program as they are: it moves
                # them in one call, where an eager jnp conversion is a
                # dispatch of its own (0.1-0.5 ms each)
                tok0_dev, self._cache = self.backend.prefill_chunk(
                    ids, self._cache, np.asarray(job.table_row[None]),
                    np.int32(job.done), np.int32(n),
                    job.sub, job.temp, job.topk, job.topp)
                self._enqueued(self._chunk_span)
            job.done += n
            spent += n
            self.prefill_chunks += 1
            self._chunks_unread += 1
            self.prefilled_tokens += n
            _M_PREFILLS.inc()
            if tr is not None:
                tr.span_at(job.run.request.request_id, "prefill_chunk",
                           t_chunk, tokens=n, done=job.done, total=L)
            if job.done >= L:
                self._jobs.pop(0)
                self._finish_prefill(job, tok0_dev)
        return spent

    def _finish_prefill(self, job: _PrefillJob, tok0_dev):
        req = job.run.request
        now = time.perf_counter()
        eos = req.eos_token_id
        if job.resume_tok is not None:
            # preemption resume: the carried stream owns the next token
            # — the chunk's in-graph sample is discarded, tokens and the
            # TTFT timestamp ride over from the evicted run
            t_synced = time.perf_counter_ns()       # nothing is fetched
            tok0 = job.resume_tok
            rem0 = req.max_new_tokens - len(job.run.tokens)
            req.resume = None
            if self.tracer is not None:
                self.tracer.instant(req.request_id, "resume",
                                    slot=job.slot,
                                    reused_tokens=len(job.run.tokens))
        else:
            began = self._stall_watch.begin()
            with _span("serving.prefill_sync", rid=req.request_id) as sp:
                tok0 = int(tok0_dev)        # host blocked on the last chunk
            t_synced = sp.start + sp.dur
            if self._pending_block is None:     # the last chunk was all
                self._drained_ns = t_synced     # the device had queued
            self._sync_ended(sp, began)
            job.run.tokens = [tok0]
            job.run.t_admit = now           # TTFT timestamp
            self.tokens_emitted += 1
            _M_TOKENS.inc()
            rem0 = req.max_new_tokens - 1
            if eos is not None and tok0 == eos:
                rem0 = 0
        # the prompt's full blocks are resident now — index them so the
        # NEXT request with this prefix skips the compute
        self._register_prompt(job)
        self._prefill_slots.discard(job.slot)
        if rem0 <= 0:                # finished at admission
            self._retire(job.slot, job.run, now)
        else:
            self._arm(job.slot, job.table_row, tok0, len(job.prompt), rem0,
                      eos, job.temp, job.topk, job.topp, job.key)
            if self.tracer is not None:
                self.tracer.span_begin(req.request_id, "decode",
                                       slot=job.slot)
        # host time between the last chunk's end and the next enqueue that
        # no span covers (it is ``serving.tick``'s own): the next
        # ``serving.decode_block`` carries the sum as ``arm_ns``
        self._arm_ns += time.perf_counter_ns() - t_synced

    def _register_prompt(self, job: _PrefillJob):
        """Index the prompt's shareable blocks (now filled) from the
        chain the run carries since its admission, extended over the
        blocks the lookup stopped short of."""
        m, run = self.manager, job.run
        m.register_chain(
            m.extend_chain(run.chain, job.prompt,
                           m._shareable_blocks(job.prompt)),
            run.block_ids)

    def _arm(self, slot, table_row, tok0, pos0, rem0, eos, temp, topk,
             topp, key):
        """Arm ``slot`` for decoding, in-graph and in the host's mirrors
        of its remaining count and position (the one arm site: local
        prefill completion and the fleet's adopted hand-offs)."""
        self._state = self._arm_jit(
            self._state, np.int32(slot), np.asarray(table_row, np.int32),
            np.int32(tok0), np.int32(pos0), np.int32(rem0),
            np.int32(-1 if eos is None else eos), temp, topk, topp, key)
        self._remaining_host[slot] = rem0
        self._pos_host[slot] = pos0
        self._run_groups[slot] = self._table_run_groups(
            np.asarray(table_row)[None])[0]

    def _table_run_groups(self, rows):
        """:func:`paged_attention.run_groups` of the full-length table's
        columns of ``rows``."""
        from ..ops.pallas.paged_attention import run_groups
        return run_groups(rows[:, :self.max_blocks])

    # -- preemption --------------------------------------------------------
    def can_resume(self, run: "_SlotRun") -> bool:
        """Whether a preempted ``run`` could later be re-admitted: its
        prompt + generated history must still fit the engine (its slot
        capacity and the block pool). The preemption policy checks this
        BEFORE evicting — a victim that could never come back would be a
        silent kill, not a preemption."""
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

    def _release_slot_resources(self, run):
        """Retirement and preemption release: the run's arena blocks
        drop one ref — registered prompt-prefix blocks park in the LRU
        cache (their prefix-index entries RETAINED, so a preempted run's
        re-prefill is mostly cache hits), unregistered decode blocks
        return to the free list."""
        if run.block_ids is not None:
            self.manager.release(run.block_ids)
            run.block_ids = None

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
            self._pending_block = tuple(out[2:])
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
            oks_np = np.asarray(oks)
            rem_np = np.asarray(self._state["remaining"])
            counts_np = self._read_program_counters()
            sp.ids["fetches"] = 4 + (counts_np is not None)
        self._sync_ended(sp, began)
        self._pending_block = None
        with _span("serving.harvest"):
            self._credit_block(toks_np, lives_np, oks_np, rem_np)
            if counts_np is not None:
                self._credit_program_counters(counts_np)

    def _decode_block_counters(self) -> dict:
        """Counters the ``serving.decode_block`` span carries besides the
        engine's own (``steps``, ``slot_steps``): ``sampled_steps``, and
        the pages one layer's paged decode reads walk over the block about
        to be dispatched, from the host's mirrors of ``pos`` and
        ``remaining`` (no device fetch): at step k a slot's read is
        ``pos + min(k, steps it stays live) + 1`` tokens long, and a dead
        slot re-reads the length it was left at, through its zeroed table
        row. ``ops.pallas.paged_attention.walk_counts`` mirrors the
        kernel's loop bound. A slot that meets its EOS inside the block
        is counted as if it ran to the block's end (at most one page a
        block over). ``kv_rows_live``: the tokens those reads must see,
        over the decoding slots. ``kv_pages_in_runs``: of the live pages,
        those the walk moves 8 to a copy
        (``paged_attention.walk_run_pages``), from each decoding slot's row
        as it was armed; a dead slot reads through a zeroed row, page by
        page."""
        from ..ops.pallas.paged_attention import walk_counts, walk_run_pages
        sampled = self._count_sampled_steps(self.decode_block)
        lengths, decoding = self._block_read_lengths()
        live, copied, _ = walk_counts(lengths, self.max_blocks,
                                      self.kv_block_size)
        in_runs = walk_run_pages(self._run_groups,
                                 np.where(decoding, lengths, 0),
                                 self.kv_block_size)
        rows = int((lengths * decoding).sum())
        self.kv_pages_live += live
        self.kv_pages_copied += copied
        self.kv_pages_in_runs += in_runs
        self.kv_rows_live += rows
        ids = dict(sampled_steps=sampled, kv_pages_live=live,
                   kv_pages_copied=copied, kv_pages_in_runs=in_runs,
                   kv_rows_live=rows, arm_ns=self._arm_ns)
        self._arm_ns = 0
        if self.cache_passes > 1:
            ids["ut_steps"] = self.decode_block * self.cache_passes
            self.ut_steps += ids["ut_steps"]
        return ids

    def _block_read_lengths(self):
        """``(lengths, decoding)``, each ``(decode_block, slots)``: the
        length of every slot's read at every step of the block about to
        be dispatched, and whether the slot is still decoding there."""
        k = np.arange(self.decode_block)[:, None]
        stays = np.minimum(self._remaining_host, self.decode_block)[None, :]
        return self._pos_host[None, :] + np.minimum(k, stays) + 1, k < stays

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
        """What the model's programs counted into the cache (a model that
        declares ``cache_counters``), fetched with the block's other
        transfers; None where nothing is counted."""
        return np.asarray(self._cache[-1]) if self._counter_names else None

    def _credit_program_counters(self, counts_np):
        """Fold the programs' counts into the engine's counters and onto
        the spans of what ran: the decode row onto this block's
        ``serving.decode_block``, the chunk row — every chunk since the
        last read — onto the latest ``serving.prefill_chunk`` (with
        ``chunks``, how many it speaks for). Sums are differences of
        wrapping int32 totals; a maximum is the largest so far."""
        spans = (("", self._block_span, {}),
                 ("prefill_", self._chunk_span if self._chunks_unread
                  else None, {"chunks": self._chunks_unread}))
        now = counts_np.astype(np.int64)
        for row, (prefix, span_, ids) in enumerate(spans):
            for col, (name, fold) in enumerate(self._counter_names):
                v = int(now[row, col])
                if fold == "sum":
                    v = (v - int(self._counts_seen[row, col])) % 2 ** 32
                    setattr(self, prefix + name,
                            getattr(self, prefix + name) + v)
                else:
                    setattr(self, prefix + name, v)
                ids[name] = v
            if span_ is not None:
                span_.ids.update(ids)
        self._counts_seen = now
        self._chunks_unread = 0

    def _credit_block(self, toks_np, lives_np, oks_np, rem_np):
        """The host half of a decode block: credit each live slot its
        emitted tokens, quarantine poisoned rows, retire finished
        slots."""
        self._pos_host += lives_np.sum(axis=0)      # pos += live, per step
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
            if self.nan_sentinel and n > 0 \
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
        resources (arena blocks at correct refcounts, pending prefill job
        dropped), and surface the run through ``drain_finished`` with
        ``failure=reason`` so the Server records a RequestFailure instead
        of hanging the stream."""
        run = self._slots[slot]
        if run is None:
            return False
        run.failure = reason
        if slot in self._prefill_slots:
            self._prefill_slots.discard(slot)
            self._abort_prefill(slot)   # drop the pending job
        else:
            self._state = self._cancel_jit(self._state, jnp.int32(slot))
        self._retire(slot, run, time.perf_counter())
        self._remaining_host[slot] = 0
        return True

    def _abort_prefill(self, slot):
        """Cancel a mid-prefill request: drop its pending job (the
        chunk loop never sees it again); its blocks release through the
        shared ``_retire`` path. The slot never armed, so there is no
        in-graph state to kill."""
        self._jobs = [j for j in self._jobs if j.slot != slot]

    def _poison_live_slot(self):
        """Fault action for the ``serving.poison`` site: NaN the arena
        block holding the first decoding slot's
        position ``pos-1``. That block is always (a) within the slot's
        attended range, so the sentinel trips on the very next step,
        and (b) a FRESH block owned only by this slot — its index
        ``(pos-1)//bs >= (L-1)//bs`` sits past both the shared-prefix
        and the registered range, so no other slot (and no future
        prefix match) can ever read the poison."""
        for slot, run in enumerate(self._slots):
            if run is not None and slot not in self._prefill_slots:
                L = int(np.asarray(run.request.prompt).reshape(-1)
                        .shape[0])
                pos = L + len(run.tokens) - 1
                blk = run.block_ids[(pos - 1) // self.kv_block_size]
                self._cache = tuple(
                    c.at[blk].set(jnp.nan)
                    if jnp.issubdtype(c.dtype, jnp.floating) else c
                    for c in self._cache)
                return slot
        return None

    def _retire(self, slot, run, now):
        """Move a finished slot to the harvest list and release its arena
        blocks."""
        run.t_done = now
        if self.tracer is not None:
            self.tracer.span_end(run.request.request_id, "decode",
                                 tokens=len(run.tokens))
        self._finished.append(run)
        self._free_slot(slot)
        if run.block_ids is not None:
            if run.failure is None and run.tokens:
                # decode-time block sharing: every position the stream
                # WROTE is resident — prompt plus generated history
                # minus the final sampled token (never written). Extend
                # the digest chain over the fully-written blocks so a
                # later request continuing this conversation shares the
                # decode-position KV too. Failed/poisoned runs register
                # NOTHING (a poisoned block must never be matchable).
                # The run's chain already holds the prompt's shareable
                # blocks: only the blocks written since are hashed.
                seq = np.concatenate([
                    np.asarray(run.request.prompt, np.int32).reshape(-1),
                    np.asarray(run.tokens[:-1], np.int32)])
                self._register_written(
                    run, self.manager.extend_chain(
                        run.chain, seq, len(seq) // self.kv_block_size))
            self._release_slot_resources(run)

    def _register_written(self, run, chain):
        """Index the fully-written blocks of a finished stream."""
        self.manager.register_chain(chain, run.block_ids)

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
        state (positions, rng keys, sampling params and the block
        tables riding it), and the host bookkeeping. Taken at a
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
        m = self.manager
        meta["manager"] = {
            "num_blocks": m.num_blocks, "block_size": m.block_size,
            "free": list(m._free),
            "ref": [[int(b), int(r)] for b, r in m._ref.items()],
            "digest_of": [[int(b), d.hex()]
                          for b, d in m._digest_of.items()],
            "index": [[d.hex(), int(bid), [int(t) for t in chunk]]
                      for d, (bid, chunk) in m._index.items()],
            "cached": [int(b) for b in m._cached],   # LRU order
            "lookups": m.lookups, "hit_blocks": m.hit_blocks,
            "depth": [[d.hex(), int(n)] for d, n in m._depth.items()],
            "evictions": m.evictions,
            "hits": [[int(b), int(h)] for b, h in m._hits.items()],
        }
        jobs_meta = []
        for j, job in enumerate(self._jobs):
            arrays[f"job{j}_prompt"] = np.asarray(job.prompt, np.int32)
            arrays[f"job{j}_table"] = np.asarray(job.table_row, np.int32)
            arrays[f"job{j}_key"] = np.asarray(job.key)
            arrays[f"job{j}_sub"] = np.asarray(job.sub)
            jobs_meta.append({
                "slot": job.slot, "done": job.done,
                "temp": float(job.temp), "topk": int(job.topk),
                "topp": float(job.topp), "tok0": job.tok0,
                "resume_tok": job.resume_tok})
        meta["jobs"] = jobs_meta
        meta["paged_counters"] = {
            "prompt_tokens": self.prompt_tokens,
            "shared_tokens": self.shared_tokens,
            "prefilled_tokens": self.prefilled_tokens,
            "prefill_chunks": self.prefill_chunks,
            "fetched_tokens": self.fetched_tokens,
            "kv_pages_live": self.kv_pages_live,
            "kv_pages_copied": self.kv_pages_copied,
            "kv_pages_in_runs": self.kv_pages_in_runs,
            "ut_steps": self.ut_steps}
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
                f"engine is {type(self).__name__}")
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
        mm = meta["manager"]
        m = self.manager
        if (mm["num_blocks"], mm["block_size"]) != (m.num_blocks,
                                                   m.block_size):
            raise ValueError(
                f"snapshot arena {mm['num_blocks']}x{mm['block_size']} "
                f"does not match this engine's "
                f"{m.num_blocks}x{m.block_size}")
        m._free = list(mm["free"])
        m._ref = {int(b): int(r) for b, r in mm["ref"]}
        m._digest_of = {int(b): bytes.fromhex(d)
                        for b, d in mm["digest_of"]}
        m._index = {bytes.fromhex(d): (int(bid), tuple(chunk))
                    for d, bid, chunk in mm["index"]}
        m._cached = OrderedDict((int(b), None) for b in mm["cached"])
        m.lookups, m.hit_blocks = mm["lookups"], mm["hit_blocks"]
        m._depth = {bytes.fromhex(d): int(n)
                    for d, n in mm.get("depth", [])}
        m._depth = {d: n for d, n in m._depth.items()
                    if d in m._index}
        m.evictions = int(mm.get("evictions", 0))
        m._hits = {int(b): int(h) for b, h in mm.get("hits", [])
                   if int(b) in m._digest_of}
        m._reindex_cached()
        m.assert_consistent()
        self._jobs = []
        for j, jm in enumerate(meta["jobs"]):
            run = self._slots[jm["slot"]]
            self._jobs.append(_PrefillJob(
                run=run, slot=jm["slot"],
                prompt=np.asarray(arrays[f"job{j}_prompt"], np.int32),
                done=jm["done"],
                table_row=np.asarray(arrays[f"job{j}_table"], np.int32),
                key=jnp.asarray(arrays[f"job{j}_key"]),
                sub=jnp.asarray(arrays[f"job{j}_sub"]),
                temp=jnp.float32(jm["temp"]),
                topk=jnp.int32(jm["topk"]),
                topp=jnp.float32(jm["topp"]), tok0=jm["tok0"],
                resume_tok=jm.get("resume_tok")))
        pc = meta["paged_counters"]
        self.prompt_tokens = pc["prompt_tokens"]
        self.shared_tokens = pc["shared_tokens"]
        self.prefilled_tokens = pc["prefilled_tokens"]
        self.prefill_chunks = pc["prefill_chunks"]
        self.fetched_tokens = pc.get("fetched_tokens", 0)
        self.kv_pages_live = pc.get("kv_pages_live", 0)
        self.kv_pages_copied = pc.get("kv_pages_copied", 0)
        self.kv_pages_in_runs = pc.get("kv_pages_in_runs", 0)
        self.ut_steps = pc.get("ut_steps", 0)
        self._pos_host = np.asarray(self._state["pos"]).astype(np.int64)
        self._run_groups = self._table_run_groups(
            np.asarray(self._state["table"]))

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
