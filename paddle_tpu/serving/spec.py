"""Speculative multi-token decoding riding the slot pool: draft-verify
inside the engine's ONE compiled decode program.

Plain decode emits one token per compiled step per slot — the step is
memory-bandwidth-bound (every parameter and KV byte is re-read per
token) and the MXU sits mostly idle. Speculative decoding converts that
idle compute into tokens: a host-side **n-gram / prompt-lookup drafter**
(no second model) proposes up to ``k`` candidate tokens per live slot
from the tokens the slot has already seen (prompt + generated history),
and ONE compiled **verify step** scores all ``k+1`` positions per slot
in a single forward:

- the ``(S, k+1)`` verify block generalizes the existing ``(S, 1)``
  decode block — ``cached_attention`` already takes vector per-row
  ``pos``, so row ``i`` of the window attends its own prefix *plus the
  drafts before it*, exactly the causal semantics verification needs;
- the KV write is a masked ``k+1``-wide scatter: all ``k+1`` candidate
  K/V entries land at ``pos .. pos+k`` up front (paged: through the
  slot's block table, with positions past the table routed to the
  trash block);
- **greedy acceptance** keeps the longest draft prefix matching the
  target model's own argmax, plus one bonus token: the emitted tokens
  of a step are ``t_0 .. t_a`` where ``t_i = argmax(logits at position
  i)`` and ``a`` = number of leading drafts with ``d_{i+1} == t_i``.
  Every emitted token is the target model's own choice, so greedy
  streams are **bit-identical** to non-speculative decode — the
  serving parity harness is the verifier;
- per-slot **ragged advance** moves ``pos``/``remaining``/eos state
  in-graph by each slot's accepted length (0..k+1 tokens per step per
  slot, including an eos landing mid-span).

The dead-KV invariant (why rejected drafts are harmless): a step that
advances by ``n`` leaves junk K/V at positions ``pos+n .. pos+k``, but
the NEXT step writes its own ``k+1`` window starting at ``pos+n`` —
which covers every junk position — before attention can read them
(row ``i`` masks ``t_idx <= pos+n+i``, and positions up to ``pos+n+i``
are freshly written this step or emitted history). ``pos`` never
reaches a rejected position, so no junk entry is ever attended, dense
or paged. Paged slots already allocate blocks for the full request up
front (``blocks_needed``), so the max advance is always covered; draft
positions past the table width scatter into the reserved trash block.

Seeded sampling initially falls back to ``k = 0``: a sampled slot's
verify step emits exactly one token through the SAME per-slot
key-split + ``slot_sample_logits`` sequence as the plain block, so the
per-request key-schedule parity with ``generate(seed)`` is preserved
(speculative sampling with rejection resampling would change the
schedule — a follow-up, not a silent break).

Everything is default-off: pass ``spec=SpecConfig(k=...)`` (or
``spec=True``) to ``ContinuousBatchingEngine``, or set
``PT_SERVING_SPEC=<k>`` (``PT_SERVING_SPEC_NGRAM`` bounds the drafter's
n-gram length). Composes with ``paged=True``; tensor-parallel serving
(``tp=``) is not yet composed with spec and is refused loudly.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import metrics as _om
from ..observability.tracing import named_program, span as _span
from ..utils import faults
from ..utils.flags import env_int
from .engine import (DECODE_PROGRAM, ContinuousBatchingEngine,
                     ModelStepBackend, _M_COMPILES, _M_DECODE_TOKENS,
                     _M_STEPS, _M_TOKENS, slot_sample_logits)
from .paging import (PagedEngine, PagedModelStepBackend,
                     refuse_looped_cache)

__all__ = ["SpecConfig", "resolve_spec_config", "ngram_propose",
           "build_spec_block_fn", "SpecModelStepBackend",
           "SpecPagedStepBackend", "SpecEngine", "SpecPagedEngine"]

# speculative-decode metric families (no-ops until metrics.enable() /
# PT_METRICS; registered at import so the catalog is complete at zero)
_M_SPEC_STEPS = _om.counter("pt_serving_spec_verify_steps_total",
                            "speculative verify steps dispatched")
_M_SPEC_DRAFTED = _om.counter("pt_serving_spec_draft_tokens_total",
                              "draft tokens proposed to the verify block")
_M_SPEC_ACCEPTED = _om.counter(
    "pt_serving_spec_accepted_tokens_total",
    "draft tokens the target model's argmax confirmed")
_M_SPEC_EMITTED = _om.counter(
    "pt_serving_spec_emitted_tokens_total",
    "tokens emitted by verify steps (accepted drafts + bonus tokens)")
_M_SPEC_RATE = _om.gauge(
    "pt_serving_spec_acceptance_rate",
    "lifetime accepted/proposed draft-token ratio of the engine")


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """How to speculate. ``k``: max draft tokens per slot per verify
    step (the verify window is ``k+1`` wide; ``k=0`` degenerates to
    plain one-token decode through the same program). ``ngram_max`` /
    ``ngram_min``: the prompt-lookup drafter matches the longest
    trailing n-gram in this range against the slot's own history."""
    k: int = 4
    ngram_max: int = 3
    ngram_min: int = 1

    def __post_init__(self):
        if self.k < 0:
            raise ValueError(f"SpecConfig.k={self.k}; must be >= 0")
        if self.ngram_min < 1:
            raise ValueError(
                f"SpecConfig.ngram_min={self.ngram_min}; must be >= 1")
        if self.ngram_max < self.ngram_min:
            raise ValueError(
                f"SpecConfig.ngram_max={self.ngram_max} < ngram_min="
                f"{self.ngram_min}")


def resolve_spec_config(spec) -> Optional[SpecConfig]:
    """Normalize the engine's ``spec`` argument: SpecConfig
    pass-through, ``True`` -> defaults, ``False`` -> off, ``None`` ->
    the ``PT_SERVING_SPEC`` env knob (integer k; 0/unset disables)."""
    if isinstance(spec, SpecConfig):
        return spec
    if spec is True:
        return SpecConfig()
    if spec is False:
        return None
    if spec is not None:
        raise ValueError(f"spec={spec!r}: pass a SpecConfig, "
                         "True/False, or None (env-controlled)")
    k = env_int("PT_SERVING_SPEC", 0)
    if k <= 0:
        return None
    return SpecConfig(k=k, ngram_max=env_int("PT_SERVING_SPEC_NGRAM", 3))


def spec_requested(spec, backend) -> bool:
    """The ``__new__`` routing decision: an explicitly passed spec
    backend IS the decision; otherwise the spec argument / env knob
    (an explicit non-spec backend is never rerouted by the env flag —
    same contract as paged/tp)."""
    if backend is not None:
        return getattr(backend, "spec_k", None) is not None
    return resolve_spec_config(spec) is not None


# ---------------------------------------------------------------------------
# host-side drafter: n-gram / prompt lookup
# ---------------------------------------------------------------------------

def _lookup_once(h: np.ndarray, k: int, ngram_max: int,
                 ngram_min: int) -> np.ndarray:
    """One prompt-lookup round: the continuation after the most recent
    earlier occurrence of the longest trailing n-gram of ``h``."""
    L = int(h.size)
    empty = np.zeros((0,), np.int32)
    if L < 2:
        return empty
    for n in range(min(ngram_max, L - 1), ngram_min - 1, -1):
        pat = h[L - n:]
        # windows over h[:-1]: every match has at least one continuation
        # token (which may overlap the pattern itself — cycles)
        win = np.lib.stride_tricks.sliding_window_view(h[:L - 1], n)
        hits = np.flatnonzero((win == pat[None, :]).all(axis=1))
        if hits.size:
            s = int(hits[-1])
            out = h[s + n:s + n + k]
            if out.size:
                return out.astype(np.int32)
    return empty


def ngram_propose(history, k: int, ngram_max: int = 3,
                  ngram_min: int = 1) -> np.ndarray:
    """Prompt-lookup drafting: find the most recent earlier occurrence
    of the LONGEST trailing n-gram (length ``ngram_max`` down to
    ``ngram_min``) of ``history`` and propose the tokens that followed
    it. The lookup is SELF-EXTENDING: when the match sits near the end
    of history (a cycle of period p offers only p continuation tokens),
    the draft-so-far is appended to the history and the lookup repeats
    until ``k`` tokens are drafted or no match remains — so a period-2
    loop still fills a k=8 window. Returns a (<=k,) int32 array (empty
    = no draft). Pure host numpy — it never touches the compiled
    program; a greedy stream that has entered a cycle is predicted
    perfectly once the cycle has appeared twice."""
    h = np.asarray(history, np.int32).reshape(-1)
    empty = np.zeros((0,), np.int32)
    if k <= 0 or h.size < 2:
        return empty
    out = empty
    while out.size < k:
        prop = _lookup_once(np.concatenate([h, out]) if out.size else h,
                            k - int(out.size), ngram_max, ngram_min)
        if prop.size == 0:
            break
        out = np.concatenate([out, prop])
    return out


# ---------------------------------------------------------------------------
# the ONE compiled verify program
# ---------------------------------------------------------------------------

def build_spec_block_fn(pure, k: int, trace_counter=None,
                        paged: bool = False):
    """The spec engine's ONE decode program: a single draft-verify step
    over the slot pool. ``pure`` must be the all-positions verify head
    (``build_decode_step(..., all_positions=True)``) — it returns
    (S, k+1, V) log-probs for the window ``[tok, d_1 .. d_k]`` written
    at per-row positions ``pos .. pos+k``.

    In-graph per slot: targets ``t_i = argmax`` per position (row 0 of
    a sampled slot goes through the SAME key-split +
    ``slot_sample_logits`` sequence as the plain block — sampled slots
    never speculate, keeping generate(seed) key-schedule parity),
    greedy acceptance ``a`` = longest prefix with ``d_{i+1} == t_i``,
    ragged advance ``n_emit = min(a+1, remaining)`` further cut at the
    first emitted eos; ``pos/tok/remaining/live`` advance by each
    slot's own ``n_emit``. Emits the (S, k+1) target-token matrix,
    per-slot emission counts, and per-slot no-NaN ``ok`` flags (the
    resilience sentinel, same contract as the plain block)."""
    W = k + 1

    def block_fn(pv, bv, cache_flat, state, draft, n_draft):
        if trace_counter is not None:       # runs only while tracing
            trace_counter[0] += 1
        st = state
        sp = jax.vmap(jax.random.split)(st["key"])      # (S, 2, 2)
        new_key, sub = sp[:, 0], sp[:, 1]
        toks_in = jnp.concatenate(
            [st["tok"][:, None], draft.astype(jnp.int32)], axis=1)
        if paged:
            tbl = jnp.where(st["live"][:, None], st["table"], 0)
            logp, cf = pure(pv, bv, toks_in, cache_flat, st["pos"],
                            None, None, tbl)
        else:
            logp, cf = pure(pv, bv, toks_in, cache_flat, st["pos"],
                            None, st["pad"])
        # (S, W, V) log-probs; NaN anywhere in the slot's window marks
        # a poisoned row (finite weights/cache cannot produce NaN)
        ok = ~jnp.any(jnp.isnan(logp), axis=(1, 2))
        t = jnp.argmax(logp, axis=-1).astype(jnp.int32)       # (S, W)
        # position 0 through the sampling path: greedy rows get the
        # identical argmax, sampled rows the identical key schedule
        first = slot_sample_logits(logp[:, 0], sub, st["temp"],
                                   st["topk"], st["topp"], st["live"])
        t = t.at[:, 0].set(first)
        # sampled rows never accept drafts (k=0 fallback in-graph even
        # if the host proposed some)
        n_eff = jnp.where(st["temp"] <= 0.0, n_draft, 0)
        if k > 0:
            idx = jnp.arange(k)
            acc = (idx[None, :] < n_eff[:, None]) & (draft == t[:, :k])
            a = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)
        else:
            a = jnp.zeros_like(st["pos"])
        live = st["live"]
        n_emit = jnp.where(live, jnp.minimum(a + 1, st["remaining"]), 0)
        cols = jnp.arange(W)[None, :]
        is_eos = ((st["eos"][:, None] >= 0)
                  & (t == st["eos"][:, None])
                  & (cols < n_emit[:, None]))
        eos_pos = jnp.min(jnp.where(is_eos, cols, W), axis=1)
        hit = eos_pos < W               # eos inside the accepted span
        n_emit = jnp.where(hit, eos_pos + 1, n_emit)
        rem = jnp.where(live, st["remaining"] - n_emit, st["remaining"])
        rem = jnp.where(hit, 0, rem)
        last = jnp.take_along_axis(
            t, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
        st2 = dict(st,
                   tok=jnp.where(n_emit > 0, last, st["tok"]),
                   pos=st["pos"] + n_emit,
                   remaining=rem, key=new_key,
                   live=live & (rem > 0))
        return cf, st2, t, n_emit, ok

    # the verify step IS this engine's decode program: same trace name
    return named_program(block_fn, DECODE_PROGRAM)


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

class _SpecBackendMixin:
    """Adds the verify program to a model step backend. The plain
    decode-block jit stays constructed (jax.jit wrapping is free until
    traced) but the spec engine never calls it — ``decode_traces``
    counts the verify block, so the compile-count pin stays == 1."""

    def _setup_spec(self, model, spec: SpecConfig, paged: bool):
        from ..models.generation import build_decode_step
        self.spec = spec
        self.spec_k = spec.k
        # the verify head dequantizes the same weight codes as the
        # plain block under weight-only quant (no-op wrapper otherwise)
        verify = self._maybe_quant_pure(
            build_decode_step(model, None, self._tree_holder,
                              all_positions=True))
        self._spec_jit = jax.jit(
            build_spec_block_fn(verify, spec.k, self.decode_traces,
                                paged=paged),
            donate_argnums=(2, 3))
        # one verify step per host round-trip (drafts are host inputs)
        self.block_size = 1

    def spec_verify(self, cache_flat, state, draft, n_draft):
        return self._spec_jit(self._pv, self._bv, cache_flat, state,
                              draft, n_draft)


class SpecModelStepBackend(_SpecBackendMixin, ModelStepBackend):
    """Dense slot-pool backend with the (S, k+1) verify program."""

    def __init__(self, model, num_slots: int, max_len: int,
                 decode_block: int, spec: SpecConfig, quant=None):
        super().__init__(model, num_slots, max_len, decode_block,
                         quant=quant)
        self._setup_spec(model, spec, paged=False)


class SpecPagedStepBackend(_SpecBackendMixin, PagedModelStepBackend):
    """Paged-arena backend with the (S, k+1) verify program (chunked
    prefill is inherited unchanged)."""

    def __init__(self, model, num_slots: int, max_len: int,
                 decode_block: int, block_size: int, num_blocks: int,
                 kv_int8: bool, prefill_chunk: int, spec: SpecConfig,
                 quant=None):
        refuse_looped_cache(model, "speculative decoding")
        super().__init__(model, num_slots, max_len, decode_block,
                         block_size, num_blocks, kv_int8, prefill_chunk,
                         quant=quant)
        self._setup_spec(model, spec, paged=True)


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

class _SpecEngineMixin:
    """Draft-verify step loop + acceptance accounting shared by the
    dense and paged speculative engines. Overrides ``step_block`` with
    the verify dispatch; admission, cancellation, deadlines, the NaN
    quarantine and snapshot/restore all ride the base machinery."""

    def _init_spec(self, spec: Optional[SpecConfig], backend, tp=None):
        from .tp import resolve_tp_config
        if backend is None and resolve_tp_config(tp) is not None:
            raise NotImplementedError(
                "speculative decoding is not yet composed with "
                "tensor-parallel serving — drop spec= or tp= (ROADMAP "
                "follow-up)")
        if backend is not None:
            cfg = getattr(backend, "spec", None)
            if cfg is None:
                raise ValueError(
                    "speculative engines need a spec backend "
                    "(SpecModelStepBackend / SpecPagedStepBackend); got "
                    f"{type(backend).__name__}")
            self.spec = cfg
        else:
            self.spec = resolve_spec_config(spec) or SpecConfig()
        self.spec_k = self.spec.k

    # -- lifecycle ---------------------------------------------------------
    def reset(self):
        super().reset()
        self.verify_steps = 0
        self.draft_proposed = 0        # draft tokens handed to verify
        self.draft_accepted = 0        # drafts the target confirmed

    # -- introspection -----------------------------------------------------
    def acceptance_rate(self) -> float:
        """Lifetime accepted/proposed draft-token ratio."""
        return self.draft_accepted / self.draft_proposed \
            if self.draft_proposed else 0.0

    def mean_accepted_per_step(self) -> float:
        """Mean accepted draft tokens per verify step (the emitted
        tokens/step is this + the always-emitted bonus token)."""
        return self.draft_accepted / self.verify_steps \
            if self.verify_steps else 0.0

    # -- drafting ----------------------------------------------------------
    @staticmethod
    def _history(run) -> np.ndarray:
        """The slot's prompt+generated history as int32, cached on the
        run and extended incrementally — re-converting the whole
        (growing) token list every tick measurably taxes the host side
        of the verify loop. The cache is plain derived state: restored
        runs just rebuild it on first use."""
        done = len(run.tokens)
        cached = getattr(run, "_spec_hist", None)
        if cached is not None and cached[0] == done:
            return cached[1]
        if cached is not None and cached[0] < done:
            hist = np.concatenate([
                cached[1],
                np.asarray(run.tokens[cached[0]:], np.int32)])
        else:
            hist = np.concatenate([
                np.asarray(run.request.prompt, np.int32).reshape(-1),
                np.asarray(run.tokens, np.int32)])
        run._spec_hist = (done, hist)
        return hist

    def _propose(self) -> Tuple[np.ndarray, np.ndarray]:
        """Per-slot draft proposals for this tick: (S, k) tokens +
        (S,) counts. Greedy decoding slots only (sampled slots keep the
        k=0 key-schedule fallback); capped at remaining-1 so a draft
        can never outrun the slot's token budget."""
        S, k = self.num_slots, self.spec_k
        draft = np.zeros((S, k), np.int32)
        n = np.zeros((S,), np.int32)
        if k == 0:
            return draft, n
        cfg = self.spec
        for slot, run in enumerate(self._slots):
            if run is None or slot in self._prefill_slots:
                continue
            if run.request.temperature > 0:
                continue               # sampled: k=0 fallback
            cap = min(k, int(self._remaining_host[slot]) - 1)
            if cap <= 0:
                continue
            prop = ngram_propose(self._history(run), cap,
                                 cfg.ngram_max, cfg.ngram_min)
            if prop.size:
                draft[slot, :prop.size] = prop
                n[slot] = prop.size
        return draft, n

    # -- decode ------------------------------------------------------------
    def step_block(self):
        """One draft-verify round over the pool, then sync ONCE: pull
        the (S, k+1) target-token matrix + per-slot emission counts,
        credit each slot its 0..k+1 accepted tokens, retire finished
        slots. Same failure semantics as the plain block: the
        ``serving.step_block`` fault site raises BEFORE drafting (a
        retry re-drafts the identical proposal — drafting is a pure
        function of host state), ``serving.harvest`` raises between
        dispatch and transfer with the outputs parked for a
        re-harvest, and a NaN slot is quarantined alone."""
        from ..profiler import RecordEvent
        if self._pending_block is None:
            if not self.has_decoding():
                return
            if faults.should_fire("serving.poison"):
                self._poison_live_slot()
            faults.fault_point("serving.step_block")
            draft, n_draft = self._propose()
            with RecordEvent("serving.spec_verify") as ev:
                out = self.backend.spec_verify(
                    self._cache, self._state, jnp.asarray(draft),
                    jnp.asarray(n_draft))
                self._enqueued(ev._span)
            self._cache, self._state = out[0], out[1]
            self._pending_block = (out[2], out[3], out[4], n_draft)
            self.steps += 1
            self._count_sampled_steps(1)
            self.verify_steps += 1
            proposed = int(n_draft.sum())
            self.draft_proposed += proposed
            # the verify lattice is S slots x (k+1) positions per step
            self.slot_steps += self.num_slots * (self.spec_k + 1)
            _M_STEPS.inc()
            _M_COMPILES.set(self.backend.decode_traces[0])
            self._note_decode_bytes(1)
            _M_SPEC_STEPS.inc()
            _M_SPEC_DRAFTED.inc(proposed)
        faults.fault_point("serving.harvest")
        toks, counts, oks, n_draft = self._pending_block
        # ONE batched host sync per verify step (4 separate np.asarray
        # round-trips measurably tax the tick at CPU dispatch scale)
        began = self._stall_watch.begin()
        with _span("serving.decode_sync", fetches=1) as sp:
            toks_np, counts_np, oks_np, rem_np = jax.device_get(
                (toks, counts, oks, self._state["remaining"]))
            self._drained_ns = sp.mark("first")
        self._sync_ended(sp, began)
        self._pending_block = None
        emitted = int(counts_np.sum())
        accepted = int(np.maximum(counts_np - 1, 0).sum())
        self.decode_tokens += emitted
        self.tokens_emitted += emitted
        self.draft_accepted += accepted
        _M_DECODE_TOKENS.inc(emitted)
        _M_TOKENS.inc(emitted)
        _M_SPEC_EMITTED.inc(emitted)
        _M_SPEC_ACCEPTED.inc(accepted)
        _M_SPEC_RATE.set(self.acceptance_rate())
        now = time.perf_counter()
        for slot, run in enumerate(self._slots):
            if run is None or slot in self._prefill_slots:
                continue
            n = int(counts_np[slot])
            if n > 0:
                run.tokens.extend(int(t) for t in toks_np[slot, :n])
            if self.nan_sentinel and n > 0 and not bool(oks_np[slot]):
                self.cancel_slot(slot, "poisoned")
                continue
            self._remaining_host[slot] = rem_np[slot]
            if rem_np[slot] == 0:
                self._retire(slot, run, now)

    # -- snapshot / restore ------------------------------------------------
    def snapshot_state(self):
        meta, arrays = super().snapshot_state()
        meta["spec"] = {"k": self.spec.k,
                        "ngram_max": self.spec.ngram_max,
                        "ngram_min": self.spec.ngram_min,
                        "verify_steps": self.verify_steps,
                        "draft_proposed": self.draft_proposed,
                        "draft_accepted": self.draft_accepted}
        return meta, arrays

    def restore_state(self, meta, arrays):
        sm = meta.get("spec")
        if sm is not None and sm["k"] != self.spec.k:
            raise ValueError(
                f"snapshot was taken at spec k={sm['k']}, this engine "
                f"runs k={self.spec.k} — the verify program shape (and "
                "the paged write window) must match to resume")
        super().restore_state(meta, arrays)
        if sm is not None:
            self.verify_steps = sm["verify_steps"]
            self.draft_proposed = sm["draft_proposed"]
            self.draft_accepted = sm["draft_accepted"]


class SpecEngine(_SpecEngineMixin, ContinuousBatchingEngine):
    """Dense slot-pool engine with draft-verify decode. Constructed via
    ``ContinuousBatchingEngine(..., spec=SpecConfig(k=...))`` (or
    ``PT_SERVING_SPEC=<k>``)."""

    def __init__(self, model=None, num_slots: int = 4,
                 max_len: int = 256, decode_block: int = 8,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 backend=None, *, paged: Optional[bool] = None,
                 spec=None, tp=None, quant=None):
        if paged:
            # same loud-refusal rule as spec= on a direct subclass
            # ctor: silently serving DENSE from a paged= request would
            # be a misconfiguration, not a preference
            raise ValueError(
                "SpecEngine is the dense speculative engine — use the "
                "ContinuousBatchingEngine factory (paged=True, "
                "spec=...) or SpecPagedEngine for the paged one")
        self._init_spec(spec, backend, tp)
        super().__init__(model, num_slots, max_len, decode_block,
                         prompt_buckets, backend, paged=False,
                         quant=quant)

    def _build_backend(self, model, num_slots, max_len, decode_block,
                       quant=None):
        return SpecModelStepBackend(model, num_slots, max_len,
                                    decode_block, self.spec, quant=quant)


class SpecPagedEngine(_SpecEngineMixin, PagedEngine):
    """Paged-arena engine with draft-verify decode (chunked prefill,
    prefix reuse and the block manager are inherited unchanged — the
    verify window's junk writes past a slot's table land in the trash
    block, and accepted positions are covered by the blocks the
    request already allocated up front)."""

    def __init__(self, model=None, num_slots: int = 4,
                 max_len: int = 256, decode_block: int = 8,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 backend=None, *, paged: bool = True, spec=None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 kv_int8: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 hash_fn=None, tp=None, quant=None):
        if paged is not None and not paged:
            raise ValueError(
                "SpecPagedEngine is the paged speculative engine — use "
                "the ContinuousBatchingEngine factory (spec=...) or "
                "SpecEngine for the dense one")
        self._init_spec(spec, backend, tp)
        super().__init__(model, num_slots, max_len, decode_block,
                         prompt_buckets, backend, paged=True,
                         block_size=block_size, num_blocks=num_blocks,
                         kv_int8=kv_int8, prefill_chunk=prefill_chunk,
                         hash_fn=hash_fn, quant=quant)

    def _build_paged_backend(self, model, num_slots, max_len,
                             decode_block, block_size, num_blocks,
                             kv_int8, prefill_chunk, quant=None):
        return SpecPagedStepBackend(model, num_slots, max_len,
                                    decode_block, block_size,
                                    num_blocks, kv_int8, prefill_chunk,
                                    self.spec, quant=quant)
