"""Disaggregated prefill/decode serving fleet: prefill workers, decode
workers, KV-block handoff, prefix-affinity routing, live migration.

Prefill is compute-bound (one big batched forward per prompt) and
decode is bandwidth-bound (every weight and KV byte re-read per token);
at production scale they want different hardware pools. This module
splits the Server into replicas of two specialties and a router:

- **PrefillWorker**: a Server over a prefill-only engine
  (:class:`PrefillPagedEngine`). Prompts
  admit, chunk-prefill and sample their first token exactly as on
  a unified server — same programs, same key schedule — but a finished
  prefill parks in a handoff **outbox** instead of arming the slot.
  The slot and its arena blocks stay held until the payload ships, so
  a serialize/transport fault retries against live state.
- **KV handoff** (serving/handoff.py): the outbox entry serializes to
  a versioned, bytes-true payload — prompt-position KV blocks at
  storage dtype (int8 codes + scales ship quantized, never dequantized
  in transit), the in-hand token, the post-split rng key, the request.
- **DecodeWorker**: a Server over an ordinary engine. ``adopt()``
  allocates the request's blocks from its OWN BlockManager at exact
  refcounts, scatters the shipped rows into its arena through ONE
  fixed-shape jitted program (padded to ``max_blocks``; pad rows land
  in the trash block), registers the prompt prefix in its own index,
  and arms the slot through the engine's EXISTING arm program —
  zero new compiled programs on the decode steady path, decode compile
  count stays 1. A request prefilled on worker A and decoded on worker
  B streams BIT-IDENTICAL to a single-replica Server (greedy and
  seeded-sampled; fp32 and kv_int8 arenas) because the decode block is
  a pure function of exactly the adopted state.
- **FleetRouter**: chained-SHA1 prefix-hash affinity — the digest of a
  prompt's first full block (the same key the BlockManager indexes it
  under) picks the prefill worker, so a tenant's system prompt lands
  where its registered blocks already live and the PR 4 prefix cache
  becomes a fleet-wide asset. Queue-depth spillover diverts from a
  backlogged affinity target to the least-loaded worker.
- **Transport** (serving/transport.py): the in-process FIFO default,
  or the REAL localhost-TCP :class:`SocketTransport` (length-framed,
  CRC32-trailed, seq-numbered, acked, reconnecting, at-least-once —
  adopt() restores exactly-once by (rid, payload seq) dedup). Handoff
  failures ride the PR 5 retry/backoff/breaker machinery
  (``ResilienceState``): serialize, transport and adopt faults retry
  with seeded backoff, a permanent failure records an explicit
  ``RequestFailure(reason="handoff")``, and an open circuit fails
  fast as ``circuit_open``.
- **Failure domains** (PR 15): per-worker heartbeat leases (a worker
  missing N beats is DEAD — flight event + ``pt_fleet_worker_state``
  gauge, never read again), and REDRIVE of streams lost with a dead
  decode worker: rebuilt from the fleet's own records (submission +
  shipped key + heartbeat token progress, key host-replayed one
  split per observed token), re-prefilled on a surviving prefill
  worker via a ``redrive`` ResumeState, completing bit-identical to
  an unfailed run. A dead prefill worker's un-shipped requests
  resubmit under their original ids; unrecoverable streams fail
  explicitly as ``worker_lost``.
- **Live migration / scale**: a decode worker snapshots via the PR 5
  ``Server.snapshot`` path and restores into a fresh engine
  (``Fleet.migrate_decode_worker``) with every in-flight stream
  finishing bit-identical; ``add_decode_worker`` scales the decode
  pool mid-stream; ``drain_prefill_worker`` stops routing to a worker
  so it can retire cleanly.

- **Fleet-wide prefix cache** (PR 16, serving/prefix_cache.py):
  workers publish their registered digest chains with each heartbeat
  into a :class:`~paddle_tpu.serving.prefix_cache.
  PrefixCacheDirectory`; on a prefill-admission miss where the
  directory holds a longer chain, the admitting worker FETCHES the
  covered blocks from the owner over the same transport (a
  ``pt-kv-fetch`` payload on the worker's ``#fetch`` side channel,
  CRC-verified, resilience-retried, ``fleet.fetch`` fault site),
  adopts them through the shared idempotent-adopt scatter and
  chunk-prefills only the uncovered suffix. Any fetch failure falls
  back to local prefill — warm remote state is a perf tier, never a
  dependency. Fleet-global block-pressure watermarks evict LRU
  unreferenced registered blocks so the tier stays bounded.

Knobs (utils/flags helpers): ``PT_SERVING_FLEET_AFFINITY`` (default
on), ``PT_SERVING_FLEET_SPILL_DEPTH`` (default 8),
``PT_SERVING_FLEET_LEASE_MISSES`` (default 3 missed heartbeats),
``PT_SERVING_FLEET_PREFIX_CACHE`` (default on) and the
eviction watermarks ``PT_SERVING_FLEET_EVICT_HIGH`` / ``_LOW``
(default 0.85 / 0.70 of fleet-global block pressure).
"""
from __future__ import annotations

import os
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import FlightRecorder
from ..observability import metrics as _om
from ..utils import faults
from ..utils.flags import env_bool, env_float, env_int
from . import durability as _dur
from .engine import ContinuousBatchingEngine, _M_TOKENS, _SlotRun
from .handoff import KVHandoff, decode_handoff, encode_handoff
from .paging import _sha1_chain, refuse_looped_cache
from . import prefix_cache as _pc
from .prefix_cache import (PrefixCacheDirectory, _adopt_scatter,
                           adopt_prefix, extract_prefix)
from .resilience import (RequestFailure, ResilienceConfig,
                         ResilienceState, request_from_meta,
                         request_to_meta)
from .scheduler import Request, ResumeState
from .server import Server
from .transport import (InProcessTransport, SocketTransport, Transport,
                        TransportError, fetch_endpoint)

__all__ = ["DecodeWorker", "Fleet", "FleetRouter", "InProcessTransport",
           "PrefillPagedEngine", "PrefillWorker", "SocketTransport",
           "Transport", "TransportError"]

# fleet metric families (registered at import so the catalog stays
# complete at zero; no-ops until metrics.enable()/PT_METRICS)
_M_HANDOFFS = _om.counter("pt_fleet_handoffs_total",
                          "KV handoff payloads adopted by decode "
                          "workers")
_M_HANDOFF_BYTES = _om.counter("pt_fleet_handoff_bytes_total",
                               "wire bytes of shipped handoff payloads")
_M_HANDOFF_FAILS = _om.counter(
    "pt_fleet_handoff_failures_total",
    "handoffs that permanently failed, by reason", labels=("reason",))
_M_FLEET_RETRIES = _om.counter("pt_fleet_retries_total",
                               "transient handoff-op retry attempts")
_M_ADOPT_DEFERS = _om.counter(
    "pt_fleet_adopt_defers_total",
    "adoptions deferred (decode slot/block pool momentarily full)")
_M_AFFINITY = _om.counter("pt_fleet_affinity_routes_total",
                          "submissions routed by prefix-hash affinity")
_M_SPILL = _om.counter("pt_fleet_spillovers_total",
                       "submissions diverted off their affinity worker "
                       "by queue-depth spillover")
_M_MIGRATIONS = _om.counter("pt_fleet_migrations_total",
                            "live worker migrations (snapshot/restore)")
_M_PF_DEPTH = _om.gauge("pt_fleet_prefill_queue_depth",
                        "queued requests per prefill worker",
                        labels=("worker",))
_M_DEC_FREE = _om.gauge("pt_fleet_decode_free_slots",
                        "free decode slots per decode worker",
                        labels=("worker",))
# failure-domain families (PR 15)
_M_WORKER_STATE = _om.gauge("pt_fleet_worker_state",
                            "per-worker lease state: 1 live, 0 dead",
                            labels=("worker",))
_M_WORKERS_LOST = _om.counter("pt_fleet_workers_lost_total",
                              "workers whose lease expired, by role",
                              labels=("role",))
_M_REDRIVES = _om.counter(
    "pt_fleet_redrives_total",
    "streams reconstructed from fleet records after a worker died")
_M_ADOPT_DUPS = _om.counter(
    "pt_fleet_adopt_duplicates_total",
    "adopt() calls deduplicated on (rid, payload seq) — the "
    "at-least-once wire's retransmits made idempotent")


def _replay_key(key0, n: int) -> np.ndarray:
    """Host replay of the decode block's per-slot key schedule: the
    in-graph step does ``key, sub = split(key)`` exactly once per
    emitted token, so a slot that produced ``n`` decode tokens after
    arming with ``key0`` holds ``split^n(key0)[0]``. This is what makes
    a stream reconstructible from OBSERVED tokens alone — the fleet
    never needs to read a dead worker's device state to resume its
    seeded-sampled streams bit-identically."""
    k = jnp.asarray(np.asarray(key0, np.uint32).reshape(2))
    for _ in range(n):
        k = jax.random.split(k)[0]
    return np.asarray(k, np.uint32)


def _leaf_specs(backend) -> list:
    """Canonical per-leaf KV layout (shape past the pool dim + dtype):
    the ONE compatibility signature shared by payload producers
    (extract_handoff), the adopt-time validator and the fleet-wide
    compat check — a format change cannot drift them apart."""
    return [[list(s[1:]), str(np.dtype(d))]
            for s, d in backend.pool_specs]


def _stamp_resume_meta(meta: dict, ph: "_PendingHandoff"):
    """Redrive payloads carry the generated history: the decode worker
    arms with ``tokens[-1]`` and its run starts from the FULL token
    list, so the completed result is original-prompt + every token.
    ``orig_prompt_len`` is recorded because ``arrays["prompt"]`` is
    then the re-prefilled ``prompt + tokens[:-1]`` sequence, not the
    user's prompt."""
    if ph.tokens is not None:
        meta["tokens"] = [int(t) for t in ph.tokens]
        meta["orig_prompt_len"] = int(ph.orig_len)


# ---------------------------------------------------------------------------
# prefill-only engines
# ---------------------------------------------------------------------------

@dataclass
class _PendingHandoff:
    """One finished prefill waiting to ship. The slot stays occupied
    (in ``_prefill_slots``, so it never decodes) and its blocks stay
    referenced until the payload is on the wire — a serialize or
    transport fault retries against state that is still alive."""
    run: _SlotRun
    slot: int
    prompt: np.ndarray                  # the PREFILLED token sequence
    tok0: int
    rem0: int
    key: np.ndarray                     # (2,) uint32 post-split key
    # redrive resume: the carried generated history (tokens[-1] ==
    # tok0) and the ORIGINAL prompt length — ``prompt`` above is then
    # prompt+tokens[:-1], the re-prefilled sequence
    tokens: Optional[List[int]] = None
    orig_len: Optional[int] = None


class PrefillPagedEngine(ContinuousBatchingEngine):
    """The engine, prefilling but never decoding: chunked prefill,
    prefix reuse and the block manager are inherited unchanged; a
    finished prefill parks in the handoff outbox with its blocks still
    referenced instead of arming the slot. Requests that finish AT
    prefill (eos on the first token, max_new==1) complete here — no
    decode worker ever sees them."""

    def reset(self):
        super().reset()
        self._outbox: List[_PendingHandoff] = []

    def take_handoffs(self) -> List[_PendingHandoff]:
        """Drain ship-ready outbox entries. Entries whose run was
        cancelled meanwhile (deadline expiry went through
        ``cancel_slot`` → ``_retire``, which already released the slot
        and blocks) are dropped here, not shipped."""
        live, self._outbox = self._outbox, []
        return [ph for ph in live
                if ph.run.failure is None
                and self._slots[ph.slot] is ph.run]

    def release_handoff(self, ph: _PendingHandoff):
        """Free everything a shipped (or permanently failed) handoff
        held on this worker: the slot, and its arena blocks at exact
        refcounts (registered prefix blocks park in the LRU cache, which
        is what keeps the worker's prefix index hot for the next
        same-prefix arrival)."""
        self._prefill_slots.discard(ph.slot)
        if self._slots[ph.slot] is ph.run:
            self._slots[ph.slot] = None
        self._release_slot_resources(ph.run)

    def snapshot_state(self):
        """Un-shipped handoffs RIDE the snapshot (PR 20) instead of
        refusing it: each live outbox entry serializes alongside the
        engine state — its run is a live slot, so the base snapshot
        already carries the slot/blocks; this adds the parked
        ship-side fields. A coordinated fleet checkpoint can therefore
        land at ANY tick boundary."""
        meta, arrays = super().snapshot_state()
        ob_meta = []
        for ph in self._outbox:
            if ph.run.failure is not None \
                    or self._slots[ph.slot] is not ph.run:
                continue                    # cancelled — never ships
            k = len(ob_meta)
            arrays[f"ob{k}_prompt"] = np.asarray(ph.prompt, np.int32)
            arrays[f"ob{k}_key"] = np.asarray(ph.key, np.uint32)
            ob_meta.append({
                "slot": int(ph.slot), "tok0": int(ph.tok0),
                "rem0": int(ph.rem0),
                "tokens": None if ph.tokens is None
                else [int(t) for t in ph.tokens],
                "orig_len": None if ph.orig_len is None
                else int(ph.orig_len)})
        meta["outbox"] = ob_meta
        return meta, arrays

    def restore_state(self, meta, arrays):
        super().restore_state(meta, arrays)
        self._outbox = []
        for k, e in enumerate(meta.get("outbox", ())):
            run = self._slots[e["slot"]]
            if run is None:
                continue
            self._outbox.append(_PendingHandoff(
                run=run, slot=int(e["slot"]),
                prompt=np.asarray(arrays[f"ob{k}_prompt"], np.int32),
                tok0=int(e["tok0"]), rem0=int(e["rem0"]),
                key=np.asarray(arrays[f"ob{k}_key"], np.uint32),
                tokens=e["tokens"],
                orig_len=e["orig_len"]))

    def try_admit(self, request) -> bool:
        resume = getattr(request, "resume", None)
        if resume is not None and resume.tokens \
                and not resume.redrive:
            raise NotImplementedError(
                "prefill workers do not take preemption resumes — the "
                "fleet never preempts (route resumes to a unified "
                "Server)")
        # a redrive resume rides the PR 13 resume branch
        # unchanged: chunked re-prefill of prompt+tokens[:-1] (mostly
        # prefix-index hits for shared prompts), carried key armed,
        # the chunk programs' in-graph samples discarded
        return super().try_admit(request)

    #: fleet-installed hook ``fn(full_tokens, local_blocks) ->
    #: fetched_block_ids | None``: consult the fleet prefix directory
    #: and fetch the covered blocks a remote worker holds beyond the
    #: local match (Fleet._fetch_prefix). None outside a fleet.
    prefix_fetcher = None

    def _match_prefix_for_admission(self, full, chain):
        shared = self.manager.match_prefix(full, chain)
        if self.prefix_fetcher is not None:
            fetched = self.prefix_fetcher(full, shared)
            if fetched:
                # fetched blocks arrive allocated at refcount 1 and
                # already registered — exactly the hold a local match
                # would have acquired, so the admission path (and its
                # release-on-exhaustion error path) treats them as
                # shared blocks with zero special cases
                shared = shared + fetched
                self.fetched_tokens += len(fetched) * self.kv_block_size
        return shared

    def _finish_prefill(self, job, tok0_dev):
        req = job.run.request
        now = time.perf_counter()
        eos = req.eos_token_id
        if job.resume_tok is not None:      # redrive re-prefill done
            tok0 = job.resume_tok           # the carried in-hand token
            rem0 = req.max_new_tokens - len(job.run.tokens)
            req.resume = None
            tokens = list(job.run.tokens)
            orig_len = int(np.asarray(req.prompt).reshape(-1).size)
            if self.tracer is not None:
                self.tracer.instant(req.request_id, "resume",
                                    slot=job.slot, redrive=True,
                                    reused_tokens=len(tokens))
        else:
            tok0 = int(tok0_dev)
            job.run.tokens = [tok0]
            job.run.t_admit = now           # the fleet TTFT timestamp
            self.tokens_emitted += 1
            _M_TOKENS.inc()
            rem0 = req.max_new_tokens - 1
            if eos is not None and tok0 == eos:
                rem0 = 0
            tokens, orig_len = None, None
        self._register_prompt(job)
        if rem0 <= 0:                       # finished at admission
            self._prefill_slots.discard(job.slot)
            self._retire(job.slot, job.run, now)
            return
        if self.tracer is not None:
            self.tracer.instant(req.request_id, "handoff_ready",
                                slot=job.slot)
        self._outbox.append(_PendingHandoff(
            run=job.run, slot=job.slot, prompt=job.prompt, tok0=tok0,
            rem0=rem0, key=np.asarray(job.key, np.uint32),
            tokens=tokens, orig_len=orig_len))

    def extract_handoff(self, ph: _PendingHandoff,
                        source: str = "") -> KVHandoff:
        """Build the wire payload from live state: only the blocks
        holding prompt positions ``[0, L)`` ship — decode-position
        blocks are junk the decode worker overwrites before reading.
        Arrays leave at storage dtype (int8 codes stay int8)."""
        L = int(ph.prompt.shape[0])
        bs = self.kv_block_size
        n_ship = -(-L // bs)
        ids = np.asarray(ph.run.block_ids[:n_ship], np.int32)
        arrays = {"prompt": np.asarray(ph.prompt, np.int32),
                  "key": np.asarray(ph.key, np.uint32)}
        for i, c in enumerate(self._cache):
            arrays[f"kv_{i}"] = np.asarray(c[ids])
        req = ph.run.request
        meta = {
            "kind": "paged", "request": request_to_meta(req),
            "tok0": ph.tok0, "pos0": L, "rem0": ph.rem0,
            "n_blocks": len(ph.run.block_ids), "n_ship": n_ship,
            "block_size": bs, "kv_int8": bool(self.kv_int8),
            "leaf_specs": _leaf_specs(self.backend),
            "t_admit": float(ph.run.t_admit),
            "source": {"worker": source,
                       "tp_degree": self.tp_degree()},
        }
        _stamp_resume_meta(meta, ph)
        return KVHandoff(meta=meta, arrays=arrays)


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

class FleetRouter:
    """Prefix-affinity request router with queue-depth spillover.

    The affinity key of a prompt is the chained-SHA1 digest of its
    FIRST full block — the exact key the BlockManager's prefix index
    stores that block under — so every request sharing a system prompt
    maps to the same prefill worker and its registered blocks.
    Prompts too short to share (no full block: ``L <= block_size``)
    key on their whole token tuple, which is still deterministic.
    Spillover: when the affinity target's queue is ``spill_depth``
    deeper than the shallowest worker's, the request diverts to the
    least-loaded worker (prefix locality traded for latency, counted).
    """

    def __init__(self, block_size: int, affinity: Optional[bool] = None,
                 spill_depth: Optional[int] = None):
        if affinity is None:
            affinity = env_bool("PT_SERVING_FLEET_AFFINITY", True)
        if spill_depth is None:
            spill_depth = env_int("PT_SERVING_FLEET_SPILL_DEPTH", 8)
        if spill_depth < 1:
            raise ValueError(
                f"spill_depth={spill_depth}; must be >= 1")
        self.block_size = block_size
        self.affinity = bool(affinity)
        self.spill_depth = spill_depth
        self.affinity_routes = 0
        self.spillovers = 0

    def affinity_key(self, prompt) -> bytes:
        toks = np.asarray(prompt).reshape(-1)
        if toks.size > self.block_size:      # has a shareable block
            toks = toks[:self.block_size]
        return _sha1_chain(b"", tuple(int(t) for t in toks))

    def route(self, prompt, depths: List[int],
              eligible: List[int], warm=None) -> int:
        """Pick a prefill worker index. ``depths`` aligns with
        ``eligible`` (the non-draining workers). ``warm`` (optional)
        is the set of positions within ``eligible`` whose worker the
        fleet prefix directory lists as holding this prompt's chain
        head: when the affinity target spills over, a warm worker
        within tolerance beats the plain least-loaded one (the fetch
        it saves costs more than a few queue places)."""
        if not eligible:
            raise RuntimeError("no routable prefill worker (all "
                               "draining)")
        least = min(range(len(eligible)), key=lambda i: (depths[i], i))
        if not self.affinity:
            return eligible[least]
        pick = int.from_bytes(self.affinity_key(prompt)[:8], "big") \
            % len(eligible)
        if depths[pick] - depths[least] > self.spill_depth:
            self.spillovers += 1
            _M_SPILL.inc()
            if warm:
                wl = min((i for i in warm if i != pick),
                         key=lambda i: (depths[i], i), default=None)
                if wl is not None \
                        and depths[wl] - depths[least] \
                        <= self.spill_depth:
                    return eligible[wl]
            return eligible[least]
        self.affinity_routes += 1
        _M_AFFINITY.inc()
        return eligible[pick]


# ---------------------------------------------------------------------------
# workers
# ---------------------------------------------------------------------------

class PrefillWorker:
    """A Server over a prefill-only engine. The full PR 5/13 door
    machinery applies — scheduler gating, queue-depth shedding,
    deadlines (an expired outbox entry is dropped un-shipped), retries
    around prefill faults — while decode never runs here."""

    def __init__(self, engine, *, name: str = "",
                 scheduler=None, resilience=None, observability=None,
                 server: Optional[Server] = None):
        if not isinstance(engine, PrefillPagedEngine):
            raise ValueError(
                "PrefillWorker needs a prefill-only engine "
                f"(PrefillPagedEngine); got {type(engine).__name__}")
        refuse_looped_cache(engine, "the fleet's hand-off and prefix tier")
        self.engine = engine
        self.name = name
        self.server = server or Server(engine, scheduler, resilience,
                                       observability)
        self.killed = False

    def kill(self):
        """Simulate whole-worker loss (see DecodeWorker.kill)."""
        self.killed = True

    def heartbeat(self) -> Optional[dict]:
        if self.killed:
            return None
        hb = {"queue_depth": self.server.scheduler.pending(),
              "occupancy": self.engine.occupancy(),
              "outbox": len(self.engine._outbox),
              # the prefix-directory publish: heartbeat-shaped, so
              # directory state rides the lease machinery for free
              "prefixes": self.engine.manager.registered_chains()}
        return hb

    def queue_depth(self) -> int:
        return self.server.scheduler.pending()

    def busy(self) -> bool:
        return self.server.scheduler.pending() > 0 \
            or self.engine.has_live()

    def tick(self):
        if self.killed:
            return
        self.server.run_until_idle(max_ticks=1)


class DecodeWorker:
    """A Server over an ordinary engine whose requests arrive by
    adoption instead of submission. ``adopt()`` is the only addition;
    decode, harvest, deadlines, NaN quarantine, streaming sinks and
    snapshot/restore are the stock Server/engine paths — which is why
    migration is just PR 5 snapshot/restore.

    Liveness: the worker emits a :meth:`heartbeat` each fleet tick
    (queue depth, occupancy, and per-stream token progress — the
    observations the fleet's redrive records are built from). A worker
    ``kill()``-ed to simulate whole-process loss stops ticking,
    adopting and heartbeating; the fleet notices via its lease and
    redrives every stream the corpse owned."""

    def __init__(self, engine, *, name: str = "", resilience=None,
                 observability=None, server: Optional[Server] = None):
        if isinstance(engine, PrefillPagedEngine):
            raise ValueError("DecodeWorker needs a decoding engine, "
                             "not a prefill-only one")
        if getattr(engine, "window_manager", None) is not None:
            raise NotImplementedError(
                "the fleet's hand-off moves the (k, v) blocks of ONE pool: "
                "a hybrid cache's two groups cannot be adopted yet")
        refuse_looped_cache(engine, "the fleet's hand-off and prefix tier")
        self.engine = engine
        self.name = name
        self.server = server or Server(engine, resilience=resilience,
                                       observability=observability)
        self._adopt_jit = None
        self.killed = False
        # exactly-once adoption over an at-least-once wire: payloads
        # already armed, keyed (rid, payload seq)
        self._adopted: set = set()
        self.duplicate_adopts = 0

    # -- liveness ----------------------------------------------------------
    def kill(self):
        """Simulate whole-worker loss: the worker stops participating
        (no ticks, no adopts, no heartbeats). Its ENGINE state — KV
        arena, slot state, rng keys — is deliberately never read again
        by the fleet: stream recovery must work from the fleet's own
        records, as it would have to across a real process boundary.
        Its ``server.results`` ledger IS still read: those outputs
        were delivered at harvest time (the stream sink fires before
        any kill can land), so the in-process dict stands in for the
        client's already-received copy, not for worker memory."""
        self.killed = True

    def heartbeat(self) -> Optional[dict]:
        """One liveness report, or None from a dead worker. Carries
        queue depth/occupancy (the health the router could act on) and
        per-live-stream token progress — the fleet's redrive substrate:
        everything needed to reconstruct a stream is on this side of
        the wire BEFORE the worker can die."""
        if self.killed:
            return None
        if len(self._adopted) > 256:
            # duplicates only arrive within one ship's retransmit
            # window; once a stream terminated (its rid is in the
            # results ledger, which adopt() also dedups against) its
            # dedup entries are dead weight
            self._adopted = {t for t in self._adopted
                             if t[0] not in self.server.results}
        hb = {
            "queue_depth": self.server.scheduler.pending(),
            "occupancy": self.engine.occupancy(),
            "free_slots": self.engine.free_slot_count(),
            "progress": {run.request.request_id: list(run.tokens)
                         for _slot, run in self.engine.live_runs()},
            # decode workers publish too: adopted prompts and
            # decode-time-shared completed sequences are fetchable
            # warm state like any prefill worker's
            "prefixes": self.engine.manager.registered_chains(),
        }
        return hb

    # -- capacity ----------------------------------------------------------
    def free_slots(self) -> int:
        return self.engine.free_slot_count()

    def busy(self) -> bool:
        return self.engine.has_live()

    def tick(self):
        if self.killed:
            return
        self.server.run_until_idle(max_ticks=1)

    # -- adoption ----------------------------------------------------------
    def _validate(self, h: KVHandoff):
        eng = self.engine
        if h.kind != "paged":
            raise ValueError(
                f"a {h.kind!r} payload cannot adopt as a stream")
        specs = _leaf_specs(eng.backend)
        if h.meta["leaf_specs"] != specs:
            raise ValueError(
                "handoff KV layout does not match this engine "
                f"(payload {h.meta['leaf_specs'][:2]}..., engine "
                f"{specs[:2]}...) — same model config / paging layout "
                "required")
        if h.meta["block_size"] != eng.kv_block_size \
                or bool(h.meta["kv_int8"]) != bool(eng.kv_int8):
            raise ValueError(
                "handoff arena geometry mismatch (block_size/kv_int8)")
        if h.meta["pos0"] + h.meta["rem0"] > eng.max_len:
            raise ValueError(
                f"handoff needs {h.meta['pos0'] + h.meta['rem0']} "
                f"positions but this engine's max_len is {eng.max_len}")

    #: adopt() outcomes
    ADOPTED = "adopted"         # slot armed in the ONE decode block
    DEFER = "defer"             # momentarily out of slots/blocks
    DUPLICATE = "duplicate"     # (rid, payload seq) already armed

    def adopt(self, h: KVHandoff) -> str:
        """Adopt one payload; returns :data:`ADOPTED`, :data:`DEFER`
        (retry after retirements) or :data:`DUPLICATE`. The
        ``fleet.adopt`` fault site fires before any state mutates, so
        a retry is clean.

        Idempotency contract (the at-least-once wire's other half): a
        payload whose ``(rid, meta["seq"])`` was already armed — an
        ack-lost retransmit — is a NO-OP at exact refcounts: no slot,
        no block allocation, no arena write, no double-registration.
        And a payload whose ``meta["crc32"]`` does not match its
        arrays is refused loudly BEFORE any allocator state is
        touched."""
        faults.fault_point("fleet.adopt")
        if self.killed:
            raise TransportError(
                f"decode worker {self.name!r} is dead")
        h.verify_crc()                  # loud, pre-allocation
        rid = h.request_id
        seq = h.meta.get("seq")
        if (seq is not None and (rid, seq) in self._adopted) \
                or rid in self.server.results:
            # dedup by (rid, seq) while the stream is open, and by the
            # results ledger after it terminated — a straggler
            # duplicate must never re-decode a finished stream
            self.duplicate_adopts += 1
            _M_ADOPT_DUPS.inc()
            return self.DUPLICATE
        self._validate(h)
        eng = self.engine
        slot = next((i for i, s in enumerate(eng._slots) if s is None),
                    None)
        if slot is None:
            return self.DEFER
        if not self._adopt_blocks(h, slot):
            return self.DEFER
        if seq is not None:
            self._adopted.add((rid, seq))
        srv = self.server
        srv._tenant_of[rid] = h.meta["request"].get("tenant", "default")
        if srv.tracer.enabled:
            srv.tracer.start(rid)
            srv.tracer.span_begin(rid, "decode", slot=slot,
                                  adopted=True)
        _M_HANDOFFS.inc()
        return self.ADOPTED

    def _commit(self):
        """TP targets re-shard freshly adopted arrays onto their mesh
        through the same backend hook snapshot restore uses — the
        portable-redistribution half of cross-degree handoff."""
        commit = getattr(self.engine.backend, "commit_arrays", None)
        if commit is not None:
            self.engine._cache, self.engine._state = commit(
                self.engine._cache, self.engine._state)

    @staticmethod
    def _carried(meta, prompt):
        """(request, tokens) for the adopted run: a redrive payload's
        ``arrays["prompt"]`` is the re-prefilled prompt+history, so
        the request is rebuilt over the ORIGINAL prompt prefix and the
        run starts from the full carried token list — harvest then
        assembles original-prompt + every token, exactly the unfailed
        stream."""
        orig = prompt[:int(meta.get("orig_prompt_len",
                                    prompt.shape[0]))]
        req = request_from_meta(meta["request"], orig)
        toks = [int(t) for t in meta.get("tokens", [meta["tok0"]])]
        return req, toks

    def _adopt_blocks(self, h: KVHandoff, slot: int) -> bool:
        eng = self.engine
        meta = h.meta
        prompt = h.arrays["prompt"]
        n_total, n_ship = meta["n_blocks"], meta["n_ship"]
        blocks = eng.manager.allocate(n_total)
        if blocks is None:
            return False
        req, toks = self._carried(meta, prompt)
        table_row = np.zeros((eng.max_blocks,), np.int32)
        table_row[:n_total] = blocks
        if self._adopt_jit is None:
            # the shared adopt scatter (prefix_cache._adopt_scatter):
            # pad rows beyond the shipped prefix write zeros into the
            # reserved trash block, so handoff adopts and prefix-fetch
            # adopts are literally the same program
            self._adopt_jit = jax.jit(_adopt_scatter,
                                      donate_argnums=(0,))
        rows = []
        for i, (shape, dtype) in enumerate(eng.backend.pool_specs):
            r = np.zeros((eng.max_blocks,) + tuple(shape[1:]),
                         np.dtype(dtype))
            r[:n_ship] = h.arrays[f"kv_{i}"]
            rows.append(r)
        eng._cache = self._adopt_jit(eng._cache, tuple(rows), table_row)
        # index the prompt's prefix blocks in THIS worker's manager so
        # the adopted copy is reusable here too (no-op for any digest
        # already registered)
        eng.manager.register_prefix(prompt, blocks)
        run = _SlotRun(req, tokens=toks,
                       t_admit=meta["t_admit"], block_ids=blocks)
        eng._slots[slot] = run
        eng._arm(slot, table_row, meta["tok0"], meta["pos0"], meta["rem0"],
                 req.eos_token_id, np.float32(req.temperature),
                 np.int32(req.top_k), np.float32(req.top_p),
                 np.asarray(h.arrays["key"], np.uint32))
        self._commit()
        return True


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------

class Fleet:
    """N prefill workers + M decode workers + router + transport, one
    deterministic tick loop. ``submit()`` routes by prefix affinity;
    each tick advances every prefill worker, ships ready handoffs to
    the least-loaded decode worker, adopts delivered payloads,
    advances every decode worker, then collects heartbeats and renews
    leases. ``results`` aggregates every worker's results plus
    explicit handoff failures — each submitted request ends in exactly
    one of them.

    **Failure domains** (PR 15): every worker holds a lease renewed by
    its per-tick heartbeat; a worker missing ``lease_misses``
    consecutive heartbeats is marked DEAD (flight-recorder event +
    ``pt_fleet_worker_state`` gauge) and never read again. Streams a
    dead decode worker owned are REDRIVEN from the fleet's own
    records — the submitted request, the shipped rng key, and the
    token progress carried by heartbeats — via a ``redrive``
    :class:`ResumeState`: re-prefill of prompt+tokens[:-1] on a
    surviving prefill worker (mostly prefix-index hits), then a normal
    handoff arming the carried next token and the host-replayed key,
    so the recovered stream completes BIT-IDENTICAL to an unfailed
    run (greedy AND seeded-sampled). A dead prefill worker's
    un-handed-off requests are resubmitted from the fleet's
    submission records under their original ids. Streams that cannot
    be redriven (no surviving workers, unfittable history) fail
    explicitly as ``RequestFailure(reason="worker_lost")``."""

    def __init__(self, prefill_workers: List[PrefillWorker],
                 decode_workers: List[DecodeWorker], *,
                 transport: Optional[Transport] = None,
                 affinity: Optional[bool] = None,
                 spill_depth: Optional[int] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 lease_misses: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 evict_high: Optional[float] = None,
                 evict_low: Optional[float] = None,
                 durability: Optional[str] = None,
                 spill_max_bytes: Optional[int] = None):
        if not prefill_workers or not decode_workers:
            raise ValueError("need at least one prefill and one decode "
                             "worker")
        if lease_misses is None:
            lease_misses = env_int("PT_SERVING_FLEET_LEASE_MISSES", 3)
        if lease_misses < 1:
            raise ValueError(
                f"lease_misses={lease_misses}; must be >= 1")
        self.lease_misses = lease_misses
        self.prefill = list(prefill_workers)
        self.decode = list(decode_workers)
        for i, w in enumerate(self.prefill):
            w.name = w.name or f"prefill{i}"
            # disjoint request-id ranges: the rid a prefill worker
            # assigns IS the fleet-wide id the decode worker completes
            if w.server._next_id == 0:
                w.server._next_id = (i + 1) * 1_000_000
        for i, d in enumerate(self.decode):
            d.name = d.name or f"decode{i}"
        names = [w.name for w in self.prefill] \
            + [d.name for d in self.decode]
        if len(set(names)) != len(names):
            raise ValueError(
                f"duplicate worker names {sorted(names)} — names "
                "address transport queues, leases and assignment "
                "counters, so they must be unique")
        self._check_compat()
        self.transport = transport or InProcessTransport()
        self.router = FleetRouter(
            self.prefill[0].engine.kv_block_size,
            affinity=affinity, spill_depth=spill_depth)
        self.resilience = resilience or ResilienceConfig()
        self._res = ResilienceState(self.resilience)
        self.flight = FlightRecorder()
        self._failures: Dict[int, RequestFailure] = {}
        # redrive-completed streams that never re-reach a worker (the
        # carried history already held every token)
        self._local_results: Dict[int, np.ndarray] = {}
        self._pending_adopt: Dict[str, deque] = {
            d.name: deque() for d in self.decode}
        self._assigned: Dict[str, int] = {d.name: 0
                                          for d in self.decode}
        self._draining: set = set()          # prefill indices
        self._draining_decode: set = set()   # decode NAMES (stable
        # across removals, unlike indices)
        # -- failure-domain records (everything redrive needs lives on
        # THIS side of the wire) --
        # rid -> {prompt, kw, worker, t_submit}: every submission
        self._requests: Dict[int, dict] = {}
        # rid -> {dst, key0, base_len, t_admit}: every shipped handoff
        # (key0 = the rng key at ship, base_len = carried tokens then)
        self._handoffs: Dict[int, dict] = {}
        # rid -> last observed token list (heartbeat-carried)
        self._progress: Dict[int, list] = {}
        # worker name -> health record; 1 heartbeat miss tolerated per
        # missing tick, lease_misses misses = dead
        self._health: Dict[str, dict] = {
            n: {"state": "live", "misses": 0} for n in names}
        for n in names:
            _M_WORKER_STATE.set(1, worker=n)
        # -- fleet-wide prefix cache (PR 16) --
        if prefix_cache is None:
            prefix_cache = env_bool("PT_SERVING_FLEET_PREFIX_CACHE",
                                    True)
        if evict_high is None:
            evict_high = env_float("PT_SERVING_FLEET_EVICT_HIGH", 0.85)
        if evict_low is None:
            evict_low = env_float("PT_SERVING_FLEET_EVICT_LOW", 0.70)
        if not 0.0 < evict_low <= evict_high <= 1.0:
            raise ValueError(
                f"eviction watermarks need 0 < low <= high <= 1; got "
                f"low={evict_low}, high={evict_high}")
        self.prefix_cache_enabled = bool(prefix_cache)
        self.evict_high, self.evict_low = float(evict_high), \
            float(evict_low)
        self.directory = PrefixCacheDirectory()
        self._fetch_seq = 0
        self._fetch_endpoints: set = set()
        self.prefix_fetches = 0
        self.prefix_fetch_blocks = 0
        self.prefix_fetch_kv_bytes: List[int] = []
        self.prefix_fetch_failures: Dict[str, int] = {}
        self.prefix_fetch_duplicates = 0
        self.prefix_evictions = 0
        if self.prefix_cache_enabled:
            for w in self.prefill:
                w.engine.prefix_fetcher = self._make_fetcher(w)
        self._handoff_seq = 0
        self.handoffs = 0
        self.handoff_wire_bytes: List[int] = []
        self.handoff_kv_bytes: List[int] = []
        self.migrations = 0
        self.redrives = 0
        self.workers_lost = 0
        self.redrive_latencies: List[float] = []
        # rid -> (detection wall time) for redriven streams still open
        self._redrive_t0: Dict[int, float] = {}
        self._clock = 0
        # -- durable control plane (PR 20) --
        self.durability_dir: Optional[str] = None
        self._dur_epoch = 0
        self._journal: Optional[_dur.WriteAheadJournal] = None
        self._spill: Optional[_dur.PrefixSpillStore] = None
        # rid -> journaled token high-water mark / terminal written
        self._journaled_progress: Dict[int, int] = {}
        self._journaled_terminals: set = set()
        self.recoveries = 0
        self.last_recovery: Optional[dict] = None
        if spill_max_bytes is None:
            spill_max_bytes = env_int("PT_SERVING_SPILL_MAX_BYTES",
                                      1 << 28)
        self._spill_max_bytes = int(spill_max_bytes)
        if durability is not None:
            self._attach_durability(durability, epoch=0)
            if self._journal.empty():
                self._jrec({"k": "genesis",
                            "prefill": [w.name for w in self.prefill],
                            "decode": [d.name for d in self.decode]})

    def _attach_durability(self, dirname: str, epoch: int):
        """Open (or reopen, in recovery) the journal segment for
        ``epoch`` and the spill tier under ``dirname``."""
        os.makedirs(dirname, exist_ok=True)
        self.durability_dir = dirname
        self._dur_epoch = int(epoch)
        self._journal = _dur.WriteAheadJournal(
            _dur.journal_path(dirname, epoch))
        if self.prefix_cache_enabled:
            self._spill = _dur.PrefixSpillStore(
                os.path.join(dirname, "spill"),
                max_bytes=self._spill_max_bytes)

    def _jrec(self, rec: dict):
        """Append one control-plane record, retrying transient
        failures with the fleet's seeded backoff. Durability is a HARD
        contract: a permanently failing journal is a crashed fleet,
        not a silently forgetful one."""
        if self._journal is None:
            return
        last = None
        for attempt in range(self.resilience.retry_attempts + 1):
            try:
                self._journal.append(rec)
                return
            except (faults.InjectedFault, OSError) as e:
                last = e
                if attempt < self.resilience.retry_attempts:
                    time.sleep(self._res.backoff_s(attempt))
        raise RuntimeError(
            f"write-ahead journal append failed past the retry "
            f"budget: {type(last).__name__}: {last}")

    def _check_compat(self):
        """Every engine in the fleet must share the KV layout — a
        payload must adopt onto ANY decode worker. Refused loudly at
        construction (and at add_decode_worker), not discovered
        mid-stream."""
        engines = [w.engine for w in self.prefill] \
            + [d.engine for d in self.decode]
        for e in engines[1:]:
            self._check_engine_compat(e, engines[0])

    @staticmethod
    def _check_engine_compat(e, first):
        if e.max_len != first.max_len:
            raise ValueError(
                f"max_len mismatch across the fleet "
                f"({e.max_len} vs {first.max_len})")
        if _leaf_specs(e.backend) != _leaf_specs(first.backend):
            raise ValueError(
                "KV leaf layout mismatch across the fleet — same "
                "model config / paging layout required")
        if e.kv_block_size != first.kv_block_size \
                or bool(e.kv_int8) != bool(first.kv_int8):
            raise ValueError(
                "arena geometry mismatch across the fleet "
                "(block_size/kv_int8)")

    # -- submission --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 20,
               prefill_worker: Optional[str] = None, **kw) -> int:
        """Route and submit one request; returns the fleet-wide id
        (key into ``results``). Capacity is validated against BOTH
        pools at the door: the routed prefill worker's (inside
        ``Server.submit``) and the largest decode pool's — a request no
        decode worker could ever adopt is refused here, not deferred
        forever mid-stream. ``prefill_worker`` pins the request to a
        named routable worker, bypassing the router — the test/bench
        hook that forces a warm-REMOTE prefill (affinity would
        otherwise co-locate every same-prefix request with the warm
        copy and the fetch path would never exercise)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        err = None
        for d in self._live_decode():
            try:
                d.engine.validate_request(int(prompt.size),
                                          max_new_tokens)
                err = None
                break
            except ValueError as e:
                err = e
        if err is not None:
            raise ValueError(f"no decode worker can serve this "
                             f"request: {err}")
        eligible = self._routable_prefill()
        if prefill_worker is not None:
            by_name = {self.prefill[i].name: i for i in eligible}
            if prefill_worker not in by_name:
                raise ValueError(
                    f"prefill worker {prefill_worker!r} is not "
                    f"routable (have {sorted(by_name)})")
            wi = by_name[prefill_worker]
        else:
            depths = [self.prefill[i].queue_depth() for i in eligible]
            warm = None
            if self.prefix_cache_enabled:
                owners = set(self.directory.owners(
                    self.router.affinity_key(prompt)))
                if owners:
                    warm = {pos for pos, i in enumerate(eligible)
                            if self.prefill[i].name in owners}
            wi = self.router.route(prompt, depths, eligible, warm=warm)
        w = self.prefill[wi]
        rid = w.server.submit(prompt, max_new_tokens=max_new_tokens,
                              **kw)
        # the submission record: with this (plus the shipped key and
        # heartbeat-carried progress) the fleet can rebuild the request
        # after ANY worker holding it dies
        self._requests[rid] = {
            "prompt": prompt.copy(), "worker": w.name,
            "t_submit": time.perf_counter(),
            "kw": dict(kw, max_new_tokens=max_new_tokens)}
        if self._journal is not None:
            self._jrec({"k": "submit", "rid": int(rid),
                        "prompt": [int(t) for t in prompt],
                        "worker": w.name,
                        "kw": {k: v for k, v in
                               self._requests[rid]["kw"].items()}})
        return rid

    # -- liveness views ----------------------------------------------------
    def _alive(self, name: str) -> bool:
        return self._health[name]["state"] == "live"

    def _live_decode(self) -> List[DecodeWorker]:
        return [d for d in self.decode if self._alive(d.name)]

    def _routable_prefill(self) -> List[int]:
        return [i for i in range(len(self.prefill))
                if i not in self._draining
                and self._alive(self.prefill[i].name)]

    # -- the tick ----------------------------------------------------------
    def _with_retry(self, fn):
        """PR 5 retry/backoff/breaker around one handoff op. Returns
        ``(ok, value)``; counts toward the fleet's consecutive-failure
        budget and trips its breaker like Server's step retries. Same
        policy loop as ``Server._with_retry`` over the same
        ``ResilienceState``, minus the per-server flight-recorder/
        tracer hooks (the fleet has neither) and plus the return
        value adopt() needs."""
        res, cfg = self._res, self.resilience
        for attempt in range(cfg.retry_attempts + 1):
            if res.breaker_open:
                return False, None
            try:
                out = fn()
                res.consecutive_failures = 0
                return True, out
            except res.transient as e:
                res.step_failures += 1
                res.consecutive_failures += 1
                res.last_error = f"{type(e).__name__}: {e}"
                if res.consecutive_failures >= cfg.breaker_threshold:
                    res.breaker_open = True
                    return False, None
                if attempt < cfg.retry_attempts:
                    res.retries += 1
                    _M_FLEET_RETRIES.inc()
                    time.sleep(res.backoff_s(attempt))
        return False, None

    def _fail_handoff(self, rid: int, reason: str, message: str,
                      tokens: int = 0):
        self._failures[rid] = RequestFailure(
            request_id=rid, reason=reason, message=message,
            tokens_emitted=tokens)
        self._res.count_failure(reason)
        _M_HANDOFF_FAILS.inc(reason=reason)

    def _pick_decode(self) -> Optional[int]:
        """Least-loaded LIVE decode worker: free slots minus payloads
        already assigned but not yet adopted; ties break low-index for
        determinism. A killed-but-undetected worker is still a target
        (the fleet cannot know yet — its payloads are redriven when
        the lease expires); a detected-dead one never is; a DRAINING
        one only when no non-draining worker survives (correct but
        dispreferred — the drain must eventually converge). None when
        the decode pool is gone entirely."""
        names = [d.name for d in self.decode]
        live = [i for i in range(len(self.decode))
                if self._alive(names[i])]
        if not live:
            return None
        routable = [i for i in live
                    if names[i] not in self._draining_decode]
        return max(routable or live,
                   key=lambda i: (self.decode[i].free_slots()
                                  - self._assigned[names[i]],
                                  -i))

    def _ship(self, w: PrefillWorker, ph: _PendingHandoff):
        rid = ph.run.request.request_id
        if self._res.breaker_open:
            w.engine.release_handoff(ph)
            self._fail_handoff(rid, "circuit_open",
                               "fleet handoff circuit open")
            return
        di = self._pick_decode()
        if di is None:
            w.engine.release_handoff(ph)
            self._fail_handoff(rid, "worker_lost",
                               "no live decode worker to ship to",
                               tokens=len(ph.run.tokens))
            return
        dst = self.decode[di].name
        self._handoff_seq += 1
        seq = self._handoff_seq
        holder = {}

        def _do():
            if "data" not in holder:          # extract + serialize
                h = w.engine.extract_handoff(ph, source=w.name)
                # payload seq (adopt's dedup key half) + arrays CRC
                # (refused loudly pre-allocation) ride the meta
                h.meta["seq"] = seq
                h.meta["crc32"] = h.payload_crc32()
                holder["h"] = h
                holder["kv"] = h.kv_bytes()
                holder["data"] = encode_handoff(h)
            self.transport.send(dst, holder["data"])

        ok, _ = self._with_retry(_do)
        if ok:
            w.engine.release_handoff(ph)
            self._assigned[dst] += 1
            self.handoffs += 1
            self.handoff_wire_bytes.append(len(holder["data"]))
            self.handoff_kv_bytes.append(holder["kv"])
            _M_HANDOFF_BYTES.inc(len(holder["data"]))
            # the redrive record: the key the slot arms with and how
            # many tokens it carried — with heartbeat progress, the
            # slot key after m more emissions is split^m(key0)
            h = holder["h"]
            toks = [int(t) for t in h.meta.get("tokens",
                                               [h.meta["tok0"]])]
            self._handoffs[rid] = {
                "dst": dst,
                "key0": np.asarray(h.arrays["key"], np.uint32),
                "base_len": len(toks), "tokens0": list(toks),
                "t_admit": float(h.meta["t_admit"])}
            self._progress[rid] = toks
            if self._journal is not None:
                self._jrec({
                    "k": "ship", "rid": int(rid), "dst": dst,
                    "seq": int(seq),
                    "key0": [int(x) for x in
                             np.asarray(h.arrays["key"],
                                        np.uint32).reshape(-1)],
                    "base_len": len(toks),
                    "tokens0": [int(t) for t in toks],
                    "t_admit": float(h.meta["t_admit"])})
                self._journaled_progress[rid] = len(toks)
        else:
            reason = "circuit_open" if self._res.breaker_open \
                else "handoff"
            w.engine.release_handoff(ph)
            self._fail_handoff(
                rid, reason,
                f"handoff to {dst} failed: {self._res.last_error}",
                tokens=len(ph.run.tokens))

    def _deliver(self, d: DecodeWorker):
        if d.killed:        # a dead process runs no receive loop; its
            return          # queued payloads redrive at lease expiry
        q = self._pending_adopt[d.name]
        while True:
            if not q:
                data = self.transport.recv(d.name)
                if data is None:
                    return
                q.append(decode_handoff(data))
            h = q[0]
            if h.request_id in self._failures:
                # an at-least-once straggler: one send attempt reached
                # the receiver, but the ship as a whole was recorded a
                # permanent failure (breaker/budget) and released the
                # prefill state. The stream's terminal already exists —
                # drop the frame, never adopt it (and never decrement
                # _assigned: a failed ship never incremented it)
                q.popleft()
                continue
            carried = len(h.meta.get("tokens", [h.meta.get("tok0")]))
            try:
                ok, status = self._with_retry(lambda: d.adopt(h))
            except ValueError as e:
                # corrupt/incompatible payload: permanent, loud, no
                # retry — the prefill side's state is long released,
                # so the stream ends in an explicit failure
                self._fail_handoff(h.request_id, "handoff",
                                   f"adopt refused: {e}",
                                   tokens=carried)
                q.popleft()
                self._assigned[d.name] -= 1
                continue
            if ok and status == DecodeWorker.ADOPTED:
                q.popleft()
                self._assigned[d.name] -= 1
                if self._journal is not None:
                    self._jrec({"k": "adopt",
                                "rid": int(h.request_id),
                                "worker": d.name,
                                "seq": int(h.meta.get("seq", 0))})
                continue
            if ok and status == DecodeWorker.DUPLICATE:
                # an ack-lost retransmit: the first copy already
                # decremented the assignment — drop silently
                q.popleft()
                continue
            if ok:                            # DEFER: retry next tick
                _M_ADOPT_DEFERS.inc()
                return
            reason = "circuit_open" if self._res.breaker_open \
                else "handoff"
            self._fail_handoff(
                h.request_id, reason,
                f"adopt on {d.name} failed: {self._res.last_error}",
                tokens=carried)
            q.popleft()
            self._assigned[d.name] -= 1

    # -- fleet-wide prefix cache: fetch / directory / eviction -------------
    def _make_fetcher(self, w: PrefillWorker):
        def _fetch(full, local_blocks):
            return self._fetch_prefix(w, full, local_blocks)
        return _fetch

    def _worker_by_name(self, name: str):
        for w in self.prefill:
            if w.name == name:
                return w
        for d in self.decode:
            if d.name == name:
                return d
        return None

    def _note_fetch_fail(self, reason: str):
        self.prefix_fetch_failures[reason] = \
            self.prefix_fetch_failures.get(reason, 0) + 1
        _pc._M_FETCH_FAILS.inc(reason=reason)

    def _drain_fetch_endpoint(self, ep: str):
        """Discard stray frames on a fetch side channel — late
        at-least-once retransmits of fetches that already concluded
        (adopted, or given up on). Left queued they would hold
        ``transport.pending()`` above zero and spin the idle loop."""
        while self.transport.recv(ep) is not None:
            self.prefix_fetch_duplicates += 1
            _pc._M_FETCH_DUPS.inc()

    def _fetch_prefix(self, w: PrefillWorker, full,
                      local_blocks) -> Optional[List[int]]:
        """One synchronous remote prefix fetch on behalf of worker
        ``w``'s admission: directory lookup → owner-side extract →
        transport round trip on ``w``'s ``#fetch`` side channel → CRC
        verify → idempotent adopt → register. Returns the adopted
        block ids, or None — and EVERY failure (dead owner, exhausted
        retry budget, stale directory, CRC mismatch, full pool, open
        breaker) is a None: the request prefills locally, it never
        fails because warm remote state was advertised."""
        eng = w.engine
        n_local = len(local_blocks)
        exclude = {w.name} | {n for n, h in self._health.items()
                              if h["state"] != "live"}
        depth, owners = self.directory.deepest_covered(
            full, eng.kv_block_size, eng.manager.hash_fn,
            exclude=exclude)
        if self._spill is not None:
            # the disk tier competes with live owners: strictly deeper
            # spilled coverage wins (tie → live owner, it is fresher);
            # ANY spill failure falls through to the remote path below
            got = self._spill_fetch(w, full, local_blocks, depth)
            if got is not None:
                return got
        if depth <= n_local:
            return None                  # nothing beyond the local match
        if self._res.breaker_open:
            self._note_fetch_fail("circuit_open")
            return None
        owner = self._worker_by_name(owners[0])
        if owner is None:
            self._note_fetch_fail("stale")
            return None
        self._fetch_seq += 1
        seq = self._fetch_seq
        ep = fetch_endpoint(w.name)
        self._fetch_endpoints.add(ep)
        holder: dict = {}

        def _do():
            faults.fault_point("fleet.fetch")
            if owner.killed:
                raise TransportError(
                    f"prefix owner {owner.name!r} is dead")
            if "data" not in holder and "stale" not in holder:
                # extract + serialize ONCE; retries resend the same
                # bytes (same discipline as _ship)
                h = extract_prefix(owner.engine, full, depth,
                                   skip=n_local, source=owner.name)
                if h is None:    # owner evicted since its last beat
                    holder["stale"] = True
                    return
                h.meta["request"] = {"request_id": -seq}
                h.meta["seq"] = seq
                h.meta["crc32"] = h.payload_crc32()
                holder["kv"] = h.kv_bytes()
                holder["data"] = encode_handoff(h)
            self.transport.send(ep, holder["data"])

        ok, _ = self._with_retry(_do)
        if holder.get("stale"):
            self._note_fetch_fail("stale")
            return None
        if not ok:
            self._note_fetch_fail("circuit_open"
                                  if self._res.breaker_open
                                  else "transport")
            # one attempt may still have delivered a frame whose ack
            # was lost — clean the side channel before falling back
            self._drain_fetch_endpoint(ep)
            return None
        fetched = None
        while True:                      # drain the side channel FULLY
            data = self.transport.recv(ep)
            if data is None:
                break
            try:
                h = decode_handoff(data)
                if h.meta.get("seq") != seq or fetched is not None:
                    # at-least-once retransmit: this fetch's duplicate
                    # or a concluded earlier fetch's straggler
                    self.prefix_fetch_duplicates += 1
                    _pc._M_FETCH_DUPS.inc()
                    continue
                h.verify_crc()           # loud, pre-allocation
            except ValueError:
                self._note_fetch_fail("corrupt")
                continue
            got = adopt_prefix(eng, h, local_blocks, full)
            if got is None:
                self._note_fetch_fail("pool_full")
                continue
            fetched = got
            self.prefix_fetches += 1
            self.prefix_fetch_blocks += len(got)
            self.prefix_fetch_kv_bytes.append(holder["kv"])
            _pc._M_FETCHES.inc()
            _pc._M_FETCH_BLOCKS.inc(len(got))
            _pc._M_FETCH_BYTES.inc(len(holder["data"]))
            self.flight.record("prefix_fetch", worker=w.name,
                               owner=owner.name, blocks=len(got),
                               clock=self._clock)
        return fetched

    def _spill_fetch(self, w: PrefillWorker, full, local_blocks,
                     dir_depth: int) -> Optional[List[int]]:
        """Serve a prefix fetch from the disk spill tier: deepest
        spilled chain on the prompt's digest path, CRC-verified,
        token-compared, re-skipped past the local match and adopted
        through the SAME scatter as a live fetch — bit-identical state
        either way. Every failure (armed ``spill.read``, unreadable
        file, CRC/collision mismatch, full pool) counts a miss and
        returns None: the caller falls back to a live owner or local
        prefill."""
        eng = w.engine
        n_local = len(local_blocks)
        sdepth, digest = self._spill.lookup(
            full, eng.kv_block_size, eng.manager.hash_fn)
        if digest is None or sdepth <= max(dir_depth, n_local):
            return None
        try:
            h = self._spill.read(digest)
        except (faults.InjectedFault, OSError, ValueError):
            self._spill.note_miss()
            self._note_fetch_fail("spill")
            return None
        bs = eng.kv_block_size
        stored = [int(t) for t in h.arrays["tokens"][:sdepth * bs]]
        if stored != [int(t) for t in full[:sdepth * bs]] \
                or int(h.meta.get("n_blocks", 0)) != sdepth:
            self._spill.note_miss()      # hash collision / stale file
            self._note_fetch_fail("spill")
            return None
        try:
            got = adopt_prefix(eng, _dur.slice_prefix_payload(
                h, n_local), local_blocks, full)
        except ValueError:
            self._spill.note_miss()      # incompatible payload
            self._note_fetch_fail("spill")
            return None
        if got is None:
            self._spill.note_miss()
            self._note_fetch_fail("pool_full")
            return None
        self._spill.note_hit()
        self.prefix_fetches += 1
        self.prefix_fetch_blocks += len(got)
        self.flight.record("prefix_spill_hit", worker=w.name,
                           blocks=len(got), depth=sdepth,
                           clock=self._clock)
        return got

    def _evict_tick(self):
        """Watermark eviction: when fleet-global block pressure (the
        fraction of usable blocks not free, summed over every live
        arena) exceeds ``evict_high``, evict LRU unreferenced
        registered blocks — most-pressured arenas first — until it is
        back at ``evict_low``. Referenced blocks are untouchable, so
        live streams never lose state; the owners' next heartbeats
        retract the evicted digests from the directory."""
        pool = [(w.engine, w.name) for w in self.prefill
                if self._alive(w.name)] \
            + [(d.engine, d.name) for d in self.decode
               if self._alive(d.name)]
        usable = sum(e.manager.usable_blocks() for e, _ in pool)
        if not usable:
            return
        free = sum(len(e.manager._free) for e, _ in pool)
        if 1.0 - free / usable <= self.evict_high:
            return
        need = int(np.ceil((1.0 - self.evict_low) * usable)) - free
        done = 0
        for e, name in sorted(pool,
                              key=lambda p: p[0].manager
                              .block_pressure(), reverse=True):
            if need <= 0:
                break
            if self._spill is not None:
                self._spill_victims(e, name, need)
            n = e.manager.evict_cached(need)
            need -= n
            done += n
        if done:
            self.prefix_evictions += done
            self.flight.record("prefix_evict", blocks=done,
                               clock=self._clock)

    def _spill_victims(self, engine, name: str, n: int):
        """Copy the chains about to be watermark-evicted from
        ``engine``'s arena into the disk spill tier — BEFORE
        ``evict_cached`` frees them, via the side-effect-free preview
        + extraction (the spill must not perturb which blocks the
        eviction then picks). Deepest chains only, deduped by prefix
        containment; a failed spill write is a lost optimization,
        never a failed eviction."""
        m = engine.manager
        victims = set(m.eviction_victims(n))
        if not victims:
            return
        tok_map = m.chain_tokens_map()
        cands = []
        for b in victims:
            d = m._digest_of.get(b)
            t = tok_map.get(d) if d is not None else None
            if t is not None:
                cands.append((m._depth.get(d, 0), d, t))
        cands.sort(key=lambda c: (-c[0], c[1]))
        kept = []
        for depth, d, t in cands:
            if any(kt[:len(t)] == t for _, _, kt in kept):
                continue            # covered by a deeper kept chain
            kept.append((depth, d, t))
        for depth, d, t in kept:
            try:
                h = _dur.extract_chain(engine, t, depth, source=name)
                if h is not None:
                    self._spill.put(d, h)
            except (faults.InjectedFault, OSError, ValueError):
                continue            # spill is best-effort by contract

    def tick(self):
        """One fleet tick: prefill advance → ship → deliver/adopt →
        decode advance → heartbeats/lease scan. Deterministic given
        the same submissions, kill schedule and fault schedule. Dead
        workers (lease expired) are skipped everywhere; killed-but-
        undetected workers simply stop making progress until their
        lease expires and their streams redrive."""
        self._clock += 1
        for w in self.prefill:
            if self._alive(w.name):
                w.tick()
        for w in self.prefill:
            if w.killed or not self._alive(w.name):
                continue        # a dead process ships nothing
            for ph in w.engine.take_handoffs():
                self._ship(w, ph)
        for d in self.decode:
            if self._alive(d.name):
                self._deliver(d)
        for d in self.decode:
            if self._alive(d.name):
                d.tick()
        self._beat()
        if self.prefix_cache_enabled:
            for ep in list(self._fetch_endpoints):
                self._drain_fetch_endpoint(ep)
            self._evict_tick()
        if self._redrive_t0:
            self._settle_redrives()
        if self._journal is not None:
            # terminals journal BEFORE the gc can drop their records —
            # a crash after gc must still know the stream concluded
            self._journal_terminals()
        if self._clock % 64 == 0:
            self._gc_records()
        if _om.enabled():
            for w in self.prefill:
                if self._alive(w.name):
                    _M_PF_DEPTH.set(w.queue_depth(), worker=w.name)
            for d in self.decode:
                if self._alive(d.name):
                    _M_DEC_FREE.set(d.free_slots(), worker=d.name)

    def busy(self) -> bool:
        return (any(w.busy() for w in self.prefill
                    if self._alive(w.name))
                or self.transport.pending() > 0
                or any(q for n, q in self._pending_adopt.items()
                       if self._alive(n))
                or any(d.busy() for d in self.decode
                       if self._alive(d.name)))

    def run_until_idle(self, max_ticks: Optional[int] = None
                       ) -> Dict[int, object]:
        ticks = 0
        while self.busy():
            if max_ticks is not None and ticks >= max_ticks:
                break
            self.tick()
            ticks += 1
        return self.results

    # -- worker health: heartbeats, leases, death --------------------------
    def _beat(self):
        """Collect every worker's heartbeat, renew leases, absorb
        decode-side token progress into the redrive records, and
        declare workers whose lease ran out dead."""
        for w in self.prefill:
            self._beat_one(w, "prefill")
        for d in self.decode:
            self._beat_one(d, "decode")

    def _beat_one(self, worker, role: str):
        h = self._health[worker.name]
        if h["state"] == "dead":
            return
        hb = worker.heartbeat()
        if hb is None:
            h["misses"] += 1
            self.flight.record("heartbeat_miss", worker=worker.name,
                               role=role, misses=h["misses"],
                               clock=self._clock)
            if h["misses"] >= self.lease_misses:
                self._declare_dead(worker, role)
            return
        h["misses"] = 0
        h["last"] = hb
        if self.prefix_cache_enabled and "prefixes" in hb:
            # the fleet.directory fault drops ONE publish: the
            # directory serves a stale view until the next beat — the
            # fetch path must degrade to stale-fallback, never corrupt
            if not faults.should_fire("fleet.directory"):
                self.directory.publish(worker.name, hb["prefixes"])
        if role == "decode":
            # progress carried by the heartbeat IS the redrive record:
            # after the worker dies, tokens generated since its last
            # beat are simply regenerated (the decode block is a pure
            # function of the carried state)
            for rid, toks in hb["progress"].items():
                if rid in self._handoffs:
                    self._progress[rid] = list(toks)
                    if self._journal is not None:
                        n0 = self._journaled_progress.get(rid, 0)
                        if len(toks) > n0:
                            # high-water marks journal as DELTAS; the
                            # only-extend replay guard makes them
                            # idempotent over a newer manifest
                            self._jrec({
                                "k": "progress", "rid": int(rid),
                                "base": int(n0),
                                "ext": [int(t)
                                        for t in toks[n0:]]})
                            self._journaled_progress[rid] = len(toks)

    def _declare_dead(self, worker, role: str):
        h = self._health[worker.name]
        h["state"] = "dead"
        self.workers_lost += 1
        _M_WORKERS_LOST.inc(role=role)
        _M_WORKER_STATE.set(0, worker=worker.name)
        self.flight.record("worker_dead", worker=worker.name,
                           role=role, clock=self._clock,
                           lease_misses=self.lease_misses)
        # the dead worker's directory entries expire with its lease —
        # later fetches stop considering it immediately
        self.directory.drop_worker(worker.name)
        if self._journal is not None:
            # recovery must NOT restore a worker that died post-
            # checkpoint: its streams redrive below, producing fresh
            # ship records the restored corpse would conflict with
            self._jrec({"k": "scale", "action": "dead",
                        "worker": worker.name, "role": role})
        if role == "decode":
            self._recover_decode_streams(worker)
        else:
            self._recover_prefill_streams(worker)

    def kill_decode_worker(self, idx: int):
        """Test/chaos hook: kill decode worker ``idx`` (the worker
        stops participating; the fleet notices via the lease and
        redrives its streams ``lease_misses`` ticks later)."""
        self.decode[idx].kill()

    def kill_prefill_worker(self, idx: int):
        self.prefill[idx].kill()

    # -- redrive: streams lost with a dead worker --------------------------
    def _terminal(self, rid: int) -> bool:
        return (rid in self._failures or rid in self._local_results
                or any(rid in w.server.results for w in self.prefill)
                or any(rid in d.server.results for d in self.decode))

    def _terminal_value(self, rid: int):
        """The terminal row/failure for ``rid``, or None while the
        stream is still open."""
        if rid in self._failures:
            return self._failures[rid]
        if rid in self._local_results:
            return self._local_results[rid]
        for w in self.prefill:
            v = w.server.results.get(rid)
            if v is not None:
                return v
        for d in self.decode:
            v = d.server.results.get(rid)
            if v is not None:
                return v
        return None

    def _journal_terminals(self):
        """Journal every terminal not yet written: completed ROWS ride
        the journal (first-write-wins), so finished results survive a
        whole-process crash without re-decoding — the worker results
        ledgers live in hub memory otherwise."""
        for rid in list(self._requests):
            if rid in self._journaled_terminals:
                continue
            v = self._terminal_value(rid)
            if v is None:
                continue
            if isinstance(v, RequestFailure):
                self._jrec({"k": "terminal", "rid": int(rid),
                            "failure": {
                                "reason": v.reason,
                                "message": v.message,
                                "tokens_emitted":
                                    int(v.tokens_emitted)}})
            else:
                self._jrec({"k": "terminal", "rid": int(rid),
                            "tokens": [int(t)
                                       for t in np.asarray(v)
                                       .reshape(-1)]})
            self._journaled_terminals.add(rid)
            self._journaled_progress.pop(rid, None)

    def _recover_decode_streams(self, d: DecodeWorker):
        """Every stream the dead decode worker owned — adopted,
        in-flight on the wire, or queued for adoption — is redriven
        from the fleet's records. The corpse's ENGINE state is never
        read: completed results count as terminal because they were
        DELIVERED at harvest (see DecodeWorker.kill); everything else
        reconstructs from the submission record + shipped key +
        heartbeat progress."""
        self.transport.drop_endpoint(d.name)
        self._pending_adopt[d.name].clear()
        self._assigned[d.name] = 0
        lost = [rid for rid, rec in self._handoffs.items()
                if rec["dst"] == d.name and not self._terminal(rid)]
        for rid in sorted(lost):
            self._redrive(rid)

    def _recover_prefill_streams(self, w: PrefillWorker):
        """A dead prefill worker's un-handed-off requests (queued,
        mid-prefill, or parked in its outbox) resubmit from the
        fleet's submission records under their ORIGINAL ids — nothing
        was lost but compute, so a fresh prefill on a surviving worker
        regenerates the identical stream."""
        ep = fetch_endpoint(w.name)
        self.transport.drop_endpoint(ep)
        self._fetch_endpoints.discard(ep)
        lost = [rid for rid, rec in self._requests.items()
                if rec["worker"] == w.name and rid not in self._handoffs
                and not self._terminal(rid)]
        for rid in sorted(lost):
            self._reinject(rid, resume=None)

    def _request_from_record(self, rid: int, resume) -> Request:
        rec = self._requests[rid]
        kw = rec["kw"]
        return Request(
            request_id=rid, prompt=rec["prompt"],
            max_new_tokens=kw.get("max_new_tokens", 20),
            temperature=kw.get("temperature", 0.0),
            top_k=kw.get("top_k", 0), top_p=kw.get("top_p", 1.0),
            eos_token_id=kw.get("eos_token_id"),
            seed=kw.get("seed", 0), t_submit=rec["t_submit"],
            deadline_ticks=kw.get("deadline_ticks"),
            deadline_s=kw.get("deadline_s"),
            tenant=kw.get("tenant", "default"),
            priority=kw.get("priority", 0), resume=resume)

    def _reinject(self, rid: int, resume) -> bool:
        """Route a reconstructed request to a surviving prefill worker
        under its original id. False = nowhere to go / cannot fit —
        the stream fails explicitly as ``worker_lost``."""
        if rid not in self._requests:
            self._fail_handoff(rid, "worker_lost",
                               "no submission record to redrive from")
            return False
        eligible = self._routable_prefill()
        if not eligible:
            self._fail_handoff(rid, "worker_lost",
                               "no surviving prefill worker to "
                               "redrive on")
            return False
        rec = self._requests[rid]
        req = self._request_from_record(rid, resume)
        pl = int(rec["prompt"].size)
        mnt = req.max_new_tokens
        if resume is not None and resume.tokens:
            pl += len(resume.tokens) - 1
            mnt = req.max_new_tokens - len(resume.tokens) + 1
        depths = [self.prefill[i].queue_depth() for i in eligible]
        wi = self.router.route(rec["prompt"], depths, eligible)
        w = self.prefill[wi]
        try:
            # the re-prefill must fit the TARGET engine
            w.engine.validate_request(pl, mnt)
        except ValueError as e:
            self._fail_handoff(rid, "worker_lost",
                               f"redrive does not fit {w.name}: {e}",
                               tokens=len(resume.tokens)
                               if resume else 0)
            return False
        # visible on the target's own clock immediately; rec["worker"]
        # moves so a second failure redrives from the right place
        req.arrival_step = w.server._clock
        rec["worker"] = w.name
        w.server.inject(req)
        self.flight.record("redrive", rid=rid, to=w.name,
                           carried_tokens=len(resume.tokens)
                           if resume else 0, clock=self._clock)
        return True

    def _redrive(self, rid: int):
        """Rebuild one lost stream: carried tokens from the last
        heartbeat, the rng key host-replayed from the shipped key
        (one split per observed token — the decode block's schedule),
        and the PR 13 resume path doing the rest. The redriven stream
        completes BIT-IDENTICAL to an unfailed run."""
        hrec = self._handoffs.pop(rid)
        toks = [int(t)
                for t in self._progress.pop(rid, hrec["tokens0"])]
        self._redrive_t0[rid] = time.perf_counter()
        key = _replay_key(hrec["key0"], len(toks) - hrec["base_len"])
        resume = ResumeState(tokens=toks, key=key,
                             t_admit=hrec["t_admit"], redrive=True)
        rec = self._requests.get(rid)
        if rec is not None:
            kw = rec["kw"]
            eos = kw.get("eos_token_id")
            done = (len(toks) >= kw.get("max_new_tokens", 20)
                    or (eos is not None and toks[-1] == eos))
            if done:
                # the carried history already holds every token (the
                # worker died between producing the last token and
                # harvesting it): complete locally, eos-padded to
                # max_new exactly like Server._harvest
                out = list(toks)
                mn = kw.get("max_new_tokens", 20)
                if len(out) < mn:
                    out += [eos] * (mn - len(out))
                self._local_results[rid] = np.concatenate(
                    [rec["prompt"],
                     np.asarray(out, np.int32)]).astype(np.int32)
                self.redrives += 1
                _M_REDRIVES.inc()
                return
        if self._reinject(rid, resume):
            self.redrives += 1
            _M_REDRIVES.inc()

    def _gc_records(self):
        """Drop failure-domain records of streams that reached a
        terminal (amortized: every 64 ticks) — a long-lived fleet must
        not hold every prompt it ever served. Open streams' records
        are untouchable: they ARE the redrive substrate."""
        done = [rid for rid in self._requests if self._terminal(rid)]
        for rid in done:
            self._requests.pop(rid, None)
            self._handoffs.pop(rid, None)
            self._progress.pop(rid, None)
            self._journaled_progress.pop(rid, None)
        self._journaled_terminals &= set(self._requests)

    def _settle_redrives(self):
        """Close the redrive-latency clock for redriven streams that
        reached a terminal (the bench's recovery-latency numbers)."""
        for rid in list(self._redrive_t0):
            if self._terminal(rid):
                self.redrive_latencies.append(
                    time.perf_counter() - self._redrive_t0.pop(rid))

    # -- results / stats ---------------------------------------------------
    @property
    def results(self) -> Dict[int, object]:
        out: Dict[int, object] = {}
        for w in self.prefill:
            out.update(w.server.results)
        for d in self.decode:
            out.update(d.server.results)
        out.update(self._local_results)
        out.update(self._failures)
        return out

    def prefix_hit_rate(self) -> float:
        """Fleet-wide prefix-cache hit rate: shared / submitted prompt
        tokens summed over every prefill worker."""
        pt = sum(w.engine.prompt_tokens for w in self.prefill)
        st = sum(w.engine.shared_tokens for w in self.prefill)
        return st / pt if pt else 0.0

    def stats(self) -> dict:
        res = self.results
        completed = sum(1 for v in res.values()
                        if not isinstance(v, RequestFailure))
        wire = self.handoff_wire_bytes
        kv = self.handoff_kv_bytes
        return {
            "requests_completed": completed,
            "requests_failed": len(res) - completed,
            "handoffs": self.handoffs,
            "handoff_wire_bytes_mean": round(float(np.mean(wire)), 1)
            if wire else 0.0,
            "handoff_kv_bytes_mean": round(float(np.mean(kv)), 1)
            if kv else 0.0,
            "handoff_failures": dict(self._res.failures_by_reason),
            "handoff_retries": self._res.retries,
            "breaker_open": self._res.breaker_open,
            "affinity_routes": self.router.affinity_routes,
            "spillovers": self.router.spillovers,
            "prefix_hit_rate": round(self.prefix_hit_rate(), 4),
            "prefix_fetches": self.prefix_fetches,
            "prefix_fetch_blocks": self.prefix_fetch_blocks,
            "prefix_fetch_kv_bytes_mean": round(float(np.mean(
                self.prefix_fetch_kv_bytes)), 1)
            if self.prefix_fetch_kv_bytes else 0.0,
            "prefix_fetch_failures": dict(self.prefix_fetch_failures),
            "prefix_fetch_duplicates": self.prefix_fetch_duplicates,
            "prefix_evictions": self.prefix_evictions,
            "prefix_directory": self.directory.stats()
            if self.prefix_cache_enabled else None,
            "migrations": self.migrations,
            "ticks": self._clock,
            "lease_misses": self.lease_misses,
            "workers_lost": self.workers_lost,
            "redrives": self.redrives,
            "redrive_latency_p50_s": round(float(np.percentile(
                self.redrive_latencies, 50)), 4)
            if self.redrive_latencies else None,
            "redrive_latency_p95_s": round(float(np.percentile(
                self.redrive_latencies, 95)), 4)
            if self.redrive_latencies else None,
            "duplicate_adopts": sum(d.duplicate_adopts
                                    for d in self.decode),
            "worker_states": {n: h["state"]
                              for n, h in sorted(self._health.items())},
            "transport": self.transport.stats()
            if hasattr(self.transport, "stats") else None,
            "durability": None if self.durability_dir is None else {
                "dir": self.durability_dir,
                "epoch": self._dur_epoch,
                "journal_seq": self._journal.seq,
                "journal_appends": self._journal.appends,
                "journal_bytes": self._journal.bytes_written,
                "recoveries": self.recoveries,
                "last_recovery": self.last_recovery,
                "spill": self._spill.stats()
                if self._spill is not None else None},
            "prefill_workers": [
                {"name": w.name, "state": self._health[w.name]["state"],
                 "queue": w.queue_depth(),
                 "tokens_emitted": w.engine.tokens_emitted,
                 "block_pressure": round(
                     w.engine.manager.block_pressure(), 4),
                 "prefill_compiles": w.engine.prefill_compile_count()}
                for w in self.prefill],
            "decode_workers": [
                {"name": d.name, "state": self._health[d.name]["state"],
                 "free_slots": d.free_slots(),
                 "draining": d.name in self._draining_decode,
                 "tokens_emitted": d.engine.tokens_emitted,
                 "block_pressure": round(
                     d.engine.manager.block_pressure(), 4),
                 "decode_compiles": d.engine.decode_compile_count()}
                for d in self.decode],
        }

    # -- durable control plane: checkpoint / recover (PR 20) ---------------
    def checkpoint(self) -> str:
        """Coordinated fleet checkpoint at a tick boundary: snapshot
        every live worker's Server (the PR 5 npz path — un-shipped
        outboxes now ride it), then commit fleet registries + topology
        + the flight ring ATOMICALLY by renaming the epoch manifest
        into place. The rename is THE commit: only after it does the
        journal rotate to a fresh segment (the old one is fully
        absorbed) and stale epochs get pruned. A crash anywhere in
        between recovers from the previous epoch's manifest+journal —
        every window is covered. Returns the manifest path."""
        if self.durability_dir is None:
            raise RuntimeError(
                "fleet has no durability directory — construct with "
                "durability=<dir> to enable checkpoints")
        d = self.durability_dir
        epoch = self._dur_epoch + 1
        # the checkpoint event goes into the ring BEFORE capture so
        # the recovered fleet's history includes it (PR 6 contract)
        self.flight.record("checkpoint", epoch=epoch,
                           clock=self._clock)
        workers = []
        for i, w in enumerate(self.prefill):
            if w.killed or not self._alive(w.name):
                continue        # a corpse's state is unreadable by
            snap = os.path.basename(    # contract; its streams redrive
                _dur.snapshot_path(d, epoch, w.name))
            w.server.snapshot(os.path.join(d, snap))
            workers.append({"name": w.name, "role": "prefill",
                            "snapshot": snap,
                            "draining": i in self._draining})
        for dw in self.decode:
            if dw.killed or not self._alive(dw.name):
                continue
            snap = os.path.basename(
                _dur.snapshot_path(d, epoch, dw.name))
            dw.server.snapshot(os.path.join(d, snap))
            workers.append({"name": dw.name, "role": "decode",
                            "snapshot": snap,
                            "draining":
                                dw.name in self._draining_decode})
        manifest = {
            "clock": self._clock,
            "workers": workers,
            "requests": {str(rid): {
                "prompt": [int(t) for t in rec["prompt"]],
                "worker": rec["worker"], "kw": dict(rec["kw"])}
                for rid, rec in self._requests.items()},
            "handoffs": {str(rid): {
                "dst": h["dst"],
                "key0": [int(x) for x in
                         np.asarray(h["key0"]).reshape(-1)],
                "base_len": int(h["base_len"]),
                "tokens0": [int(t) for t in h["tokens0"]],
                "t_admit": float(h["t_admit"])}
                for rid, h in self._handoffs.items()},
            "progress": {str(rid): [int(t) for t in toks]
                         for rid, toks in self._progress.items()},
            "failures": {str(rid): {
                "reason": f.reason, "message": f.message,
                "tokens_emitted": int(f.tokens_emitted)}
                for rid, f in self._failures.items()},
            "local_results": {str(rid): [int(t) for t in
                                         np.asarray(v).reshape(-1)]
                              for rid, v in
                              self._local_results.items()},
            "router": {"affinity_routes": self.router.affinity_routes,
                       "spillovers": self.router.spillovers},
            "handoff_seq": self._handoff_seq,
            "fetch_seq": self._fetch_seq,
            "counters": {
                "handoffs": self.handoffs,
                "migrations": self.migrations,
                "redrives": self.redrives,
                "workers_lost": self.workers_lost,
                "prefix_evictions": self.prefix_evictions,
                "prefix_fetches": self.prefix_fetches,
                "prefix_fetch_blocks": self.prefix_fetch_blocks,
                "recoveries": self.recoveries},
            "flight": self.flight.to_meta(),
        }
        path = _dur.write_manifest(d, epoch, manifest)
        # the commit landed: rotate to the fresh segment and remember
        # what the manifest already absorbed so nothing re-journals
        self._journal.close()
        self._attach_durability(d, epoch)
        self._journaled_terminals = {
            rid for rid in self._requests if self._terminal(rid)}
        self._journaled_progress = {
            rid: len(toks) for rid, toks in self._progress.items()}
        self._prune_durability(epoch)
        return path

    def _prune_durability(self, keep_epoch: int):
        """Delete manifests/journals/snapshots of epochs older than
        ``keep_epoch`` — including orphans of checkpoints that crashed
        before their commit."""
        d = self.durability_dir
        for name in os.listdir(d):
            for pfx in ("manifest-", "journal-", "ckpt-"):
                if not name.startswith(pfx):
                    continue
                stem = name[len(pfx):].split("-", 1)[0] \
                    .split(".", 1)[0]
                if stem.isdigit() and int(stem) < keep_epoch:
                    try:
                        os.remove(os.path.join(d, name))
                    except OSError:
                        pass

    @classmethod
    def recover(cls, dirname: str, *, engine_factory,
                transport: Optional[Transport] = None,
                **fleet_kw) -> "Fleet":
        """Cold-start recovery of a whole killed fleet: load the
        newest VALID manifest (torn ones discarded loudly), replay the
        journal tail (torn tail truncated loudly), rebuild every
        worker via ``engine_factory(role, name)`` + ``Server.restore``,
        purge streams the journal knows concluded, and REDRIVE every
        stream that was in flight — queued, mid-prefill, shipped-in-
        transit, adopted — with the PR 15 host-replayed key machinery,
        so completed rows are BIT-IDENTICAL to an uncrashed run. The
        recovered fleet continues journaling into the same epoch
        segment."""
        epoch, manifest = _dur.load_latest_manifest(dirname)
        if manifest is None:
            epochs = _dur.list_epochs(dirname, "journal")
            if not epochs:
                raise FileNotFoundError(
                    f"no checkpoint manifest or journal under "
                    f"{dirname!r} — nothing to recover")
            epoch = epochs[-1]
        records, torn = _dur.WriteAheadJournal.replay(
            _dur.journal_path(dirname, epoch))
        # -- topology: manifest workers (or journal genesis), then the
        # journal's scale/death records applied in order --
        if manifest is not None:
            spec = [dict(e) for e in manifest["workers"]]
        else:
            gen = next((r for r in records
                        if r.get("k") == "genesis"), None)
            if gen is None:
                raise RuntimeError(
                    f"journal epoch {epoch} has no genesis record and "
                    "no manifest — cannot derive the fleet topology")
            spec = [{"name": n, "role": "prefill", "snapshot": None,
                     "draining": False} for n in gen["prefill"]] \
                + [{"name": n, "role": "decode", "snapshot": None,
                    "draining": False} for n in gen["decode"]]
        for r in records:
            if r.get("k") != "scale":
                continue
            a, n = r["action"], r["worker"]
            if a == "add_decode":
                spec.append({"name": n, "role": "decode",
                             "snapshot": None, "draining": False})
            elif a in ("remove_decode", "remove_prefill", "dead"):
                spec = [e for e in spec if e["name"] != n]
            elif a in ("drain_decode", "drain_prefill"):
                for e in spec:
                    if e["name"] == n:
                        e["draining"] = True
            elif a == "undrain_decode":
                for e in spec:
                    if e["name"] == n:
                        e["draining"] = False
        pws: List[PrefillWorker] = []
        dws: List[DecodeWorker] = []
        for e in spec:
            eng = engine_factory(e["role"], e["name"])
            srv = None
            if e.get("snapshot"):
                srv = Server.restore(
                    os.path.join(dirname, e["snapshot"]), eng)
            if e["role"] == "prefill":
                pws.append(PrefillWorker(eng, name=e["name"],
                                         server=srv))
            else:
                dws.append(DecodeWorker(eng, name=e["name"],
                                        server=srv))
        fleet = cls(pws, dws, transport=transport, **fleet_kw)
        # -- registries: manifest base, then the journal overlay
        # applied sequentially (idempotent: progress only extends,
        # terminals first-write-wins) --
        if manifest is not None:
            fleet._clock = int(manifest.get("clock", 0))
            now = time.perf_counter()
            for rid_s, m in manifest["requests"].items():
                fleet._requests[int(rid_s)] = {
                    "prompt": np.asarray(m["prompt"], np.int32),
                    "worker": m["worker"], "t_submit": now,
                    "kw": dict(m["kw"])}
            for rid_s, m in manifest["handoffs"].items():
                fleet._handoffs[int(rid_s)] = {
                    "dst": m["dst"],
                    "key0": np.asarray(m["key0"], np.uint32),
                    "base_len": int(m["base_len"]),
                    "tokens0": list(m["tokens0"]),
                    "t_admit": float(m["t_admit"])}
            fleet._progress = {int(r): list(t) for r, t in
                               manifest["progress"].items()}
            for rid_s, m in manifest["failures"].items():
                rid = int(rid_s)
                fleet._failures[rid] = RequestFailure(
                    request_id=rid, reason=m["reason"],
                    message=m["message"],
                    tokens_emitted=int(m["tokens_emitted"]))
            fleet._local_results = {
                int(r): np.asarray(t, np.int32)
                for r, t in manifest["local_results"].items()}
            fleet.router.affinity_routes = \
                int(manifest["router"]["affinity_routes"])
            fleet.router.spillovers = \
                int(manifest["router"]["spillovers"])
            fleet._handoff_seq = int(manifest["handoff_seq"])
            fleet._fetch_seq = int(manifest["fetch_seq"])
            c = manifest.get("counters", {})
            fleet.handoffs = int(c.get("handoffs", 0))
            fleet.migrations = int(c.get("migrations", 0))
            fleet.redrives = int(c.get("redrives", 0))
            fleet.workers_lost = int(c.get("workers_lost", 0))
            fleet.prefix_evictions = int(c.get("prefix_evictions", 0))
            fleet.prefix_fetches = int(c.get("prefix_fetches", 0))
            fleet.prefix_fetch_blocks = \
                int(c.get("prefix_fetch_blocks", 0))
            fleet.recoveries = int(c.get("recoveries", 0))
            # the fleet flight ring survives the crash with continuing
            # seqs — the same contract PR 6 pinned for Server
            fleet.flight.restore_meta(manifest["flight"])
        name_to_pi = {w.name: i for i, w in enumerate(fleet.prefill)}
        for e in spec:
            if e.get("draining"):
                if e["role"] == "prefill":
                    fleet._draining.add(name_to_pi[e["name"]])
                else:
                    fleet._draining_decode.add(e["name"])
        now = time.perf_counter()
        for r in records:
            k = r.get("k")
            if k == "submit":
                fleet._requests[int(r["rid"])] = {
                    "prompt": np.asarray(r["prompt"], np.int32),
                    "worker": r["worker"], "t_submit": now,
                    "kw": dict(r["kw"])}
            elif k == "ship":
                rid = int(r["rid"])
                fleet._handoffs[rid] = {
                    "dst": r["dst"],
                    "key0": np.asarray(r["key0"], np.uint32),
                    "base_len": int(r["base_len"]),
                    "tokens0": list(r["tokens0"]),
                    "t_admit": float(r["t_admit"])}
                fleet._progress[rid] = list(r["tokens0"])
                fleet._handoff_seq = max(fleet._handoff_seq,
                                         int(r["seq"]))
            elif k == "progress":
                rid = int(r["rid"])
                cur = fleet._progress.get(rid)
                base = int(r["base"])
                if cur is None or base > len(cur):
                    continue        # its ship record fell in a torn
                cand = cur[:base] + list(r["ext"])      # tail — the
                if len(cand) > len(cur):    # redrive uses what stands
                    fleet._progress[rid] = cand
            elif k == "terminal":
                rid = int(r["rid"])
                if fleet._terminal(rid):
                    continue                # first write wins
                if "failure" in r:
                    f = r["failure"]
                    fleet._failures[rid] = RequestFailure(
                        request_id=rid, reason=f["reason"],
                        message=f["message"],
                        tokens_emitted=int(f["tokens_emitted"]))
                else:
                    fleet._local_results[rid] = np.asarray(
                        r["tokens"], np.int32)
        # fresh submissions must never reuse a pre-crash rid: bump
        # every prefill server's allocator past the ids its range is
        # known to have issued (snapshots cover their own, but rids
        # issued AFTER the checkpoint only exist in the journal)
        known = set(fleet._requests) | set(fleet._failures) \
            | set(fleet._local_results)
        for i, w in enumerate(fleet.prefill):
            base, hi = (i + 1) * 1_000_000, (i + 2) * 1_000_000
            mx = max((rid for rid in known if base <= rid < hi),
                     default=None)
            if mx is not None and w.server._next_id <= mx:
                w.server._next_id = mx + 1
        fleet._attach_durability(dirname, epoch)
        fleet._journaled_progress = {
            rid: len(toks) for rid, toks in fleet._progress.items()}
        fleet._journaled_terminals = {
            rid for rid in fleet._requests if fleet._terminal(rid)}
        # -- purge: streams the control plane knows concluded must not
        # decode again on a restored worker (exactly ONE terminal per
        # request across pre- and post-crash traces) --
        fleet._purge_terminal_streams()
        # -- redrive: everything in flight that no restored worker
        # owns reconstructs from the records, exactly as if the owner
        # alone had died (PR 15) --
        owned = fleet._owned_rids()
        redriven = 0
        for rid in sorted(fleet._requests):
            if rid in owned or fleet._terminal(rid):
                continue
            redriven += 1
            if rid in fleet._handoffs:
                fleet._redrive(rid)
            else:
                fleet._reinject(rid, None)
        fleet.recoveries += 1
        fleet.last_recovery = {
            "epoch": int(epoch), "replayed": len(records),
            "torn_tail": bool(torn), "redriven": redriven,
            "workers": len(spec)}
        _dur._M_J_REPLAYS.inc(len(records))
        _dur._M_CKPT_RECOVERIES.inc()
        fleet.flight.record("recovered", epoch=int(epoch),
                            clock=fleet._clock,
                            replayed=len(records), redriven=redriven)
        return fleet

    def _owned_rids(self) -> set:
        """Every rid a restored worker holds live — queued, mid-
        prefill, parked in an outbox (an outbox run occupies its
        slot), or decoding. Owned streams finish on their own,
        bit-identically: the decode block is a pure function of the
        restored state."""
        owned = set()
        for worker in list(self.prefill) + list(self.decode):
            for r in worker.server.scheduler._queue:
                owned.add(r.request_id)
            for _slot, run in worker.engine.live_runs():
                owned.add(run.request.request_id)
        return owned

    def _purge_terminal_streams(self):
        done = [rid for rid in self._requests if self._terminal(rid)]
        for rid in done:
            for w in self.prefill:
                self._purge_from_worker(w, rid, prefill=True)
            for d in self.decode:
                self._purge_from_worker(d, rid, prefill=False)

    def _purge_from_worker(self, worker, rid: int, prefill: bool):
        """Remove every live trace of a concluded stream from a
        restored worker: queue entry, outbox hold, occupied slot —
        and the cancel artifact itself, so the server never harvests
        a SECOND terminal for the rid."""
        eng = worker.engine
        worker.server.scheduler.drop_where(
            lambda r: r.request_id == rid)
        if prefill:
            for ph in list(eng._outbox):
                if ph.run.request.request_id == rid:
                    eng._outbox.remove(ph)
                    eng.release_handoff(ph)
        for slot, run in eng.live_runs():
            if run.request.request_id == rid:
                eng.cancel_slot(slot, "recovered_terminal")
        eng._finished = [r for r in eng._finished
                         if r.request.request_id != rid]

    # -- scale / migration -------------------------------------------------
    def add_decode_worker(self, worker: DecodeWorker):
        """Scale up the decode pool mid-stream; the least-loaded pick
        starts routing payloads to it on the next tick. Same
        compatibility contract as construction — an incompatible
        engine is refused here, not discovered when a payload fails to
        adopt mid-stream. The ``fleet.scale`` fault site fires BEFORE
        any state mutates, so a transiently-failed scale action
        retries cleanly under the PR 5 policy."""
        faults.fault_point("fleet.scale")
        self._check_engine_compat(worker.engine,
                                  self.prefill[0].engine)
        worker.name = worker.name or f"decode{len(self.decode)}"
        if worker.name in self._health:
            raise ValueError(f"decode worker name {worker.name!r} "
                             "already in the fleet")
        self.decode.append(worker)
        self._pending_adopt[worker.name] = deque()
        self._assigned[worker.name] = 0
        self._health[worker.name] = {"state": "live", "misses": 0}
        _M_WORKER_STATE.set(1, worker=worker.name)
        if self._journal is not None:
            self._jrec({"k": "scale", "action": "add_decode",
                        "worker": worker.name})

    def drain_decode_worker(self, idx: int):
        """Stop routing new handoffs to decode worker ``idx``; its
        in-flight streams finish in place (bit-identical — nothing
        about their state moves), and once idle it can be removed.
        Idempotent; refuses to drain the last routable decode
        worker. The ``fleet.scale`` fault site covers it like every
        scale action."""
        faults.fault_point("fleet.scale")
        if not 0 <= idx < len(self.decode):
            raise ValueError(f"no decode worker at index {idx}")
        name = self.decode[idx].name
        if name in self._draining_decode:
            return
        routable = [d.name for d in self._live_decode()
                    if d.name not in self._draining_decode
                    and d.name != name]
        if not routable:
            raise ValueError("cannot drain the last routable decode "
                             "worker")
        self._draining_decode.add(name)
        self.flight.record("decode_drain", worker=name,
                           clock=self._clock)
        if self._journal is not None:
            self._jrec({"k": "scale", "action": "drain_decode",
                        "worker": name})

    def undrain_decode_worker(self, idx: int):
        """Cancel a pending drain — the cheap scale-up when traffic
        returns before the drain converged (no fresh engine, no new
        programs; the worker simply becomes routable again)."""
        faults.fault_point("fleet.scale")
        if not 0 <= idx < len(self.decode):
            raise ValueError(f"no decode worker at index {idx}")
        name = self.decode[idx].name
        if name in self._draining_decode:
            self._draining_decode.discard(name)
            self.flight.record("decode_undrain", worker=name,
                               clock=self._clock)
            if self._journal is not None:
                self._jrec({"k": "scale", "action": "undrain_decode",
                            "worker": name})

    def remove_decode_worker(self, idx: int) -> DecodeWorker:
        """Scale down: remove a DRAINED decode worker. Refused while
        the worker still owns streams (busy slots, queued adoptions,
        or payloads assigned on the wire) — drain first and run the
        fleet until it empties. Dead workers are not removable: their
        tombstones keep the name reserved and the lease history
        readable."""
        faults.fault_point("fleet.scale")
        if not 0 <= idx < len(self.decode):
            raise ValueError(f"no decode worker at index {idx}")
        d = self.decode[idx]
        if not self._alive(d.name):
            raise RuntimeError(
                f"decode worker {d.name!r} is dead — its streams "
                "were redriven and its tombstone stays")
        if len(self._live_decode()) < 2:
            raise ValueError("cannot remove the last live decode "
                             "worker")
        if (d.busy() or self._pending_adopt[d.name]
                or self._assigned[d.name]):
            raise RuntimeError(
                f"decode worker {d.name!r} still owns streams — "
                "drain and run the fleet idle first")
        # results are fleet-durable: streams the worker completed must
        # survive its removal (scale-down would otherwise lose them)
        self._local_results.update(d.server.results)
        self.decode.pop(idx)
        self._draining_decode.discard(d.name)
        self._pending_adopt.pop(d.name, None)
        self._assigned.pop(d.name, None)
        self._health.pop(d.name, None)
        self.directory.drop_worker(d.name)
        self.transport.drop_endpoint(d.name)
        _M_WORKER_STATE.set(0, worker=d.name)
        self.flight.record("decode_remove", worker=d.name,
                           clock=self._clock)
        if self._journal is not None:
            # completed results move into _local_results above; the
            # terminal scan journals any not yet written, so removal
            # never loses a result across a crash
            self._journal_terminals()
            self._jrec({"k": "scale", "action": "remove_decode",
                        "worker": d.name})
        return d

    def migrate_decode_worker(self, idx: int, engine,
                              path: str) -> DecodeWorker:
        """Live migration = PR 5 snapshot/restore: snapshot worker
        ``idx``'s Server at a tick boundary, restore into a freshly
        constructed engine of the same configuration, and swap it into
        the fleet under the SAME name (in-transit payloads addressed to
        it deliver to the successor). Every in-flight stream finishes
        bit-identical — the decode block is a pure function of the
        restored state."""
        old = self.decode[idx]
        if old.killed or not self._alive(old.name):
            raise RuntimeError(
                "cannot migrate a dead worker — its state is "
                "unreadable by contract; its streams redrive instead")
        old.server.snapshot(path)
        srv = Server.restore(path, engine)
        new = DecodeWorker(engine, name=old.name, server=srv)
        new._adopted = set(old._adopted)     # the dedup history moves
        self.decode[idx] = new               # with the identity
        self._health[old.name] = {"state": "live", "misses": 0}
        _M_WORKER_STATE.set(1, worker=old.name)
        self.migrations += 1
        _M_MIGRATIONS.inc()
        return new

    def drain_prefill_worker(self, idx: int):
        """Stop routing new work to prefill worker ``idx``; once idle
        (queue drained, outbox shipped) it can be removed or
        snapshotted for migration. Idempotent — re-draining a draining
        worker is a no-op, not a spurious last-worker refusal."""
        if not 0 <= idx < len(self.prefill):
            raise ValueError(f"no prefill worker at index {idx}")
        if idx in self._draining:
            return
        if len([i for i in self._routable_prefill() if i != idx]) < 1:
            raise ValueError("cannot drain the last routable prefill "
                             "worker")
        self._draining.add(idx)
        if self._journal is not None:
            self._jrec({"k": "scale", "action": "drain_prefill",
                        "worker": self.prefill[idx].name})

    def remove_prefill_worker(self, idx: int):
        if self.prefill[idx].busy():
            raise RuntimeError("prefill worker still busy — drain and "
                               "run the fleet idle first")
        self._draining.discard(idx)
        w = self.prefill.pop(idx)
        self._health.pop(w.name, None)
        self.directory.drop_worker(w.name)
        ep = fetch_endpoint(w.name)
        self.transport.drop_endpoint(ep)
        self._fetch_endpoints.discard(ep)
        self._draining = {i - 1 if i > idx else i
                          for i in self._draining}
        if self._journal is not None:
            self._jrec({"k": "scale", "action": "remove_prefill",
                        "worker": w.name})
        return w
