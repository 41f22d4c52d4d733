"""Host-side request scheduler for the continuous-batching engine.

Reference parity: the reference serving frontend's dynamic batching
queue (SURVEY §2.1 Inference — verify). Admission is FIFO over an
arrival-ordered queue with a max-wait batching knob: the scheduler can
hold admissions until ``min_admit`` requests are queued (amortizing
prefill dispatches) but never longer than ``max_wait_steps`` engine
blocks past the oldest request's arrival — and it always releases when
the engine would otherwise sit idle."""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

__all__ = ["Request", "ResumeState", "Scheduler"]


@dataclass
class ResumeState:
    """Carried state of a preempted in-flight request (the host half of
    the PR 5 per-slot snapshot discipline, small enough to ride the
    queue): the token stream generated so far — ``tokens[-1]`` is the
    next token to decode, sampled but not yet written to KV — the
    slot's rng key at eviction (the key the NEXT step would have split,
    which is what makes a resumed seeded-sampled stream bit-identical
    to an uninterrupted run), and the original first-token timestamp so
    TTFT keeps measuring the FIRST admission. Serialized into server
    snapshots by ``resilience.request_to_meta``.

    ``redrive`` marks fleet failure recovery (serving/fleet.py): the
    carried state was reconstructed from the fleet's own records after
    the stream's decode worker died, not handed back by a live engine.
    Prefill-only engines accept redrive resumes (the lost stream must
    re-prefill SOMEWHERE) while still refusing user-initiated
    preemption resumes — the fleet never preempts."""
    tokens: List[int] = field(default_factory=list)
    key: Optional[np.ndarray] = None    # (2,) uint32 per-slot PRNG key
    t_admit: float = 0.0
    redrive: bool = False


@dataclass
class Request:
    """One generation request. ``temperature <= 0`` decodes greedily;
    per-request sampling params ride the engine's per-slot state arrays,
    so mixed greedy/sampled traffic shares one compiled program.
    ``arrival_step``: engine-block clock tick at which the request
    becomes visible (deterministic staggered-arrival testing).

    ``deadline_ticks`` / ``deadline_s``: per-request deadlines (engine
    ticks past ``arrival_step`` / wall seconds past submit). A request
    still queued or in flight past its deadline is CANCELLED — slot
    freed, paged blocks released — and a ``RequestFailure`` lands in
    ``Server.results`` instead of a silent hang (None disables; the
    server-level ``ResilienceConfig`` supplies defaults).

    ``tenant`` / ``priority``: the multi-tenant front-door dimensions
    (serving/frontend.py). Tenants share throughput by weighted-fair
    queueing; priorities are strict — a higher-priority request admits
    first within the fairness tier and, with preemption armed, can
    evict a strictly-lower-priority slot mid-decode. ``resume`` is set
    by that eviction: a queued request carrying one re-prefills its
    generated history instead of sampling afresh."""
    request_id: int
    prompt: np.ndarray
    max_new_tokens: int = 20
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    seed: int = 0
    arrival_step: int = 0
    t_submit: float = 0.0
    deadline_ticks: Optional[int] = None
    deadline_s: Optional[float] = None
    tenant: str = "default"
    priority: int = 0
    resume: Optional[ResumeState] = None
    # set to the current tick when a preemption requeues the request:
    # the max-queue-wait gate measures from HERE, not arrival_step — a
    # victim's decode time is service, not queue wait (deadlines, which
    # are end-to-end by contract, still measure from arrival)
    wait_from: Optional[int] = None


class Scheduler:
    """FIFO admission queue + batching gate + prefill pacing.

    ``prefill_token_budget``: per-tick cap on admitted PROMPT tokens —
    the chunked-prefill pacing knob. A long prompt admitted into the
    paged engine prefills in chunks paced by this same budget
    (engine.prefill_tick), so one tick never steals more than ~budget
    tokens of prefill from the in-flight decode — that bounds the
    decode-latency spike a long prompt used to cause. The chunk is the
    budget's granule: a tick with prefill pending always runs at least
    one chunk, which at the paged engine's default
    (``paging.default_prefill_chunk``) is up to 256 prompt tokens —
    about twice the device time of a 32-token chunk for eight times the
    tokens — so a budget below the chunk length paces to one chunk a
    tick, not to the budget. At least one request always passes when
    the gate is open (no starvation)."""

    # strict FIFO pop: a slot freed by preemption would go back to the
    # front-inserted victim, so the Server's preemption policy refuses
    # to run on this class (frontend.FairScheduler sets True)
    priority_aware = False

    def __init__(self, max_wait_steps: int = 0, min_admit: int = 1,
                 prefill_token_budget: Optional[int] = None):
        if min_admit < 1:
            raise ValueError(f"min_admit={min_admit}; must be >= 1")
        if prefill_token_budget is not None and prefill_token_budget < 1:
            raise ValueError(
                f"prefill_token_budget={prefill_token_budget}; must be "
                ">= 1 (None disables pacing)")
        self.max_wait_steps = max_wait_steps
        self.min_admit = min_admit
        self.prefill_token_budget = prefill_token_budget
        self._queue: List[Request] = []

    def submit(self, request: Request):
        # keep the queue sorted by arrival tick; insort_right preserves
        # FIFO within a tick and costs O(log Q) per submit instead of a
        # full re-sort (the north star is heavy traffic)
        bisect.insort(self._queue, request,
                      key=lambda r: r.arrival_step)

    def requeue(self, request: Request):
        """Put a popped request back at the FRONT of its arrival tick
        (the engine deferred it — e.g. the paged block pool was
        exhausted); insort_left lands it before same-tick peers."""
        bisect.insort_left(self._queue, request,
                           key=lambda r: r.arrival_step)

    def pending(self) -> int:
        return len(self._queue)

    def drop_where(self, pred) -> List[Request]:
        """Remove and return every queued request matching ``pred`` —
        the deadline/queue-wait expiry and circuit-breaker drain hook
        (arrival order of the survivors is preserved)."""
        dropped = [r for r in self._queue if pred(r)]
        if dropped:
            self._queue = [r for r in self._queue if not pred(r)]
        return dropped

    def next_arrival(self) -> Optional[int]:
        return self._queue[0].arrival_step if self._queue else None

    def visible(self, now: int) -> List[Request]:
        """Queued requests already visible at tick ``now`` (a PEEK — the
        queue is untouched). The server's preemption policy reads this
        to decide whether higher-priority work is waiting on capacity."""
        n = bisect.bisect_right(self._queue, now,
                                key=lambda r: r.arrival_step)
        return self._queue[:n]

    def _gate_visible(self, now: int, free_slots: int,
                      engine_idle: bool,
                      token_budget: Optional[int]):
        """Shared admission preamble for this class and its fair
        subclass (one implementation, so a gate-semantics fix can never
        silently diverge the two): returns ``(n_visible,
        token_budget)`` when the batching gate is open, else None. The
        gate holds until ``min_admit`` requests are visible OR the
        oldest visible request has waited ``max_wait_steps`` ticks —
        unless the engine is idle (no live slots), where holding would
        only add latency."""
        if free_slots <= 0 or not self._queue:
            return None
        # the queue is arrival-sorted: visible requests are a prefix
        n_visible = bisect.bisect_right(self._queue, now,
                                        key=lambda r: r.arrival_step)
        if n_visible == 0:
            return None
        oldest_wait = now - self._queue[0].arrival_step
        gate_open = (n_visible >= self.min_admit
                     or oldest_wait >= self.max_wait_steps
                     or engine_idle)
        if not gate_open:
            return None
        if token_budget is None:
            token_budget = self.prefill_token_budget
        return n_visible, token_budget

    def pop_ready(self, now: int, free_slots: int, engine_idle: bool,
                  token_budget: Optional[int] = None) -> List[Request]:
        """Requests to admit this tick (see :meth:`_gate_visible` for
        the batching gate). The released prefix is additionally cut at
        the prefill token budget (argument, else the scheduler's own;
        first request exempt)."""
        gate = self._gate_visible(now, free_slots, engine_idle,
                                  token_budget)
        if gate is None:
            return []
        n_visible, token_budget = gate
        take: List[Request] = []
        tokens = 0
        for r in self._queue[:min(free_slots, n_visible)]:
            t = int(np.asarray(r.prompt).size)
            if take and token_budget is not None \
                    and tokens + t > token_budget:
                break
            take.append(r)
            tokens += t
        del self._queue[:len(take)]
        return take
