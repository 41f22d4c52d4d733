"""Host-side tracing: ONE span recorder for the whole program, plus
per-request lifecycle traces for the serving stack.

**The recorder.** ``span(name, **ids)`` (a context manager; ``begin`` /
``end`` for a pair that cannot be a ``with`` block) stamps ``start`` and
``dur`` on ``time.perf_counter_ns``, remembers the span open on the same
thread when it began (``parent``, a per-thread stack) and carries the
ids it was given (``tick=<Server._clock>``, ``rid=<request id>``,
``step=<n>``). One call, two sinks:

- a process-global ring, ``deque(maxlen=65536)`` of finished spans. It
  is always on and bounded, as the flight recorder's ring is: there is
  no switch, so the price (1.4 us a span on the chip's host, 2.8 us
  inside a profiler session: PR 25's chip run; 0.3 us a mark, 7.4 us a
  :class:`StallWatch` pair, 6.1 of them its one ``getrusage``, 17 us a
  serving tick for all the marks and watches: PR 35's,
  ``tools/sync_stamp_probe.py``; PERF.md section 3; at most about 18 MB
  when the ring is full) is always paid, and spans are
  recorded at tick / step / batch granularity only — never per token,
  per slot or per layer, and never inside a compiled program;
- a ``jax.profiler.TraceAnnotation`` entered and left with the span, so
  while a ``jax.profiler`` session is open the same span lies in the
  ``/host:CPU`` plane of the ``.xplane.pb``, on the device trace's
  clock. With no session open that costs one ``is_enabled()`` check.

**Marks.** ``Span.mark(name)`` on an OPEN span stores
``ids[name + "_ns"]``, the nanoseconds since the span began: a point
inside a span without a child span (a child with a new name would take
its time out of the parent's self time, which metrics sum by name). An
id or a mark set after ``__enter__`` is in the ring and NOT in the
``TraceAnnotation`` twin, which took its ids when it opened: marks are
ring-only.

**Stalls.** :class:`StallWatch` brackets a recurring blocking span (the
serving engine's two device syncs): it keeps the last durations a kind
and, when one lasts far beyond their median, writes on that span what
the process and the thread did meanwhile (``getrusage``, this thread's
``schedstat``).

Readers of the ring: :func:`since` (spans wholly inside an interval),
:func:`self_times` (duration minus the part the children cover, by
name), :func:`chrome_events` / :func:`export_chrome_trace` (Perfetto),
``paddle_tpu.profiler`` (``RecordEvent`` is a ring span; a ``Profiler``
exports the spans of its recording intervals) and the benchmark's
per-layer metric readers (``benchmark/layer_metrics``: seven over the
seconds under the profiler, ``benchmark/span_metrics.py``; five over the
rest of the window, stamped with the profiler off,
``benchmark/window_spans.py``).

Clocks: ring stamps are ``perf_counter_ns`` (CLOCK_MONOTONIC); the
``.xplane.pb`` stamps its events in nanoseconds since the profiler
session began. The two tick at the same rate but do NOT share an
origin (the older claim here, that request spans "sit on the same
timeline" as a ``jax.profiler`` trace, was wrong): a ring span and its
twin in the xplane differ by one offset per trace — minus the
``perf_counter`` reading at the session's start. Measured on the chip's
host (PR 25; PERF.md section 5): over a traced interval that offset
wandered by under 8 us, and a twin was 2.3-2.7 us longer than its ring
span (at most 10.3 us), because the annotation opens before the ring's
first stamp and closes after its second. Lay the two accounts side by
side by that offset, not by equal stamps.

**Request traces.** Every request the Server admits gets one
:class:`RequestTrace`: a span for its queue wait, one span per prefill
dispatch (whole-prompt on the dense engine, one per chunk on the paged
engine), a decode-residency span covering its time live in the slot
pool, harvest instants, and EXACTLY ONE terminal marker —
``terminal:completed`` or ``terminal:<RequestFailure reason>`` (the
chaos tests pin the exactly-one invariant: a request whose trace never
terminates, or terminates twice, is a serving-loop bug). They allocate
per request, so they stay opt-in (``PT_TRACE_REQUESTS=1`` or
``ObservabilityConfig(trace_requests=True)``); disabled, every method
returns on a single bool check and the Server leaves ``engine.tracer``
as None so the engine hot paths pay one ``is None`` test. Request spans
are stamped in ``perf_counter_ns()/1e3`` microseconds, the ring's clock
and chrome-trace's unit.

Row layout of :func:`export_chrome_trace`: ``tid 0`` is the server row
(the ring's ``serving.*`` spans: ticks and their phases); other ring
spans render on their own thread's row; each request renders on a row
named ``request <id>``.
"""
from __future__ import annotations

import itertools
import json
import os
import resource
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional

from jax.profiler import TraceAnnotation

from ..utils.flags import env_bool

__all__ = ["Span", "span", "begin", "end", "since", "self_times", "clear",
           "chrome_events", "named_program", "RequestTracer",
           "RequestTrace", "export_chrome_trace", "now_us", "RING_SIZE",
           "StallWatch"]

_SERVER_TID = 0
_SERVER_PREFIX = "serving."

RING_SIZE = 65536
_RING: deque = deque(maxlen=RING_SIZE)     # finished spans, oldest first
_STACKS = threading.local()                # .open: this thread's open spans
_IDS = itertools.count(1)
_now_ns = time.perf_counter_ns
_thread_id = threading.get_ident
_session_open = TraceAnnotation.is_enabled  # a jax.profiler session is open


def now_us() -> float:
    """Microseconds on the ring's clock (perf_counter)."""
    return _now_ns() / 1000.0


class Span:
    """One span, and — once it has ended — its own record in the ring.

    ``name``; ``start`` and ``dur`` in ``perf_counter_ns`` nanoseconds
    (``dur`` is None while the span is open); ``id`` (process-unique);
    ``parent``, the id of the span open on the same thread when this one
    began (None at the top); ``tid``, the thread; ``ids``, the keywords
    it was given and what was stored since (:meth:`mark`). Use as
    ``with span("serving.tick", tick=n):`` or as ``s = begin(...)`` ...
    ``end(s)``; spans of one thread must end in the reverse of the order
    they began."""

    __slots__ = ("name", "ids", "id", "parent", "tid", "start", "dur",
                 "_ta")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self.dur = None

    def __enter__(self):
        try:
            stack = _STACKS.open
        except AttributeError:
            stack = _STACKS.open = []
        self.parent = stack[-1].id if stack else None
        self.id = next(_IDS)
        self.tid = _thread_id()
        stack.append(self)
        if _session_open():
            self._ta = TraceAnnotation(self.name, **self.ids)
            self._ta.__enter__()
        else:
            self._ta = None
        self.start = _now_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur = _now_ns() - self.start
        if self._ta is not None:
            self._ta.__exit__(None, None, None)
            self._ta = None
        stack = _STACKS.open
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:                 # ended out of order: keep the
            stack.remove(self)              # other spans' parents intact
        _RING.append(self)
        return False

    def mark(self, name: str) -> int:
        """Stamp a point inside this OPEN span: ``ids[name + "_ns"]`` is
        the nanoseconds since the span began. Ring-only (the twin in a
        profiler session took its ids at ``__enter__``). Returns the
        stamp itself, on the ring's clock."""
        now = _now_ns()
        self.ids[name + "_ns"] = now - self.start
        return now

    def __repr__(self):
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"start={self.start}, dur={self.dur}, ids={self.ids})")


span = Span


def begin(name: str, **ids) -> Span:
    """Open a span that :func:`end` closes — for a pair of calls that
    cannot share a ``with`` block (``RecordEvent.begin`` / ``.end``)."""
    return Span(name, **ids).__enter__()


def end(s: Span):
    s.__exit__(None, None, None)


def since(t0_s: float, t1_s: Optional[float] = None) -> List[Span]:
    """The ring's spans that lie WHOLLY inside ``[t0_s, t1_s]`` (seconds
    on ``time.perf_counter``; no upper end when ``t1_s`` is None), in
    the order they ended."""
    lo = t0_s * 1e9
    hi = float("inf") if t1_s is None else t1_s * 1e9
    return [r for r in list(_RING)
            if r.start >= lo and r.start + r.dur <= hi]


def self_times(records: Iterable[Span]) -> Dict[str, List[int]]:
    """``{name: [count, self_ns]}`` over ``records``: a span's self time
    is its duration minus the durations of its children among the
    records (children run on the parent's thread, one after another, so
    they never overlap). Self times of a tree add up to its root."""
    records = list(records)
    own = {r.id: r.dur for r in records}
    for r in records:
        if r.parent in own:
            own[r.parent] -= r.dur
    out: Dict[str, List[int]] = {}
    for r in records:
        acc = out.setdefault(r.name, [0, 0])
        acc[0] += 1
        acc[1] += own[r.id]
    return out


def clear():
    """Empty the ring (tests; a long-lived process never needs to)."""
    _RING.clear()


# A stall is a span that lasts more than STALL_FACTOR times the median of
# the last STALL_HISTORY of its name AND STALL_EXCESS_NS more than it; no
# verdict before STALL_MIN_HISTORY durations of the name are known.
STALL_HISTORY = 32
STALL_MIN_HISTORY = 8
STALL_FACTOR = 3
STALL_EXCESS_NS = 100_000_000
_SCHEDSTAT = "/proc/thread-self/schedstat"
_getrusage = resource.getrusage
_RUSAGE_SELF = resource.RUSAGE_SELF


class StallWatch:
    """Says of a recurring blocking span (a device sync) whether this one
    stalled, and what the host did meanwhile.

    ``began = watch.begin()`` before the span opens, ``watch.end(sp,
    began)`` after it closed. ``begin`` reads the process's ``getrusage``
    and this thread's ``/proc/thread-self/schedstat`` (a descriptor kept
    open, one a thread at a time; skipped where the kernel keeps no such
    file); ``end`` compares ``sp.dur`` with the median of the last
    durations of the spans of its name and, on a stall only, reads both
    again and stores on the span (ring-only) and returns::

        stall=1        over_ns       dur - median
        nivcsw nvcsw   context switches of the PROCESS, forced / voluntary
        majflt         its major page faults
        cpu_ms         its user + system time: near 0 says every thread
                       slept (driver, runtime, link); near dur x threads
                       says the client's own threads were busy
        run_delay_ms   this thread runnable and not run: the host's cores
                       were taken away

    None where the span is no stall."""

    def __init__(self):
        self._durs = {}                # span name -> its last durations
        self._sched = (None, None)     # (thread, its schedstat's descriptor)

    def _schedstat(self) -> Optional[bytes]:
        """b"<on-cpu ns> <runnable, waiting ns> <slices>" of this thread."""
        tid, fd = self._sched
        if tid != _thread_id():
            self.close()
            try:
                fd = os.open(_SCHEDSTAT, os.O_RDONLY)
            except OSError:
                fd = None
            self._sched = (_thread_id(), fd)
        return None if fd is None else os.pread(fd, 64, 0)

    def begin(self):
        return _getrusage(_RUSAGE_SELF), self._schedstat()

    def end(self, sp: Span, began) -> Optional[dict]:
        durs = self._durs.get(sp.name)
        if durs is None:
            durs = self._durs[sp.name] = deque(maxlen=STALL_HISTORY)
        dur, fields = sp.dur, None
        if len(durs) >= STALL_MIN_HISTORY:
            median = sorted(durs)[len(durs) // 2]
            over = dur - median
            if dur > STALL_FACTOR * median and over > STALL_EXCESS_NS:
                (ru0, sched0), (ru1, sched1) = began, self.begin()
                fields = {
                    "stall": 1, "over_ns": over,
                    "nivcsw": ru1.ru_nivcsw - ru0.ru_nivcsw,
                    "nvcsw": ru1.ru_nvcsw - ru0.ru_nvcsw,
                    "majflt": ru1.ru_majflt - ru0.ru_majflt,
                    "cpu_ms": round((ru1.ru_utime + ru1.ru_stime
                                     - ru0.ru_utime - ru0.ru_stime) * 1e3, 3)}
                if sched0 is not None and sched1 is not None:
                    fields["run_delay_ms"] = round(
                        (int(sched1.split()[1]) - int(sched0.split()[1]))
                        / 1e6, 3)
                sp.ids.update(fields)
        durs.append(dur)
        return fields

    def close(self):
        fd = self._sched[1]
        if fd is not None:
            os.close(fd)
        self._sched = (None, None)

    __del__ = close


def named_program(fn, program: str):
    """``fn``, renamed so that ``jax.jit(fn)`` compiles a module called
    ``program`` (jax names it ``jit_<function name>``): the device
    trace's "XLA Modules" line then shows a name chosen on purpose, which
    renaming the Python function cannot move."""
    fn.__name__ = fn.__qualname__ = program.removeprefix("jit_")
    return fn


def chrome_events(records: Iterable[Span], pid: Optional[int] = None
                  ) -> List[dict]:
    """Ring spans as chrome-trace "X" events in microseconds: the
    ``serving.*`` spans on the server row (tid 0), every other span on
    the row of the thread that recorded it."""
    pid = os.getpid() if pid is None else pid
    return [{"name": r.name, "ph": "X", "pid": pid,
             "tid": _SERVER_TID if r.name.startswith(_SERVER_PREFIX)
             else r.tid,
             "ts": r.start / 1000.0, "dur": r.dur / 1000.0,
             "args": dict(r.ids)} for r in records]


@dataclass
class RequestTrace:
    """One request's span list. ``spans`` hold completed ("X") spans
    and instants (dur None); ``open`` maps span name -> (begin ts,
    args) for spans still running; ``terminals`` records every terminal
    marker seen (the invariant is len == 1 once the request leaves the
    server)."""
    request_id: int
    t_start: float = 0.0
    spans: List[dict] = field(default_factory=list)
    open: Dict[str, tuple] = field(default_factory=dict)
    terminals: List[str] = field(default_factory=list)

    def span_names(self) -> List[str]:
        return [s["name"] for s in self.spans]


class RequestTracer:
    """Collects the request traces of one Server (the server's own row
    — ticks and their phases — is in the module's span ring).

    Armed, retention is BOUNDED (a long-lived server must not grow
    without limit): past ``max_requests`` retained traces, each
    terminal evicts the oldest already-terminated trace — still-open
    traces are never evicted, so an in-flight request always reaches
    its terminal span."""

    def __init__(self, enabled: Optional[bool] = None,
                 max_requests: int = 4096):
        self.enabled = env_bool("PT_TRACE_REQUESTS") \
            if enabled is None else bool(enabled)
        self.max_requests = max_requests
        self.traces: Dict[int, RequestTrace] = {}
        self._lock = threading.Lock()

    # -- request lifecycle -------------------------------------------------
    def start(self, rid: int):
        """Request submitted: open its trace and its queue_wait span."""
        if not self.enabled:
            return
        t = now_us()
        with self._lock:
            self.traces[rid] = RequestTrace(request_id=rid, t_start=t)
        self.span_begin(rid, "queue_wait")

    def _trace(self, rid) -> Optional[RequestTrace]:
        return self.traces.get(rid)

    def span_begin(self, rid: int, name: str, **args):
        if not self.enabled:
            return
        tr = self._trace(rid)
        if tr is not None:
            tr.open[name] = (now_us(), args)

    def span_end(self, rid: int, name: str, **args):
        """Close an open span; silently a no-op when it never opened
        (e.g. a cancelled request that never reached decode)."""
        if not self.enabled:
            return
        tr = self._trace(rid)
        if tr is None or name not in tr.open:
            return
        t0, a0 = tr.open.pop(name)
        tr.spans.append({"name": name, "ts": t0,
                         "dur": now_us() - t0, "args": {**a0, **args}})

    def span_at(self, rid: int, name: str, ts_begin_us: float, **args):
        """Append a completed span measured by the caller (begin stamp
        taken with :func:`now_us` before a dispatch) — the engine-side
        form that costs nothing when the tracer is absent."""
        if not self.enabled:
            return
        tr = self._trace(rid)
        if tr is not None:
            tr.spans.append({"name": name, "ts": ts_begin_us,
                             "dur": now_us() - ts_begin_us, "args": args})

    def instant(self, rid: int, name: str, **args):
        if not self.enabled:
            return
        tr = self._trace(rid)
        if tr is not None:
            tr.spans.append({"name": name, "ts": now_us(), "dur": None,
                             "args": args})

    def terminal(self, rid: int, state: str, **args):
        """Record the request's terminal state and close every span
        still open at that moment. Deliberately NOT idempotent: a
        double terminal is recorded so the exactly-one test catches the
        server bug instead of masking it."""
        if not self.enabled:
            return
        tr = self._trace(rid)
        if tr is None:
            return
        t = now_us()
        for name, (t0, a0) in list(tr.open.items()):
            tr.spans.append({"name": name, "ts": t0, "dur": t - t0,
                             "args": a0})
        tr.open.clear()
        tr.terminals.append(state)
        tr.spans.append({"name": f"terminal:{state}", "ts": t,
                         "dur": None, "args": args})
        if len(self.traces) > self.max_requests:
            self._evict_terminated()

    def _evict_terminated(self):
        """Drop oldest TERMINATED traces until back under the cap
        (insertion order == submit order; open traces are skipped)."""
        with self._lock:
            excess = len(self.traces) - self.max_requests
            for rid in [r for r, tr in self.traces.items()
                        if tr.terminals][:excess]:
                del self.traces[rid]

    # -- introspection -----------------------------------------------------
    def terminal_states(self) -> Dict[int, List[str]]:
        return {rid: list(tr.terminals)
                for rid, tr in self.traces.items()}

    def clear(self):
        with self._lock:
            self.traces.clear()

    # -- chrome-trace export -----------------------------------------------
    def chrome_events(self, pid: Optional[int] = None) -> List[dict]:
        """The request rows as chrome-trace events (metadata + X spans
        + instants), ready to merge with the ring's spans."""
        pid = os.getpid() if pid is None else pid
        ev: List[dict] = []

        def emit(tid, rec):
            base = {"name": rec["name"], "pid": pid, "tid": tid,
                    "ts": rec["ts"], "args": rec["args"]}
            if rec["dur"] is None:
                ev.append({**base, "ph": "i", "s": "t"})
            else:
                ev.append({**base, "ph": "X", "dur": rec["dur"]})

        for rid, tr in sorted(self.traces.items()):
            tid = rid + 1                 # tid 0 is the server row
            ev.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": f"request {rid}"}})
            for rec in tr.spans:
                emit(tid, rec)
            # still-open spans (export mid-stream): close at export time
            t = now_us()
            for name, (t0, a0) in tr.open.items():
                emit(tid, {"name": name, "ts": t0, "dur": t - t0,
                           "args": {**a0, "open_at_export": True}})
        return ev


def export_chrome_trace(path: str, tracer: Optional[RequestTracer] = None,
                        since_s: float = 0.0, extra_events=()) -> str:
    """Write ONE Perfetto-loadable chrome-trace JSON: the ring's spans
    that began after ``since_s`` (``time.perf_counter`` seconds; the
    whole ring by default) — the server row and every other host span,
    ``RecordEvent`` sites included — merged with the request rows of
    ``tracer`` and any extra pre-built events, all on the perf_counter
    clock. Parent directories are created. Returns ``path``."""
    pid = os.getpid()
    events: List[dict] = [
        {"ph": "M", "name": "thread_name", "pid": pid,
         "tid": _SERVER_TID, "args": {"name": "server"}}]
    events.extend(chrome_events(since(since_s), pid))
    if tracer is not None:
        events.extend(tracer.chrome_events(pid))
    events.extend(extra_events)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return path
