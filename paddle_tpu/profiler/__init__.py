"""Profiler (reference: python/paddle/profiler/,
paddle/fluid/platform/profiler/ RecordEvent/CUPTI tracer — verify).

TPU-native design: device tracing delegates to ``jax.profiler``
(XProf/TensorBoard, perfetto). A host span (``RecordEvent``) is a span of
the program's one recorder (``observability/tracing.py``): it lands in
the always-on ring and, while a ``jax.profiler`` session is open, in the
``.xplane.pb`` beside the device's operations. A ``Profiler`` keeps no
spans of its own: it remembers the intervals in which its scheduler said
RECORD and exports the ring's spans that lie inside them."""
from __future__ import annotations

import json
import os
import time
from enum import Enum
from typing import Callable, Iterable, Optional

from ..observability import tracing as _tracing

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "SummaryView"]


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1      # parity alias — maps to the TPU device tracer
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


class RecordEvent:
    """Host span (reference: paddle.profiler.RecordEvent / C++ RecordEvent
    — verify). Usable as context manager or begin()/end(). Always
    recorded (the ring is bounded and has no switch); a ``Profiler``
    decides only which spans its export shows."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        self._span = _tracing.begin(self.name)

    def end(self):
        if self._span is not None:
            _tracing.end(self._span)
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0):
    total = closed + ready + record

    def scheduler(step: int):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}.json")
        with open(path, "w") as f:
            json.dump({"traceEvents": prof._drain_events()}, f)
        prof._last_export = path
    return handler


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


class Profiler:
    def __init__(self, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready: Optional[Callable] = None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        self.targets = list(targets or [ProfilerTarget.CPU,
                                        ProfilerTarget.TPU])
        if isinstance(scheduler, tuple):
            start, end = scheduler
            scheduler = make_scheduler(closed=start, ready=0,
                                       record=end - start, repeat=1)
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._jax_trace_dir = None
        self._jax_active = False
        self._last_export = None
        self._intervals: list = []      # [t0_s, t1_s | None] while RECORD

    # -- device tracer ------------------------------------------------------
    def _start_device_trace(self):
        if self.timer_only or self._jax_active:
            return
        import tempfile
        import jax
        want_device = any(t in (ProfilerTarget.GPU, ProfilerTarget.TPU)
                          for t in self.targets)
        if want_device:
            self._jax_trace_dir = tempfile.mkdtemp(prefix="pdtpu_prof_")
            try:
                jax.profiler.start_trace(self._jax_trace_dir)
                self._jax_active = True
            except Exception:
                self._jax_active = False

    def _stop_device_trace(self):
        if self._jax_active:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_active = False

    def _drain_events(self):
        """The ring's spans that lie inside this profiler's recording
        intervals, as chrome-trace events; each span is handed out once
        (an interval still open restarts at now)."""
        now = time.perf_counter()
        spans = [r for t0, t1 in self._intervals
                 for r in _tracing.since(t0, now if t1 is None else t1)]
        self._intervals = [[now, None] for _, t1 in self._intervals
                           if t1 is None]
        return _tracing.chrome_events(spans)

    # -- lifecycle ----------------------------------------------------------
    @staticmethod
    def _recording(state) -> bool:
        return state in (ProfilerState.RECORD,
                         ProfilerState.RECORD_AND_RETURN)

    def _arm_host_ring(self, on: bool):
        """Host spans count for this profiler iff the scheduler state
        says so: ``start()`` must not record through CLOSED warmup
        steps, and a CLOSED->RECORD transition in ``step()`` must open
        an interval (both directions are regression-pinned in
        tests/test_observability.py)."""
        is_open = bool(self._intervals) and self._intervals[-1][1] is None
        if on and not is_open:
            self._intervals.append([time.perf_counter(), None])
        elif not on and is_open:
            self._intervals[-1][1] = time.perf_counter()

    def start(self):
        self._state = self.scheduler(self._step) if self.scheduler else \
            ProfilerState.RECORD
        self._arm_host_ring(self._recording(self._state))
        if self._recording(self._state):
            self._start_device_trace()

    def stop(self):
        self._stop_device_trace()
        self._arm_host_ring(False)
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        self._step += 1
        if self.scheduler:
            new_state = self.scheduler(self._step)
            self._arm_host_ring(self._recording(new_state))
            if self._recording(new_state) and not self._jax_active:
                self._start_device_trace()
            elif new_state == ProfilerState.CLOSED and self._jax_active:
                self._stop_device_trace()
            if self._state == ProfilerState.RECORD_AND_RETURN and \
                    self.on_trace_ready:
                self.on_trace_ready(self)
            self._state = new_state

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def export(self, path, format="json"):
        # missing parent directories are created, not a crash — bench
        # children and trace handlers export into per-run directories
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": self._drain_events()}, f)
        self._last_export = path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None, print_table=True):
        """Aggregate the drained host spans. Returns ``(table, agg)``
        — the rendered table plus the per-name
        ``{"calls", "total_us"}`` dict — and only prints when
        ``print_table`` (headless/bench callers want the numbers, not
        stdout noise)."""
        ev = self._drain_events()
        agg: dict = {}
        for e in ev:
            if e.get("ph") != "X":
                continue
            a = agg.setdefault(e["name"], {"calls": 0, "total_us": 0.0})
            a["calls"] += 1
            a["total_us"] += e.get("dur", 0.0)
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"]
        for name, a in sorted(agg.items(), key=lambda kv: -kv[1]["total_us"]):
            lines.append(f"{name:<40}{a['calls']:>8}"
                         f"{a['total_us'] / 1000:>12.3f}"
                         f"{a['total_us'] / 1000 / a['calls']:>12.3f}")
        table = "\n".join(lines)
        if print_table:
            print(table)
        return table, agg
