"""Observability: metrics registry, one host-span recorder, request
tracing, crash flight recorder. All host-side; nothing ever enters a
compiled program.

- :mod:`.metrics` — process-global Counter/Gauge/Histogram registry
  with labels; lock-free no-op when disabled (``PT_METRICS=1`` /
  ``metrics.enable()``); JSON (``dump()``) and Prometheus-text
  (``render_prometheus()``) exposition. Instrumented across the stack:
  Server tick/queue/shed/deadline, engine decode/compile, BlockManager
  pool/prefix-hit, fault fires, resilience retries/breaker, collectives
  bytes + int8 error bound, pass rewrite counts.
- :mod:`.tracing` — THE host-span recorder of the program:
  ``span(name, **ids)`` writes each span to a bounded in-memory ring
  (always on, no switch) and into ``jax.profiler``'s own trace while a
  session is open. The serving tick and its phases, the DataLoader's
  wait / unpickle / collate, ``TrainStep``'s dispatch and every
  ``profiler.RecordEvent`` site are spans of it; the chrome-trace
  export, ``paddle_tpu.profiler`` and the benchmark's per-layer metric
  readers all read that one ring. A point inside a span is a mark on it
  (``Span.mark``: ``<name>_ns`` among its ids, ring-only), not a child
  span; the serving engine's two device syncs are bracketed by a
  ``StallWatch``, which writes on a sync that lasted far beyond its
  kind's median what the process and the thread did meanwhile (the
  Server's flight event ``sync_stall``). The same module holds the opt-in
  per-request lifecycle traces (``PT_TRACE_REQUESTS=1``): queue-wait,
  prefill (chunk) spans, decode residency, harvest, exactly one
  terminal state per request, exported on the ring's clock so one
  Perfetto view shows ticks, host spans and request rows aligned.
- :mod:`.flight` — a bounded ring of recent structured events
  (``PT_FLIGHT_RECORDER_SIZE``) that auto-dumps on circuit-open,
  dumps + rides along with ``Server.snapshot()``, and restores with it.

``ObservabilityConfig`` is the per-Server knob bundle; None fields
defer to the env.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from . import metrics                      # noqa: F401
from .flight import FlightRecorder         # noqa: F401
from .tracing import (RequestTrace, RequestTracer,  # noqa: F401
                      export_chrome_trace)

__all__ = ["metrics", "FlightRecorder", "RequestTracer", "RequestTrace",
           "export_chrome_trace", "ObservabilityConfig"]


@dataclass
class ObservabilityConfig:
    """Per-Server observability knobs. ``None`` = read the env knob
    (``PT_TRACE_REQUESTS``, ``PT_FLIGHT_RECORDER_SIZE``); the global
    metrics switch lives on :mod:`.metrics` (``PT_METRICS`` /
    ``metrics.enable()``) because the registry is process-wide, not
    per-Server."""
    trace_requests: Optional[bool] = None
    flight_size: Optional[int] = None
    flight_dump_dir: Optional[str] = None
