"""Per-layer metrics read from the program's own span ring.

Since PR 25 the program records a span at every layer boundary of the two
measured paths (``paddle_tpu/observability/tracing.py``: ``serving.tick`` and
its phases, ``io.loader_*``, ``train.step_dispatch``) into a bounded
in-memory ring, stamped on ``time.perf_counter_ns`` — the clock
``Context.start_trace`` stamps ``_trace_t0`` on. A reader here takes the
ring's spans that lie WHOLLY inside the traced interval ``[_trace_t0,
_trace_t0 + trace_window_s]`` — the same seconds the device metrics cover, so
the host's account and the device's can be laid side by side — and reports
SELF time (a span's duration minus what its children cover) per tick or
step. ``None`` outside a traced run, and ``None`` (never an error) on a
program that has no such ring: the metric is then left out of the line.
"""
from __future__ import annotations


def recorder():
    """The program's span recorder, or None on a program from before it."""
    try:
        from paddle_tpu.observability import tracing
    except ImportError:
        return None
    return tracing if hasattr(tracing, "since") \
        and hasattr(tracing, "self_times") else None


def mean_self_ms(ctx, names, per: str):
    """Self time of the spans called one of ``names``, summed over the
    traced interval and divided by the number of ``per`` spans in it (ticks,
    steps), in milliseconds. None where there is nothing to read."""
    tracing = recorder()
    if tracing is None or not ctx.trace or ctx._trace_t0 is None \
            or not ctx.trace_window_s:
        return None
    own = tracing.self_times(tracing.since(
        ctx._trace_t0, ctx._trace_t0 + ctx.trace_window_s))
    n = own.get(per, (0, 0))[0]
    if not n:
        return None
    return sum(own[name][1] for name in names if name in own) / n / 1e6
