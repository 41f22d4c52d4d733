"""Per-layer metrics read from the program's span ring over the window's
UNTRACED part.

The ring (``paddle_tpu/observability/tracing.py``) is always on, so the whole
45 s window is in it, stamped on ``time.perf_counter_ns`` with the profiler
off: the conditions the end-to-end metrics are measured under. The seven
readers of ``span_metrics.py`` take the 4 s under the profiler, 21-25 ticks
that hold 0.56-1.05 admissions a tick by chance; the five here take the rest,
``[end - elapsed_s, _trace_t0]`` with ``end = _trace_t0 + trace_window_s``
(the trace stops at the window's end), 240-340 ticks, and report a cost per
EVENT (a tick, an admission, a chunk), which does not move with how many
events a span of seconds happened to hold.

What they read, all written by the program since PR 35 on spans it already
had: ``starved_ns`` on ``serving.prefill_chunk`` / ``serving.decode_block``
(the chip left with an empty queue, from the sync that said so to the return
of the next enqueue), ``first_ns`` / ``stall`` on ``serving.decode_sync``,
``reserved_ns`` / ``keyed_ns`` on ``serving.admit``, ``arm_ns`` on
``serving.decode_block``. ``None``, never an error, outside a traced run,
where the cell's kind sets no ``elapsed_s``, and on a program whose spans
have no marks: the metric is then left out of the line.

``device_starved_ms`` also logs, once a traced run, the account of both
parts of the window a tick (``account``), and on the 4 s under the profiler,
the one interval where both accounts exist, the identity they must satisfy:
starved time <= ``trace_window_s - busy_s``. Starvation is measured from
when the host LEARNS the device is drained to when an enqueue RETURNS, so it
can never exceed the device's idle time; the difference is the latency no
host code sees.
"""
from __future__ import annotations

TICK = "serving.tick"
ENQUEUES = ("serving.prefill_chunk", "serving.decode_block")
SCHED = ("serving.expire", "serving.schedule", "serving.admit")


def recorder():
    """The program's span recorder, or None on a program without marks."""
    try:
        from paddle_tpu.observability import tracing
    except ImportError:
        return None
    return tracing if hasattr(tracing, "since") \
        and hasattr(getattr(tracing, "Span", None), "mark") else None


def part(ctx, traced: bool):
    """``(lo, hi)``, seconds on ``perf_counter``: the window's untraced
    part, or the seconds under the profiler; None outside a traced run and
    where the cell's kind sets no ``elapsed_s``."""
    elapsed = (getattr(ctx, "window", None) or {}).get("elapsed_s")
    if not ctx.trace or ctx._trace_t0 is None or not ctx.trace_window_s \
            or not elapsed:
        return None
    end = ctx._trace_t0 + ctx.trace_window_s
    return (ctx._trace_t0, end) if traced else (end - elapsed, ctx._trace_t0)


def by_name(ctx, traced: bool = False):
    """``{name: [spans]}`` of the ``serving.tick`` spans wholly inside the
    window's untraced part (``traced``: inside the seconds under the
    profiler) and of their children, in the order they ended; None where
    there is nothing to read or no tick."""
    tracing, bounds = recorder(), part(ctx, traced)
    if tracing is None or bounds is None:
        return None
    inside = tracing.since(*bounds)
    ticks = {sp.id for sp in inside if sp.name == TICK}
    out = {}
    for sp in inside:
        if sp.id in ticks or sp.parent in ticks:
            out.setdefault(sp.name, []).append(sp)
    return out or None


def _say(ctx, msg: str):
    getattr(ctx, "say", print)(msg)


def _carrying(spans, key: str):
    return [sp for sp in spans if key in sp.ids]


def _enqueues_starved(by):
    """The enqueue spans that ended a starved interval."""
    return _carrying(by.get(ENQUEUES[0], []) + by.get(ENQUEUES[1], []),
                     "starved_ns")


def _between_ns(ticks) -> int:
    """Nanoseconds between the ticks (sorted by start), summed."""
    return sum(b.start - (a.start + a.dur) for a, b in zip(ticks, ticks[1:]))


def device_starved_ms(ctx):
    by = by_name(ctx)
    if by is None:
        return None
    carried = _enqueues_starved(by)
    if not carried:
        return None
    for traced in (False, True):
        account(ctx, traced)
    return sum(sp.ids["starved_ns"] for sp in carried) / len(by[TICK]) / 1e6


def sync_tail_ms(ctx):
    by = by_name(ctx)
    syncs = _carrying((by or {}).get("serving.decode_sync", []), "first_ns")
    if not syncs:
        return None
    clean = [sp for sp in syncs if not sp.ids.get("stall")]
    stalled = [sp for sp in syncs if sp.ids.get("stall")]
    _say(ctx, f"sync tail: {len(clean)} decode syncs of "
         f"{clean[0].ids.get('fetches') if clean else '-'} fetches, "
         f"{len(stalled)} stalled and left out"
         + "".join(f"; stall {sp.dur / 1e6:.1f} ms {sp.ids}"
                   for sp in stalled))
    return sum(sp.dur - sp.ids["first_ns"] for sp in clean) \
        / len(by[TICK]) / 1e6


def admit_ms_per_request(ctx):
    by = by_name(ctx)
    # a refused admission returns after ``_reserve`` and says
    # ``fresh_blocks`` 0 as a whole prefix hit does: ``keyed_ns`` tells
    admits = _carrying((by or {}).get("serving.admit", []), "keyed_ns")
    if not admits:
        return None
    n = len(admits)
    reserved = sum(sp.ids["reserved_ns"] for sp in admits) / n / 1e6
    keyed = sum(sp.ids["keyed_ns"] for sp in admits) / n / 1e6
    whole = sum(sp.dur for sp in admits) / n / 1e6
    _say(ctx, f"admission: {n} admitted ({len(by['serving.admit']) - n} "
         f"refused), {whole:.3f} ms each = reserve {reserved:.3f} (lookup, "
         f"allocation, table row) + key {keyed - reserved:.3f} (PRNGKey + "
         f"split) + uploads and job {whole - keyed:.3f}; "
         f"{n / len(by[TICK]):.3f} a tick")
    return whole


def chunk_dispatch_ms_per_chunk(ctx):
    chunks = (by_name(ctx) or {}).get(ENQUEUES[0])
    if not chunks:
        return None
    return sum(sp.dur for sp in chunks) / len(chunks) / 1e6


def between_ticks_ms(ctx):
    by = by_name(ctx)
    ticks = sorted((by or {}).get(TICK, []), key=lambda sp: sp.start)
    if len(ticks) < 2:
        return None
    return _between_ns(ticks) / (len(ticks) - 1) / 1e6


def account(ctx, traced: bool):
    """Log, a tick, where the time the chip sat with an empty queue went.
    An interval runs from a sync to the return of the next enqueue, so the
    starved time of the ticks is, but for the two at the ends: the decode
    sync's tail after its first fetch + the rest of that tick (harvest,
    deliveries) + the time between the ticks + the next tick up to the return
    of its first enqueue (scheduling, admissions, the first dispatch) + the
    intervals that begin INSIDE a tick, at a prefill's end (arming, the next
    dispatch). What does not add up is said. An interval is counted from
    the part's start at the earliest: the first one under the profiler began
    before ``start_trace`` was called, and that call lasts tens of ms."""
    by = by_name(ctx, traced)
    if by is None:
        return
    ticks = sorted(by[TICK], key=lambda sp: sp.start)
    start = {t.id: t.start for t in ticks}
    ends = {t.id: t.start + t.dur for t in ticks}
    n, lo_ns = len(ticks), part(ctx, traced)[0] * 1e9
    ms = {k: 0.0 for k in ("starved", "tail", "rest", "between", "head",
                           "mid", "arm", "sched")}
    for sp in _enqueues_starved(by):
        returned = sp.start + sp.dur
        ns = min(sp.ids["starved_ns"], returned - lo_ns)
        ms["starved"] += ns
        if returned - ns < start[sp.parent]:
            ms["head"] += returned - start[sp.parent]
        else:
            ms["mid"] += ns
    for sp in _carrying(by.get("serving.decode_sync", []), "first_ns"):
        ms["tail"] += sp.dur - sp.ids["first_ns"]
        ms["rest"] += ends[sp.parent] - (sp.start + sp.dur)
    ms["between"] = _between_ns(ticks)
    ms["arm"] = sum(sp.ids.get("arm_ns", 0) for sp in by.get(ENQUEUES[1], []))
    ms["sched"] = sum(sp.dur for name in SCHED for sp in by.get(name, []))
    ms = {k: v / n / 1e6 for k, v in ms.items()}
    parts = ms["tail"] + ms["rest"] + ms["between"] + ms["head"] + ms["mid"]
    span_ns = ticks[-1].start + ticks[-1].dur - ticks[0].start
    msg = (f"starved account, {'traced' if traced else 'untraced'} part: "
           f"{n} ticks of {span_ns / n / 1e6:.2f} ms; a tick (ms): starved "
           f"{ms['starved']:.3f} = sync tail {ms['tail']:.3f} + rest of the "
           f"tick {ms['rest']:.3f} + between ticks {ms['between']:.3f} + "
           f"tick start to first enqueue {ms['head']:.3f} (of which "
           f"expire + schedule + admit {ms['sched']:.3f}) + after a "
           f"prefill's end {ms['mid']:.3f} (of which arm_ns "
           f"{ms['arm']:.3f}) + not accounted {ms['starved'] - parts:.3f}")
    busy = (getattr(ctx, "trace_summary", None) or {}).get("busy_s")
    if traced and busy is not None:
        idle = (ctx.trace_window_s - busy) / n * 1e3
        msg += (f"; device idle {idle:.3f} = starved + unseen latency "
                f"{idle - ms['starved']:.3f}; identity starved <= idle "
                f"{'holds' if ms['starved'] <= idle else 'BROKEN'}")
    _say(ctx, msg)
