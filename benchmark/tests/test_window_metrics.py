"""The five per-layer metrics that read the program's span ring over the
window's UNTRACED part (``benchmark/window_spans.py`` and its readers under
``layer_metrics/``): which interval they take, what they leave out and
``None`` where there is nothing to read, on hand-made records; then a traced
CPU rehearsal of ``mistral7b-decode-sat`` (``run.py --rehearse``) and of
``mimo-v2-agent-decode`` (its kind has no ``--rehearse`` route:
``test_hybrid_moe.toy_context``) whose line carries all five names beside
the old ones.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``
(tier-1 collects only ``tests/``).
"""
import json
import os
import subprocess
import sys
from collections import deque
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import common                       # noqa: E402
from paddle_tpu.observability import tracing       # noqa: E402

NEW = ("device_starved_ms.serve", "sync_tail_ms.serve",
       "admit_ms_per_request.serve", "chunk_dispatch_ms_per_chunk.serve",
       "between_ticks_ms.serve")
OLD = ("tick_sched_ms.serve", "tick_dispatch_ms.serve",
       "tick_device_wait_ms.serve", "tick_harvest_ms.serve")


def read(metric, ctx):
    return common.load_module("layer_metrics", metric + ".py").read(ctx)


def rec(name, start_ms, dur_ms, id, parent=None, **ids):
    """A finished span as the ring holds it, stamped by hand (ms)."""
    s = tracing.Span(name, **ids)
    s.id, s.parent, s.tid = id, parent, 0
    s.start, s.dur = int(start_ms * 1e6), int(dur_ms * 1e6)
    return s


def ns(ms):
    return int(ms * 1e6)


def traced(elapsed_s=2.0, t0_s=3.0, window_s=1.0, **kw):
    """A window of ``elapsed_s`` whose last ``window_s`` ran under the
    profiler from ``t0_s``: the untraced part is [2000, 3000] ms."""
    said = []
    return SimpleNamespace(trace=True, _trace_t0=t0_s,
                           trace_window_s=window_s,
                           window={"elapsed_s": elapsed_s},
                           trace_summary={"busy_s": 0.8}, say=said.append,
                           said=said, **kw)


@pytest.fixture
def ring(monkeypatch):
    def fill(records):
        monkeypatch.setattr(tracing, "_RING", deque(records))
    return fill


def tick(t, id0, admitted=True, stall=False, marks=True):
    """One tick of 200 ms from ``t``, 50 ms after the one before: an
    admission of 3 ms (refused: 1 ms, no marks), a chunk of 2 ms whose
    enqueue ends a starved interval of 68.5 ms (begun at the first fetch of
    the sync before: 1.5 of its tail + 10 to that tick's end + 50 between
    the ticks + 7 of this one), a prefill sync, a decode block carrying 1.5
    ms of arming and a second interval of 2 ms, a decode sync of 150 ms
    whose first fetch returned after 148.5, a harvest."""
    p = id0
    m = (lambda **kw: kw) if marks else (lambda **kw: {})
    admit = rec("serving.admit", t + 1, 3, p + 1, p, rid=7, fresh_blocks=2,
                **m(reserved_ns=ns(1), keyed_ns=ns(2.25))) if admitted \
        else rec("serving.admit", t + 1, 1, p + 1, p, rid=7, fresh_blocks=0)
    sync = dict(m(first_ns=ns(148.5), fetches=4))
    if stall:
        sync.update(stall=1, over_ns=ns(900))
    return [
        admit,
        rec("serving.prefill_chunk", t + 5, 2, p + 2, p, rid=7, tokens=32,
            **m(starved_ns=ns(68.5))),
        rec("serving.prefill_sync", t + 7, 30, p + 3, p, rid=7),
        rec("serving.decode_block", t + 39, 1, p + 4, p, kv_pages_live=5,
            **m(arm_ns=ns(1.5), starved_ns=ns(2))),
        rec("serving.decode_sync", t + 40, 1050 if stall else 150, p + 5, p,
            **sync),
        rec("serving.harvest", t + (1091 if stall else 191), 4, p + 6, p),
        rec("serving.tick", t, 1100 if stall else 200, p, None, tick=id0),
    ]


def test_readers_take_the_untraced_part_of_the_window(ring):
    # untraced part [2000, 3000] ms: a tick before it, one whose span
    # straddles its start, three inside (250 ms apart: 50 ms between two),
    # one that straddles _trace_t0, one under the profiler
    ring(tick(1500, 100) + tick(1900, 200) + tick(2150, 300)
         + tick(2400, 400, admitted=False) + tick(2650, 500)
         + tick(2900, 600) + tick(3200, 700))
    ctx = traced()
    got = {m: read(m, ctx) for m in NEW}
    assert got == {
        # 68.5 + 2 ms in each of three ticks; a straddling tick's children
        # are out with it, those that lie inside the interval too
        "device_starved_ms.serve": pytest.approx(70.5),
        "sync_tail_ms.serve": pytest.approx(1.5),
        # the refused admission (1 ms, no marks) is out
        "admit_ms_per_request.serve": pytest.approx(3.0),
        "chunk_dispatch_ms_per_chunk.serve": pytest.approx(2.0),
        "between_ticks_ms.serve": pytest.approx(50.0),
    }
    said = " ".join(ctx.said)
    assert "2 admitted (1 refused)" in said
    assert "reserve 1.000" in said and "key 1.250" in said \
        and "uploads and job 0.750" in said
    assert "3 decode syncs of 4 fetches, 0 stalled" in said


def test_the_account_adds_up_and_states_the_identity(ring):
    from benchmark import window_spans
    ring(tick(2100, 300) + tick(2350, 400) + tick(2600, 500)
         + tick(3030, 700) + tick(3280, 800))
    ctx = traced()
    window_spans.account(ctx, traced=False)
    window_spans.account(ctx, traced=True)
    untraced, under = ctx.said
    # a tick: tail 1.5, rest of the tick 10, head 7 (tick start to the
    # chunk's return: the admission 3 of it), the in-tick interval 2 of
    # which arming 1.5; three ticks hold two of the three 50 ms between
    assert "3 ticks of 233.33 ms" in untraced
    assert "starved 70.500 = sync tail 1.500 + rest of the tick 10.000 + " \
        "between ticks 33.333 + tick start to first enqueue 7.000 (of " \
        "which expire + schedule + admit 3.000) + after a prefill's end " \
        "2.000 (of which arm_ns 1.500) + not accounted 16.667" in untraced
    assert "identity" not in untraced
    # under the profiler: 2 ticks, idle (1.0 - 0.8) s / 2 = 100 ms a tick;
    # the first tick's interval began 31.5 ms before the profiler's part
    # (start_trace lies there) and counts from its start: 37 of 68.5 ms
    assert "2 ticks" in under and "device idle 100.000" in under
    assert "starved 54.750" in under
    assert "unseen latency 45.250" in under and "holds" in under
    ctx.trace_summary = {"busy_s": 0.99}           # idle 5 ms < starved
    window_spans.account(ctx, traced=True)
    assert "BROKEN" in ctx.said[-1]


def test_a_stalled_sync_is_out_of_the_tail_and_in_the_log(ring):
    # untraced part [1000, 3000] ms: two ticks and one of 1,100 ms whose
    # sync stalled (its tail, 901.5 ms, would be 301 ms a tick)
    ring(tick(1010, 300) + tick(1260, 400, stall=True) + tick(2410, 500))
    ctx = traced(elapsed_s=3.0)
    assert read("sync_tail_ms.serve", ctx) == pytest.approx(2 * 1.5 / 3)
    assert "2 decode syncs of 4 fetches, 1 stalled and left out" \
        in ctx.said[-1]
    assert "over_ns" in ctx.said[-1]


@pytest.mark.parametrize("metric", NEW)
def test_nothing_to_read_is_none_never_an_error(metric, ring, monkeypatch):
    full = tick(2100, 300) + tick(2350, 400) + tick(2600, 500)
    ring(full)
    assert read(metric, traced()) is not None
    # untraced; no trace start; a kind that sets no elapsed_s; no window
    assert read(metric, SimpleNamespace(
        trace=False, _trace_t0=None, trace_window_s=None,
        window={"elapsed_s": 2.0})) is None
    assert read(metric, SimpleNamespace(
        trace=True, _trace_t0=None, trace_window_s=None,
        window={"elapsed_s": 2.0})) is None
    ctx = traced()
    ctx.window = {}
    assert read(metric, ctx) is None
    ctx.window = None
    assert read(metric, ctx) is None
    assert read(metric, traced(t0_s=50.0)) is None        # an empty interval
    # spans without the marks: the three readers of marks decline, the two
    # that need a span's stamps alone still answer
    ring(tick(2100, 300, marks=False) + tick(2350, 400, marks=False))
    got = read(metric, traced())
    if metric in NEW[:3]:
        assert got is None
    else:
        assert got == pytest.approx({NEW[3]: 2.0, NEW[4]: 50.0}[metric])
    # an empty ring; a program from before the marks (the parent commit)
    ring([])
    assert read(metric, traced()) is None
    ring(full)
    monkeypatch.delattr(tracing.Span, "mark")
    assert read(metric, traced()) is None


def _line_has_all(line):
    assert line["correct"] is True
    got = {n: line["metrics"][n] for n in NEW + OLD}       # all are there
    assert all(v["unit"] == "ms" and v["value"] >= 0 for v in got.values())
    # what the chip's queue lacked lies within the tick's period
    period = sum(got[n]["value"] for n in OLD) \
        + got["between_ticks_ms.serve"]["value"]
    assert 0 < got["device_starved_ms.serve"]["value"] < period
    assert got["sync_tail_ms.serve"]["value"] \
        <= got["tick_device_wait_ms.serve"]["value"] * 1.5 + 1.0


def test_rehearsed_traced_line_of_decode_sat_carries_all_five():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "mistral7b-decode-sat", "--seed", "2500000003",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    _line_has_all(json.loads(out.stdout.strip().splitlines()[-1]))
    assert "starved account, untraced part" in out.stdout
    assert "starved account, traced part" in out.stdout


def test_traced_toy_run_of_agent_decode_carries_all_five(monkeypatch):
    """The hybrid engine through its own kind, the readers called as
    ``run.py`` calls them."""
    from test_hybrid_moe import CELL, toy_context
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import fused
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
    bench, ctx, kind = toy_context(trace=1)
    result = kind.run(ctx)
    ctx.e2e, ctx.window = result["e2e"], result["window"]
    assert not result["failed"]
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    metrics = {}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            v = common.load_module("layer_metrics",
                                   m["name"] + ".py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    _line_has_all({"correct": True, "metrics": metrics})
