"""The seven per-layer metrics that read the program's span ring
(``benchmark/span_metrics.py`` and its readers under ``layer_metrics/``):
window selection, self time and ``None`` where there is nothing to read, on
hand-made records; then a ``--rehearse`` run of both cells whose traced line
carries all seven names.

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

SERVE = ("tick_sched_ms.serve", "tick_dispatch_ms.serve",
         "tick_device_wait_ms.serve", "tick_harvest_ms.serve")
TRAIN = ("loader_wait_ms_per_step", "loader_collate_ms_per_step",
         "train_dispatch_ms_per_step")


def read(metric, ctx):
    return common.load_module("layer_metrics", metric + ".py").read(ctx)


def rec(name, start_ms, dur_ms, id, parent=None, **ids):
    """A finished span as the ring holds it, stamped by hand (ms)."""
    s = tracing.Span(name, **ids)
    s.id, s.parent, s.tid = id, parent, 0
    s.start, s.dur = int(start_ms * 1e6), int(dur_ms * 1e6)
    return s


def traced(t0_s=1.0, window_s=2.0):
    return SimpleNamespace(trace=True, _trace_t0=t0_s,
                           trace_window_s=window_s)


@pytest.fixture
def ring(monkeypatch):
    def fill(records):
        monkeypatch.setattr(tracing, "_RING", deque(records))
    return fill


def tick(t_ms, id0, tick_no, sync_ms):
    """One tick of 20 + sync_ms: expire 1, schedule 2, admit 3, chunk 4 (+
    prefill_sync 1 after it), decode_block 2, decode_sync sync_ms, harvest 3
    (holding a 1 ms deliver), deliver 2; 2 ms are the tick's own."""
    t, p = t_ms, id0
    return [
        rec("serving.expire", t, 1, id0 + 1, p),
        rec("serving.schedule", t + 1, 2, id0 + 2, p),
        rec("serving.admit", t + 3, 3, id0 + 3, p, rid=7),
        rec("serving.prefill_chunk", t + 6, 4, id0 + 4, p, rid=7, tokens=32),
        rec("serving.prefill_sync", t + 10, 1, id0 + 5, p, rid=7),
        rec("serving.decode_block", t + 11, 2, id0 + 6, p),
        rec("serving.decode_sync", t + 13, sync_ms, id0 + 7, p),
        rec("serving.deliver", t + 14 + sync_ms, 1, id0 + 9, id0 + 8),
        rec("serving.harvest", t + 13 + sync_ms, 3, id0 + 8, p),
        rec("serving.deliver", t + 16 + sync_ms, 2, id0 + 10, p),
        rec("serving.tick", t, 20 + sync_ms, p, None, tick=tick_no),
    ]


def test_serve_readers_take_self_time_of_the_spans_inside_the_window(ring):
    # window [1000, 3000] ms: a tick before it, two inside, and a tick span
    # that ends after it (the harness opens and closes the trace between
    # ticks, so a tick is inside with all its children or not at all)
    before = tick(500, 100, 0, 100)
    inside = tick(1100, 200, 1, 400) + tick(1600, 300, 2, 600)
    straddles = [r for r in tick(2700, 400, 3, 500)
                 if r.name == "serving.tick"]
    ring(before + inside + straddles)
    ctx = traced()
    got = {m: read(m, ctx) for m in SERVE}
    assert got == {
        "tick_sched_ms.serve": 6.0,           # 1 + 2 + 3
        "tick_dispatch_ms.serve": 6.0,        # 4 + 2
        "tick_device_wait_ms.serve": 501.0,   # 1 + (400 + 600) / 2
        # harvest 3 - 1 (its deliver) + delivers 1 + 2 + the tick's own 2
        "tick_harvest_ms.serve": 7.0,
    }
    # by construction the four add up to the mean tick span of the window
    assert sum(got.values()) == (420 + 620) / 2


def test_a_child_without_its_tick_is_not_counted_per_tick(ring):
    # only children of a tick that began before the window: no tick inside
    ring([r for r in tick(900, 100, 0, 300) if r.name != "serving.tick"])
    assert all(read(m, traced()) is None for m in SERVE)


def test_train_readers(ring):
    steps = []
    for i, t in enumerate((1000.0, 1750.0, 2500.0)):      # three steps
        base = 10 * (i + 1)
        steps += [
            rec("io.loader_wait", t, 30 + i, base + 1, worker=i % 2),
            rec("io.loader_unpickle", t + 31 + i, 1, base + 2),
            rec("io.loader_collate", t + 33 + i, 5, base + 3, batch=2,
                bytes=65536),
            rec("train.step_dispatch", t + 40, 4, base + 4, step=i),
        ]
    early = [rec("io.loader_wait", 900, 35, 1),
             rec("train.step_dispatch", 940, 4, 2, step=99)]
    ring(early + steps)
    ctx = traced(1.0, 2.0)
    assert read("loader_wait_ms_per_step", ctx) == 31.0       # 30, 31, 32
    assert read("loader_collate_ms_per_step", ctx) == 6.0     # 1 + 5
    assert read("train_dispatch_ms_per_step", ctx) == 4.0
    # a shorter window holds the first two steps only
    ctx = traced(1.0, 1.2)
    assert read("loader_wait_ms_per_step", ctx) == 30.5


@pytest.mark.parametrize("metric", SERVE + TRAIN)
def test_nothing_to_read_is_none_never_an_error(metric, ring, monkeypatch):
    ring(tick(1100, 200, 1, 400) + [
        rec("io.loader_wait", 1200, 30, 1),
        rec("train.step_dispatch", 1300, 4, 2, step=0)])
    assert read(metric, traced()) is not None
    untraced = SimpleNamespace(trace=False, _trace_t0=None,
                               trace_window_s=None)
    assert read(metric, untraced) is None
    assert read(metric, SimpleNamespace(trace=True, _trace_t0=None,
                                        trace_window_s=None)) is None
    assert read(metric, traced(50.0, 1.0)) is None       # an empty window
    # a program from before the recorder (the parent commit) has no
    # ``since``: the reader returns None, the line leaves the metric out
    monkeypatch.delattr(tracing, "since")
    assert read(metric, traced()) is None


@pytest.mark.parametrize("cell,names", [
    ("mistral7b-decode-sat", SERVE), ("yi6b-train-4k", TRAIN)])
def test_rehearsed_traced_line_carries_the_span_metrics(cell, names):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2500000003", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = {n: line["metrics"][n] for n in names}          # all are there
    assert all(v["unit"] == "ms" and v["value"] >= 0 for v in got.values())
    if names is SERVE:
        # the four groups are the tick; tick_ms_p95.serve is the same
        # quantity's 95th percentile over the whole window (a loose guard:
        # the mean of the traced part cannot be far above it)
        total = sum(v["value"] for v in got.values())
        assert 0 < total <= 1.5 * line["metrics"]["tick_ms_p95.serve"]["value"]
    else:
        waited = got["loader_wait_ms_per_step"]["value"] \
            + got["loader_collate_ms_per_step"]["value"]
        assert waited <= 1.5 * line["metrics"]["data_wait_ms_per_step"]["value"] \
            + 1.0
