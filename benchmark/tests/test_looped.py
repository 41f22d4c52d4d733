"""The ``serve_looped`` kind end to end on the CPU at toy sizes, and the
arithmetic its metrics divide by.

``run.py --rehearse`` reads ``toy[cell["kind"]]`` from ``rehearse.json``,
which a later PR does not edit, so it cannot rehearse a new kind: this test
builds the ``Context`` itself from ``ouro_toy.json``. Run by hand:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_looped.py -q``
(under a minute; tier-1 collects only ``tests/``, where
``tests/test_benchmark_looped.py`` holds the quick arithmetic).
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import common                              # noqa: E402
from benchmark import run as harness                      # noqa: E402

CELL = "ouro-reason-decode"


def toy_context(trace: int, seconds: float = 3.0):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    toy = common.load_json("tests", "ouro_toy.json")
    cell = common.merge(common.load_json("workloads", CELL + ".json"),
                        toy["cell"])
    config = common.merge(
        common.load_json("configs", entry["config"] + ".json"),
        toy["config"])
    args = argparse.Namespace(seed=3000000019, seconds=seconds, trace=trace,
                              rehearse=True)
    ctx = harness.Context(args, entry, cell, config, None,
                          common.CompileMeter())
    ref = common.load_module("reference", cell["reference"] + ".py")
    ctx.reference = lambda model: ref.check(model, ctx)
    return bench, ctx, common.load_module("kinds", cell["kind"] + ".py")


def test_kind_runs_the_cell_on_the_cpu_at_toy_sizes(monkeypatch):
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import fused
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
    bench, ctx, kind = toy_context(trace=0)
    result = kind.run(ctx)
    failed = [k for k, ok in result["checks"].items() if not ok]
    # the toy mix is too small for the premise of the real one (four slots
    # of 8-40 tokens refill too often to stay full)
    assert all(k.startswith("the window's decode steps kept")
               for k in failed), failed
    for lead in ("(a) model logits", "(b) cached logits", "(c) the exit",
                 "timed path", "every decode step ran all 4 passes",
                 "the exit gate was computed"):
        assert any(k.startswith(lead) for k in result["checks"]), lead
    assert result["e2e"]["out_tokens_per_s"] > 0 and not result["failed"]
    ctx.e2e, ctx.window = result["e2e"], result["window"]
    w = result["window"]
    assert w["ut_steps_per_decode_step"] == 4
    assert 0 < w["ut_expected_exit_step"] < 3
    # every per-layer reader of the cell answers or declines, never raises;
    # device metrics have nothing to read in an untraced CPU run
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            v = common.load_module("layer_metrics",
                                   m["name"] + ".py").read(ctx)
            if m["source"] == "device_trace" or "hbm" in m["name"]:
                assert v is None, m["name"]
    assert common.load_module(
        "layer_metrics", "ut_expected_exit_step.py").read(ctx) \
        == w["ut_expected_exit_step"]


def test_readers_decline_on_a_program_without_the_counters():
    """The parent commit has no such counters: a reader returns None and
    the line leaves the metric out."""
    ctx = argparse.Namespace(window={}, trace_summary={}, peaks=None,
                             config={}, kernel_seconds=None)
    for name in ("looped_decode_step_roofline", "ut_decode_kernel_roofline",
                 "ut_expected_exit_step"):
        assert common.load_module("layer_metrics",
                                  name + ".py").read(ctx) is None
