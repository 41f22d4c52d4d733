"""The ``serve_conv_moe`` kind end to end on the CPU at toy sizes.

``run.py --rehearse`` reads ``toy[cell["kind"]]`` from ``rehearse.json``,
which a later PR does not edit, so it cannot rehearse a new kind: this test
builds the ``Context`` itself from ``lfm2_toy.json``. Run by hand:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_conv_moe.py -q``
(about a minute); tier-1 runs the same through
``tests/test_benchmark_conv_moe.py``, beside the arithmetic.
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

CELL = "lfm2-tool-agent-decode"
NEW_METRICS = ["conv_moe_decode_step_roofline", "short_conv_decode_roofline"]


def toy_context(trace: int, seconds: float = 3.0):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    toy = common.load_json("tests", "lfm2_toy.json")
    cell = common.merge(common.load_json("workloads", CELL + ".json"),
                        toy["cell"])
    config = common.merge(
        common.load_json("configs", entry["config"] + ".json"),
        toy["config"])
    args = argparse.Namespace(seed=3000000019, seconds=seconds, trace=trace,
                              rehearse=True)
    ctx = harness.Context(args, entry, cell, config, None,
                          common.CompileMeter())
    return bench, ctx, common.load_module("kinds", cell["kind"] + ".py")


def run_toy_cell(monkeypatch):
    """The kind on the toy cell, untraced; returns what the tests read."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import fused
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
    bench, ctx, kind = toy_context(trace=0)
    result = kind.run(ctx)
    ctx.e2e, ctx.window = result["e2e"], result["window"]
    return bench, ctx, result


def check_toy_run(bench, ctx, result):
    failed = [k for k, ok in result["checks"].items() if not ok]
    # the toy mix is too small for two premises of the real one (four
    # slots refill too often to stay full, a 96-token prefix is under 0.85
    # of a prompt)
    assert all(k.startswith(("the window's decode steps kept",
                             "the prefix index served")) for k in failed), \
        failed
    assert any(k.startswith("every admission in the window started from a "
                            "shared prefix") for k in result["checks"])
    assert any(k.startswith("timed path") for k in result["checks"])
    assert any(k.startswith("(d) cached logits") for k in result["checks"])
    assert result["e2e"]["out_tokens_per_s"] > 0 and not result["failed"]
    w = result["window"]
    assert 0 < w["moe_experts_hit_share"] <= 1
    assert 0.75 < w["prefix_hit_share"] < 1
    assert 0 < w["live_slots_per_step"] <= 4
    assert w["kv_rows_per_step"] > 96 * w["live_slots_per_step"]
    # every per-layer reader of the cell answers or declines, never raises;
    # device metrics have nothing to read in an untraced CPU run
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            v = common.load_module("layer_metrics",
                                   m["name"] + ".py").read(ctx)
            if m["source"] == "device_trace" or "hbm" in m["name"]:
                assert v is None, m["name"]
    assert common.load_module(
        "layer_metrics", "moe_experts_hit_share.py").read(ctx) \
        == w["moe_experts_hit_share"]


def test_kind_runs_the_cell_on_the_cpu_at_toy_sizes(monkeypatch):
    check_toy_run(*run_toy_cell(monkeypatch))
