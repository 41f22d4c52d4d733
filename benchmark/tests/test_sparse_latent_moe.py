"""The ``serve_sparse_latent_moe`` kind end to end on the CPU at toy sizes.

``run.py --rehearse`` reads ``toy[cell["kind"]]`` from ``rehearse.json``,
which a later PR does not edit, so it cannot rehearse a new kind: this test
builds the ``Context`` itself from ``dots3_toy.json``. Run by hand:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_sparse_latent_moe.py -q``
(about two minutes; tier-1 collects only ``tests/``, where
``tests/test_benchmark_sparse_latent.py`` holds the quick arithmetic).
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

CELL = "dots3-longdoc-decode"


def toy_context(trace: int, seconds: float = 3.0):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    toy = common.load_json("tests", "dots3_toy.json")
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
    # the toy mix is too small for two premises of the real one (four slots
    # refill too often; a 96-token document is under 0.8 of a prompt)
    assert all(k.startswith(("the window's decode steps kept",
                             "the prefix index served")) for k in failed), \
        failed
    checks = result["checks"]
    for start in ("timed path", "timed context", "(a) no-cache forward",
                  "(b) through both",
                  "every decoded token read index_topk",
                  "the indexers scored every live token",
                  "Pallas s=1 read"):
        assert any(k.startswith(start) for k in checks), start
    assert result["e2e"]["out_tokens_per_s"] > 0 and not result["failed"]
    assert "documents_s" in ctx.split
    ctx.e2e, ctx.window = result["e2e"], result["window"]
    w = result["window"]
    assert 0 < w["moe_experts_hit_share"] <= 1
    # every question hit its ingested document in both groups
    assert 0.7 < w["prefix_hit_share"] < 1
    # contexts of 100-170 tokens, 16 selected: a tenth or so
    assert 0.08 < w["dsa_selected_share"] < 0.17
    assert w["dsa_selected_rows_per_step"] <= 4 * 16
    assert 0 < w["swa_kv_resident_share"] and w["window_kv_rows_per_step"] \
        <= 4 * 9 < w["kv_rows_per_step"]
    # every per-layer reader of the cell answers or declines, never raises;
    # device metrics have nothing to read in an untraced CPU run
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            v = common.load_module("layer_metrics",
                                   m["name"] + ".py").read(ctx)
            if m["source"] == "device_trace" or "hbm" in m["name"]:
                assert v is None, m["name"]
    assert common.load_module(
        "layer_metrics", "dsa_selected_share.py").read(ctx) \
        == w["dsa_selected_share"]


def test_readers_decline_on_a_program_without_the_counters():
    """The parent commit has no such counters, spans or kernels: a reader
    returns None and the line leaves the metric out."""
    ctx = argparse.Namespace(window={}, trace_summary={}, peaks=None,
                             config={}, kernel_seconds=None,
                             scope_seconds=None)
    for name in ("dsa_moe_decode_step_roofline", "dsa_index_kernel_roofline",
                 "dsa_sparse_read_roofline", "swa_mla_decode_kernel_roofline",
                 "dsa_select_ms_per_step", "dsa_selected_share"):
        assert common.load_module("layer_metrics",
                                  name + ".py").read(ctx) is None
