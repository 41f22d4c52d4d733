#!/usr/bin/env python3
"""One process, one cell, one result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, kind, generator or
per-layer metric is a file of its own found by NAME (see README.md):

    BENCHMARK.json            which cells exist, which metrics each reports
    workloads/<cell>.json     the traffic mix and how long it warms and drains
    configs/<config>.json     the model's published sizes, its cut, the deployment
    kinds/<kind>.py           how such a cell is set up, warmed, measured, checked
    traffic/<generator>.py    requests from the mix's parameters and ``--seed``
    layer_metrics/<name>.py   one ``read(ctx)`` per per-layer metric

The last line of stdout is the contract's JSON object: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``. Without
a TPU (or with fewer chips than the cell asks for, or a device kind missing
from ``peaks.json``) the exit code is 2 and no result is printed.
``--rehearse`` (never passed by the driver) swaps in the toy sizes of
``rehearse.json`` for a CPU rehearsal of the control flow; it stamps
``platform: cpu`` truthfully and reports no device metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # before any heavy import: set-up's origin

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import common, flops        # noqa: E402


def say(msg: str):
    print(msg, flush=True)


class Context:
    """What a kind and a per-layer metric reader are handed."""

    def __init__(self, args, entry, cell, config, peaks, meter):
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), bool(args.rehearse)
        self.entry, self.cell = entry, cell
        self.config, self.peaks, self.meter = config, peaks, meter
        self.flops, self.say = flops, say
        self.split = {}             # where set-up's seconds went
        self.setup_s = None
        self.reference = None       # set by run() when the cell asks for it
        self.trace_summary = {}     # trace_reduce.reduce(), traced runs only
        self.trace_window_s = None
        self.e2e, self.window = {}, {}
        self.memory_peak_bytes = None
        self._trace_dir = os.path.join(ROOT, ".bench_out",
                                       "trace-" + entry["name"])
        self._trace_t0 = None

    def window_opens(self, t: float):
        self.setup_s = t - T_START

    def start_trace(self):
        import jax
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        os.makedirs(self._trace_dir, exist_ok=True)
        # no Python-call tracing: it slows the very host loop a tick is
        # made of; the benchmark's own spans are TraceMe events (host tracer)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._trace_t0 = time.perf_counter()

    def stop_trace(self, span_names):
        import jax
        from benchmark import trace_reduce
        self.trace_window_s = time.perf_counter() - self._trace_t0
        jax.profiler.stop_trace()
        t = time.perf_counter()
        self.trace_summary = trace_reduce.reduce_dir(self._trace_dir,
                                                     span_names)
        say(f"trace: {self.trace_window_s:.3f} s traced, reduced in "
            f"{time.perf_counter() - t:.1f} s; modules "
            f"{self.trace_summary.get('modules')}")
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(self._trace_dir, ignore_errors=True)


def reports(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rate-per-s", type=float, default=None,
                    help="offer an open-loop cell another rate: for the "
                         "one sweep that finds a new cell's knee")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        print(f"benchmark: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    cell = common.load_json("workloads", entry["name"] + ".json")
    config = common.load_json("configs", entry["config"] + ".json")
    if args.rehearse:
        toy = common.load_json("rehearse.json")
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = common.merge(common.merge(config, toy["config"]),
                              toy[cell["kind"]].get("config", {}))
        cell = common.merge(cell, cell.get("rehearse", {}))

    if args.rate_per_s is not None:
        cell["traffic"]["arrivals"]["rate_per_s"] = args.rate_per_s

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    all_peaks = common.load_json("peaks.json")
    if not args.rehearse and (dev.platform != "tpu"
                              or dev.device_kind not in all_peaks
                              or len(devices) < entry["chips"]):
        print(f"benchmark: cell {entry['name']} needs {entry['chips']} TPU "
              f"chip(s) of a kind in peaks.json ({sorted(all_peaks)}); jax "
              f"found {device}", file=sys.stderr)
        return 2
    peaks = all_peaks.get(dev.device_kind)

    from paddle_tpu.utils.compile_cache import configure_compile_cache
    # 0: the small programs of set-up (weights, admit, arm) are cached too
    cache_dir = configure_compile_cache(0.0)
    if args.rehearse:
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.ops.pallas import fused
        fused._FORCE_INTERPRET = fa._FORCE_INTERPRET = True
    meter = common.CompileMeter()
    ctx = Context(args, entry, cell, config, peaks, meter)
    ctx.split["imports_s"] = time.perf_counter() - T_START
    say(f"benchmark: cell {entry['name']} (config {entry['config']}, kind "
        f"{cell['kind']}), seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}; device {device}; jax {jax.__version__}; compile "
        f"cache {cache_dir}" + (" — CPU REHEARSAL, toy sizes" if
                                args.rehearse else ""))
    if cell.get("reference"):
        ref = common.load_module("reference", cell["reference"] + ".py")
        ctx.reference = lambda model: ref.check(model, ctx)

    kind = common.load_module("kinds", cell["kind"] + ".py")
    result = kind.run(ctx)
    ctx.e2e, ctx.window = result["e2e"], result["window"]
    ctx.e2e["setup_s"] = ctx.setup_s
    stats = dev.memory_stats() or {}
    ctx.memory_peak_bytes = stats.get("peak_bytes_in_use")

    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if reports(m, entry["name"]):
                v = common.load_module(
                    "layer_metrics", m["name"] + ".py").read(ctx)
                if v is not None:
                    metrics[m["name"]] = v
    else:
        for m in bench["end_to_end"]:
            if reports(m, entry["name"]) and ctx.e2e.get(m["name"]) is not None:
                metrics[m["name"]] = ctx.e2e[m["name"]]

    for what, ok in result["checks"].items():
        say(f"  {'ok' if ok else 'FAILED'}: {what}")
    snap = meter.snapshot()
    say(f"set-up {ctx.setup_s:.2f} s: " + ", ".join(
        f"{k} {v:.2f}" for k, v in ctx.split.items())
        + f"; compile-or-cache-load {snap['compile_s']:.2f} s over "
        f"{snap['compiles']} programs, {snap['cache_hits']} cache hits, "
        f"{snap['cache_misses']} misses")
    say("all end-to-end values: " + json.dumps(ctx.e2e))

    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    line = {"correct": all(result["checks"].values()),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in metrics.items()},
            "device": device}
    if args.trace and ctx.trace_summary:
        device["busy_s"] = ctx.trace_summary["busy_s"]
        device["window_s"] = ctx.trace_window_s
        line["breakdown"] = {
            "device_ops": ctx.trace_summary["device_ops"],
            "idle_gaps": ctx.trace_summary["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
