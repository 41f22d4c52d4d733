#!/usr/bin/env python3
"""Two questions about the program's span recorder that only a run can
answer; run by hand (on the chip through the chip tool, or here with
``--rehearse``), never by the driver:

    python benchmark/tests/span_probe.py cost
    python benchmark/tests/span_probe.py twins --workload <cell> --seed <n>
        [--seconds <s>] [--rehearse]

``cost``: what one span costs on this host — a loop of 1e5 spans with no
profiler session (the price every tick and step always pays) and inside an
open ``jax.profiler`` session (what a traced run pays).

``twins``: one traced run of a cell in this process (``run.py --trace 1``
with the trace kept), then the ring's spans of the traced interval against
the ``.xplane.pb``: every span has a twin of the same name in the
``/host:CPU`` plane, how far the two clocks lie apart and how much that
offset wanders, and (serving) the four ``tick_*_ms.serve`` metrics against
the mean ``serving.tick`` span and the mean of ``Server.tick_seconds`` over
the same ticks. Prints one JSON object and writes it to
``chiprun_out/span_probe-<what>.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def emit(what: str, out: dict):
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"span_probe-{what}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)


def cost(n: int = 100_000) -> dict:
    import tempfile

    import jax
    from paddle_tpu.observability.tracing import span

    def loop():
        best = float("inf")
        for _ in range(5):                    # best of five batches
            t0 = time.perf_counter()
            for i in range(n // 5):
                with span("serving.tick", tick=i):
                    pass
            best = min(best, (time.perf_counter() - t0) / (n // 5))
        return best * 1e6

    loop()                                    # warm the interpreter
    out = {"device": str(jax.devices()[0].device_kind),
           "spans": n, "us_per_span_no_session": loop()}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["us_per_span_in_session"] = loop()
        finally:
            jax.profiler.stop_trace()
    return out


def decode_launch_ms(profile, enqueues, module: str) -> list:
    """For each ``serving.decode_block`` twin (name, start, dur) the delay
    to the next execution of ``module`` on the device, in ms."""
    from benchmark import trace_reduce
    runs = sorted(
        s for d in trace_reduce.load(profile)["devices"].values()
        for n, s, _ in d["modules"] if trace_reduce.short_name(n) == module)
    return [min((s - e[1] for s in runs if s >= e[1]), default=0) / 1e6
            for e in enqueues]


def twins(args) -> dict:
    os.environ["BENCH_KEEP_TRACE"] = "1"
    from benchmark import run, trace_reduce
    from jax.profiler import ProfileData
    from paddle_tpu.observability import tracing

    held = {}
    stop_trace = run.Context.stop_trace

    def keep_ctx(self, span_names):
        held["ctx"] = self
        return stop_trace(self, span_names)

    run.Context.stop_trace = keep_ctx
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--trace", "1"]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    if args.rehearse:
        argv.append("--rehearse")
    rc = run.main(argv)
    ctx = held["ctx"]
    ring = sorted(tracing.since(ctx._trace_t0,
                                ctx._trace_t0 + ctx.trace_window_s),
                  key=lambda r: r.start)
    names = sorted({r.name for r in ring})
    profile = ProfileData.from_file(trace_reduce.find_xplane(ctx._trace_dir))
    host = next(p for p in profile.planes
                if p.name == trace_reduce.HOST_PLANE)
    xplane = sorted(((e.name, int(e.start_ns), int(e.duration_ns))
                     for line in host.lines for e in line.events
                     if e.name in names), key=lambda e: e[1])
    per_name = {}
    offsets, longer = [], []
    for name in names:
        mine = [r for r in ring if r.name == name]
        theirs = [e for e in xplane if e[0] == name]
        per_name[name] = {"ring": len(mine), "xplane": len(theirs)}
        if len(mine) == len(theirs):
            offsets += [e[1] - r.start for e, r in zip(theirs, mine)]
            longer += [e[2] - r.dur for e, r in zip(theirs, mine)]
    out = {"rc": rc, "cell": args.workload, "traced_s": ctx.trace_window_s,
           "spans": per_name,
           "every_span_has_its_twin": all(
               v["ring"] == v["xplane"] for v in per_name.values())}
    if offsets:
        med = statistics.median(offsets)
        out["xplane_minus_ring_ns"] = {
            "median": med, "min": min(offsets), "max": max(offsets),
            "minus_trace_t0_ns": med + ctx._trace_t0 * 1e9}
        out["twin_longer_by_ns"] = {"median": statistics.median(longer),
                                    "max": max(longer)}
    ticks = [r for r in ring if r.name == "serving.tick"]
    if ticks:
        from benchmark.common import load_module
        parts = {m: load_module("layer_metrics", m + ".py").read(ctx)
                 for m in ("tick_sched_ms.serve", "tick_dispatch_ms.serve",
                           "tick_device_wait_ms.serve",
                           "tick_harvest_ms.serve")}
        # the trace closes on the window's last tick: the last
        # len(ticks) entries of the window's tick_seconds are these ticks
        tick_s = ctx.window["tick_s"][-len(ticks):]
        own = sorted(tracing.self_times(ring).items())
        out["tick"] = {
            "n": len(ticks), "parts_ms": parts,
            "self_ms_per_tick": {k: v[1] / len(ticks) / 1e6 for k, v in own},
            "count_per_tick": {k: v[0] / len(ticks) for k, v in own},
            # each tick as (span ms, decode_sync ms), and per decode block
            # how long after the host's enqueue span began the device
            # started the program (host twin and device event share the
            # xplane's clock)
            "ticks_ms": [[t.dur / 1e6, sum(
                r.dur for r in ring if r.parent == t.id
                and r.name == "serving.decode_sync") / 1e6] for t in ticks],
            "decode_launch_ms": decode_launch_ms(
                profile, [e for e in xplane
                          if e[0] == "serving.decode_block"],
                ctx.window["decode_module"]),
            "parts_sum_ms": sum(parts.values()),
            "mean_tick_span_ms": sum(t.dur for t in ticks) / len(ticks) / 1e6,
            "mean_tick_seconds_ms": sum(tick_s) / len(tick_s) * 1e3,
            "spans_per_tick": len([r for r in ring if r.name.startswith(
                "serving.")]) / len(ticks)}
    steps = [r for r in ring if r.name == "train.step_dispatch"]
    if steps:
        own = tracing.self_times(ring)
        out["step"] = {
            "n": len(steps),
            "ms_per_step": {k: v[1] / len(steps) / 1e6
                            for k, v in sorted(own.items())},
            "data_wait_ms_per_step_window":
                sum(ctx.window["data_wait_s"])
                / len(ctx.window["data_wait_s"]) * 1e3,
            "spans_per_step": len(ring) / len(steps)}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    sub.add_parser("cost")
    tw = sub.add_parser("twins")
    tw.add_argument("--workload", required=True)
    tw.add_argument("--seed", type=int, default=0)
    tw.add_argument("--seconds", type=float, default=None)
    tw.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.what == "cost":
        emit("cost", cost())
        return 0
    out = twins(args)
    emit("twins-" + args.workload, out)
    return out["rc"]


if __name__ == "__main__":
    sys.exit(main())
