"""From a profiler trace (``.xplane.pb``) to two numbers and two short lists.

  busy_s      the union of the intervals in which an operation ran on the
              device, averaged over the device planes in the trace
  modules     per compiled program ("XLA Modules" line): executions and
              device seconds, by module name
  device_ops  the operations with most device time ("XLA Ops" line)
  idle_gaps   the longest gaps between device operations, each named after
              the benchmark's own host span (``TraceAnnotation``) open at
              the middle of the gap

Interval arithmetic works on plain ``(name, start_ns, dur_ns)`` tuples so it
is tested without a trace; ``load`` is the one adapter to
``jax.profiler.ProfileData``. On a TPU the device planes are named
``/device:TPU:<n>``; their "XLA Ops" line holds one event per executed HLO
operation and "XLA Modules" one per program execution. Host spans are
looked for on every line of the ``/host:CPU`` plane.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"


def union(intervals):
    """Merged, sorted ``[start, end)`` pairs of ``(start, dur)`` items."""
    out = []
    for s, d in sorted(intervals):
        e = s + d
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_ns(events) -> int:
    return sum(e - s for s, e in union((s, d) for _, s, d in events))


def self_times(events):
    """Events with each duration cut to SELF time: an event that lies inside
    another on the same line (a ``while`` spans its body's operations) is
    taken out of the one that holds it, so times add up to the busy time."""
    out, stack = [], []             # stack of [name, start, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[1], top[3]))
        if stack:
            stack[-1][3] -= min(d, stack[-1][2] - s)
        stack.append([name, s, s + d, d])
    out.extend((n, s, own) for n, s, _, own in stack)
    return out


def top_k(events, k: int = 10):
    """``[(name, total_ns, count)]`` of the ``k`` names with most self
    time."""
    acc = {}
    for name, _, d in self_times(events):
        t = acc.setdefault(name, [0, 0])
        t[0] += d
        t[1] += 1
    rows = sorted(acc.items(), key=lambda kv: -kv[1][0])[:k]
    return [(n, t, c) for n, (t, c) in rows]


def gaps(events, k: int = 10):
    """The ``k`` longest idle intervals between device operations, as
    ``[(start_ns, dur_ns)]``, longest first."""
    merged = union((s, d) for _, s, d in events)
    holes = [(a[1], b[0] - a[1]) for a, b in zip(merged, merged[1:])]
    return sorted(holes, key=lambda g: -g[1])[:k]


def name_gaps(holes, host_spans):
    """Name each gap after the innermost (shortest) host span that covers
    its middle; ``"(no span)"`` where none does."""
    out = []
    for s, d in holes:
        mid = s + d // 2
        covering = [(hd, n) for n, hs, hd in host_spans
                    if hs <= mid < hs + hd]
        out.append((min(covering)[1] if covering else "(no span)", d))
    return out


def short_name(name: str) -> str:
    """``%fusion.123 = bf16[...] fusion(...)`` -> ``fusion.123``; module
    names lose their ``(fingerprint)`` suffix."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name).strip()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(profile, span_names=()):
    """``ProfileData`` -> ``{"devices": {plane: {"ops": [...], "modules":
    [...]}}, "host_spans": [...]}`` with events as (name, start, dur)."""
    want = set(span_names)
    devices, host = {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
            devices[plane.name] = {"ops": lines.get(OPS_LINE, []),
                                   "modules": lines.get(MODULES_LINE, [])}
        elif plane.name == HOST_PLANE and want:
            for line in plane.lines:
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events if ev.name in want)
    return {"devices": devices, "host_spans": host}


def reduce(loaded: dict, k: int = 10) -> dict:
    """The summary the layer metrics and the ``breakdown`` read. Times in
    seconds; ``modules`` maps short module name -> (executions, seconds)
    summed over devices; the two lists come from the busiest device."""
    devs = loaded["devices"]
    if not devs:
        return {}
    busy = {n: busy_ns(d["ops"]) for n, d in devs.items()}
    modules = {}
    for d in devs.values():
        for name, total, count in top_k(d["modules"], k=10 ** 6):
            m = modules.setdefault(short_name(name), [0, 0.0])
            m[0] += count
            m[1] += total / 1e9
    lead = devs[max(busy, key=busy.get)]
    return {
        "n_devices": len(devs),
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "modules": {n: tuple(v) for n, v in modules.items()},
        "device_ops": [[short_name(n), t / 1e9]
                       for n, t, _ in top_k(lead["ops"], k)],
        "idle_gaps": [[n, d / 1e9] for n, d in name_gaps(
            gaps(lead["ops"], k), loaded["host_spans"])],
    }


def reduce_dir(trace_dir: str, span_names=()) -> dict:
    from jax.profiler import ProfileData
    return reduce(load(ProfileData.from_file(find_xplane(trace_dir)),
                       span_names))
