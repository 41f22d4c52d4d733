"""Device seconds of a kernel summed over ALL its sites.

``trace_reduce.reduce`` keeps the ten operations with most time, and a
kernel called once a layer is as many names as layers
(``mla_paged_attention_decode.3``, ``.7``, ...): none of them may be among
the ten. This sums the "XLA Ops" events whose short name starts with a
prefix, per prefix, averaged over the device planes — the number a
``<kernel>_roofline`` divides by. It reads what ``trace_reduce.load``
loaded (a kind keeps that until it has called this).
"""
from __future__ import annotations

from .trace_reduce import short_name


def seconds_by_prefix(loaded: dict, prefixes) -> dict:
    """``{prefix: (events, device seconds)}`` over ``loaded["devices"]``;
    a kernel's events are leaves of the line, so durations add."""
    devs = loaded.get("devices") or {}
    out = {p: [0, 0.0] for p in prefixes}
    for d in devs.values():
        for name, _, dur in d["ops"]:
            short = short_name(name)
            for p in prefixes:
                if short.startswith(p):
                    out[p][0] += 1
                    out[p][1] += dur / 1e9
    n = max(1, len(devs))
    return {p: (c // n, s / n) for p, (c, s) in out.items()}
