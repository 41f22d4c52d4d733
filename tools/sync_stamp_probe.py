#!/usr/bin/env python3
"""What the always-on stamps of the serving path cost on THIS host, each
from a loop of its own (best of five batches, nanoseconds a call):

    python3 tools/sync_stamp_probe.py

``span`` a ring span, ids included (PR 25's 1.4 us on the chip's host);
``mark`` ``Span.mark``; ``id_store`` one ``sp.ids[...] = n``; ``rusage`` one
``getrusage(RUSAGE_SELF)``; ``schedstat`` one 64-byte ``pread`` of this
thread's ``/proc/thread-self/schedstat`` (null where the kernel keeps no such
file); ``watch_pair`` ``StallWatch.begin`` + ``.end`` around a span that is no
stall, with a full history (what every device sync pays); ``enqueued_idle`` /
``enqueued_stamped`` ``engine._enqueued`` without and with an interval to
count. ``tick_ns`` sums what one tick of the paged engine pays beside PR 25's
spans: two admissions' marks, two chunks' and a block's ``_enqueued``, a
prefill's and a block's watched sync, the block's mark and two id stores.
No device is touched.
"""
from __future__ import annotations

import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.observability import tracing          # noqa: E402

N = 20000


def best_ns(fn) -> float:
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for _ in range(N):
            fn()
        best = min(best, (time.perf_counter_ns() - t0) / N)
    return round(best, 1)


def main() -> int:
    from paddle_tpu.serving.engine import ContinuousBatchingEngine
    out = {}

    def spanned():
        with tracing.span("serving.tick", tick=1):
            pass
    out["span"] = best_ns(spanned)
    sp = tracing.Span("probe").__enter__()
    out["mark"] = best_ns(lambda: sp.mark("first"))

    def store():
        sp.ids["fetches"] = 4
    out["id_store"] = best_ns(store)
    out["rusage"] = best_ns(
        lambda: resource.getrusage(resource.RUSAGE_SELF))
    watch = tracing.StallWatch()
    out["schedstat"] = best_ns(watch._schedstat) \
        if watch._schedstat() is not None else None
    sp.__exit__(None, None, None)
    for _ in range(tracing.STALL_HISTORY):
        watch.end(sp, watch.begin())
    out["watch_pair"] = best_ns(
        lambda: watch.end(sp, watch.begin()))
    watch.close()

    # the two methods alone, on an object that has what they touch
    eng = ContinuousBatchingEngine.__new__(ContinuousBatchingEngine)
    eng.device_starved_ns, eng._drained_ns = 0, None
    out["enqueued_idle"] = best_ns(lambda: eng._enqueued(sp))

    def stamped():
        eng._drained_ns = 1
        eng._enqueued(sp)
    out["enqueued_stamped"] = best_ns(stamped)
    out["tick_ns"] = round(
        2 * 2 * out["mark"] + 3 * out["enqueued_stamped"]
        + 2 * out["watch_pair"] + out["mark"] + 2 * out["id_store"], 1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
