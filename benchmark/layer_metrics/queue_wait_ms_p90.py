"""Serving loop: 90th percentile of the ``RequestTracer`` queue-wait span
(submit to admission) over the requests due in the window."""
from benchmark.common import percentile


def read(ctx):
    return percentile(ctx.window.get("queue_wait_ms", []), 90)
