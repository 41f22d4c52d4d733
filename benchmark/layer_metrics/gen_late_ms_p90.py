"""Load generator: 90th percentile of (submit time - due time) over the
requests due in the window, on the benchmark's clock. The generator shares
the serving loop's one thread, so this is about one tick, by design."""
from benchmark.common import percentile


def read(ctx):
    return percentile(ctx.window.get("gen_late_ms", []), 90)
