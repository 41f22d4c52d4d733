"""Serving loop: 95th percentile of ``Server.tick_seconds`` inside the
window (host clock around a tick that ends in blocking transfers)."""
from benchmark.common import percentile


def read(ctx):
    v = percentile(ctx.window.get("tick_s", []), 95)
    return None if v is None else v * 1e3
