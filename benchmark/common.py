"""Small shared pieces of the harness: files found by name, percentiles,
compile accounting, seeds."""
from __future__ import annotations

import importlib.util
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    """Import ``benchmark/<parts>`` by PATH: metric names hold dots
    (``tick_ms_p95.serve``), which a dotted import would split."""
    path = os.path.join(HERE, *parts)
    name = "benchmark_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, dict by dict."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def percentile(values, q: float):
    """Linear-interpolated percentile; None for no samples."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


def device_idle_pct(ctx):
    """1 - (union of device-operation intervals / traced span), from the
    ``.xplane.pb`` of the traced part of the window; None untraced."""
    busy = ctx.trace_summary.get("busy_s")
    if busy is None or not ctx.trace_window_s:
        return None
    return (1.0 - busy / ctx.trace_window_s) * 100.0


def hbm_peak_gib(ctx):
    """``memory_stats()["peak_bytes_in_use"]`` after the window; None where
    the run is not on the chip (a CPU rehearsal has no HBM)."""
    if ctx.memory_peak_bytes is None or ctx.peaks is None:
        return None
    return ctx.memory_peak_bytes / 2 ** 30


def fold_seed(seed: int) -> int:
    """Any whole number (the driver's are above 2**31) as a non-negative
    31-bit seed for ``jax.random.PRNGKey`` / ``paddle.seed``."""
    seed = int(seed)
    return (seed ^ (seed >> 31) ^ (seed >> 62)) & 0x7FFFFFFF


class CompileMeter:
    """Sums jax's own compile-duration events and counts persistent-cache
    hits (chip_smoke.py's, copied): splits ``setup_s`` and counts the
    compilations inside the window, which must be 0."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"t": time.perf_counter(), "compile_s": self.compile_s,
                "compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
