"""Pallas block-size autotuner: sweep → provenance-stamped table →
trace-time lookup.

Every Pallas kernel in the repo used to hard-code its block shapes
(flash 512-class tiles from one v5e profile, xent's 4096 chunk cap,
the paged arena's block_size=16). Those constants are device-kind
facts, not code facts — this module gives them a measured home:

- **Table**: one JSON file (``PT_TUNE_TABLE`` or
  ``~/.cache/paddle_tpu/tune_table.json``) holding per-kernel winning
  configs keyed by ``kernel | device_kind | sorted(key=value,...)``,
  stamped with the SAME provenance fields as every bench artifact
  (PR 7): jax/jaxlib versions, device kind, git rev, UTC.
- **Staleness**: a table whose stamp disagrees with the RUNNING
  environment (different jaxlib or device kind) is never consulted
  silently — :func:`lookup` warns once and reports misses, and
  ``tools/tier1.sh`` prints the same verdict up front. Re-sweep to
  refresh; :func:`record` starts a fresh table rather than mixing
  provenances.
- **Consumers** (all at trace time, fallback defaults documented in
  each): ``xent._best_chunk`` (chunk cap), ``flash_attention``
  (splash/flash block preferences, with the effective choice
  attributable via :func:`last_block_choice`) and the paged engine's
  default ``block_size``.
- **Sweeps** (:func:`run_autotune`): xent vocab-chunk and the paged
  arena block size measure real work on ANY backend (the CPU lane's
  numbers tune the CPU lane); the flash/splash block sweep only runs
  where the kernels do (TPU) and is recorded as skipped elsewhere — a
  CPU-stamped table never smuggles CPU timings into TPU kernels because
  the device-kind key and stamp both change.

Lookups are counted (``pt_autotune_lookups_total{kernel,result}``) so
a serving fleet can see tuner hit/miss/stale rates next to the pass
rewrite counters.
"""
from __future__ import annotations

import json
import os
import time
import warnings
from typing import Dict, Optional

from ...observability import metrics as _om
from ...utils.flags import env_str

__all__ = ["table_path", "current_stamp", "stamp_matches", "load_table",
           "lookup", "record", "tuned_paged_block_size", "run_autotune"]

_M_LOOKUPS = _om.counter(
    "pt_autotune_lookups_total",
    "autotune-table lookups by kernel and result (hit/miss/stale)",
    labels=("kernel", "result"))

_DEFAULT_PATH = os.path.join(os.path.expanduser("~"), ".cache",
                             "paddle_tpu", "tune_table.json")


def table_path() -> str:
    """Resolved tuning-table location (``PT_TUNE_TABLE`` overrides the
    per-user cache default)."""
    return env_str("PT_TUNE_TABLE") or _DEFAULT_PATH


def _device_kind() -> str:
    import jax
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


# process-constant stamp fields, resolved once: stamp_matches() runs on
# EVERY trace-time lookup against a present table, and forking
# `git rev-parse` / scanning package metadata per kernel trace would be
# pure waste (jaxlib version and device kind cannot change in-process)
_ENV_STAMP: dict = {}


def _env_stamp() -> dict:
    if not _ENV_STAMP:
        import importlib.metadata as md

        def _v(pkg):
            try:
                return md.version(pkg)
            except md.PackageNotFoundError:
                return None

        try:
            import subprocess
            rev = subprocess.run(
                ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
                 "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True,
                timeout=10).stdout.strip() or None
        except Exception:
            rev = None
        _ENV_STAMP.update(
            jax_version=_v("jax"), jaxlib_version=_v("jaxlib"),
            device_kind=_device_kind(), git_rev=rev)
    return _ENV_STAMP


def current_stamp() -> dict:
    """The provenance stamp (PR 7 bench format: software stack + source
    rev + device kind) a table written NOW would carry."""
    return dict(_env_stamp(),
                tuned_utc=time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime()))


def stamp_matches(stamp: dict) -> tuple:
    """(ok, reason): whether a table stamp is valid for the RUNNING
    environment. jaxlib version and device kind are the block-shape-
    bearing facts; jax version and git rev are recorded for the paper
    trail but do not invalidate (block shapes survive frontend
    changes)."""
    cur = _env_stamp()
    for field in ("jaxlib_version", "device_kind"):
        if stamp.get(field) != cur[field]:
            return False, (f"{field} mismatch: table has "
                           f"{stamp.get(field)!r}, running "
                           f"{cur[field]!r}")
    return True, "ok"


def _entry_key(kernel: str, key: Dict) -> str:
    parts = ",".join(f"{k}={key[k]}" for k in sorted(key))
    return f"{kernel}|{_device_kind()}|{parts}"


# per-path cache: (mtime, parsed-table-or-None, stale_reason)
_CACHE: Dict[str, tuple] = {}
_WARNED: set = set()


def load_table(path: Optional[str] = None) -> Optional[dict]:
    """Parse the table at ``path`` (cached by mtime); None when absent
    or unreadable. Staleness is judged at lookup, not load."""
    path = path or table_path()
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        _CACHE.pop(path, None)
        return None
    hit = _CACHE.get(path)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    try:
        with open(path) as f:
            table = json.load(f)
        if not isinstance(table.get("entries"), dict):
            raise ValueError("no entries dict")
    except (OSError, ValueError, json.JSONDecodeError):
        table = None
    _CACHE[path] = (mtime, table, None)
    return table


def lookup(kernel: str, key: Dict, path: Optional[str] = None
           ) -> Optional[dict]:
    """Trace-time consult: the winning config dict for ``kernel`` under
    ``key`` on THIS device kind, or None (missing table/entry, or a
    stale stamp — never silently served). Counted per result."""
    path = path or table_path()
    table = load_table(path)
    if table is None:
        _M_LOOKUPS.inc(kernel=kernel, result="miss")
        return None
    ok, reason = stamp_matches(table.get("stamp", {}))
    if not ok:
        if path not in _WARNED:
            _WARNED.add(path)
            warnings.warn(
                f"autotune table {path} is STALE ({reason}) — kernels "
                "fall back to their documented defaults; re-run the "
                "autotune sweep (autotune.run_autotune) to refresh",
                RuntimeWarning)
        _M_LOOKUPS.inc(kernel=kernel, result="stale")
        return None
    entry = table["entries"].get(_entry_key(kernel, key))
    if entry is None:
        _M_LOOKUPS.inc(kernel=kernel, result="miss")
        return None
    _M_LOOKUPS.inc(kernel=kernel, result="hit")
    return dict(entry.get("config", {}))


def record(kernel: str, key: Dict, config: Dict, measured_ms: float,
           path: Optional[str] = None, candidates: int = 0) -> str:
    """Persist one sweep winner (atomic tmp+rename). A pre-existing
    table with a MISMATCHED stamp is replaced wholesale — mixing
    provenances inside one file would defeat the staleness contract."""
    from ...distributed.checkpoint import atomic_json_dump
    path = path or table_path()
    table = load_table(path)
    if table is not None and not stamp_matches(
            table.get("stamp", {}))[0]:
        table = None            # stale: start fresh, never mix stamps
    if table is None:
        table = {"entries": {}}
    table["stamp"] = current_stamp()
    table["entries"][_entry_key(kernel, key)] = {
        "kernel": kernel, "key": dict(key), "config": dict(config),
        "measured_ms": round(float(measured_ms), 4),
        "candidates": int(candidates)}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    atomic_json_dump(path, table)
    _CACHE.pop(path, None)
    return path


def tuned_paged_block_size(default: int = 16) -> int:
    """The paged engine's default arena block size: tuned entry when a
    valid table has one, the documented default (16) otherwise. The
    explicit ``block_size=`` / ``PT_SERVING_BLOCK_SIZE`` knobs always
    win (resolution lives in serving/paging.py)."""
    cfg = lookup("paged_attention", {"knob": "block_size"})
    if cfg:
        bs = int(cfg.get("block_size", 0))
        if bs > 0:
            return bs
    return default


# ---------------------------------------------------------------------------
# sweeps
# ---------------------------------------------------------------------------

def _time_best(candidates, fn, reps: int = 3):
    """(best_candidate, best_ms, {candidate: ms}): median-free min-of-
    reps timing — the sweep wants the fastest config, and min is the
    noise-robust estimator for 'how fast can this go'."""
    results = {}
    for cand in candidates:
        fn(cand)                            # compile/warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(cand)
            best = min(best, time.perf_counter() - t0)
        results[cand] = best * 1000.0
    winner = min(results, key=results.get)
    return winner, results[winner], results


def autotune_xent(rows: int = 256, vocab: int = 8192,
                  path: Optional[str] = None) -> dict:
    """Sweep the xent fallback's vocab-chunk cap (the (N, chunk)
    transient size vs scan-step count trade — real work on every
    backend) and persist the winner for THIS (rows-class, vocab)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from .xent import _rows_scan_fwd

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(rows, vocab).astype(np.float32))
    labels = jnp.asarray(rs.randint(0, vocab, (rows,)).astype(np.int32))
    # candidates are CAPS; _best_chunk resolves each to the largest
    # divisor of vocab it allows — dedupe on the EFFECTIVE chunk so a
    # non-power-of-two vocab (e.g. 32000) still sweeps distinct real
    # schedules instead of crashing on an empty list
    from .xent import _best_chunk
    cands = sorted({_best_chunk(vocab, c)
                    for c in (512, 1024, 2048, 4096, 8192)})

    jitted = {c: jax.jit(lambda xv, lv, _c=c: _rows_scan_fwd(
        xv, lv, chunk_cap=_c)) for c in cands}

    def run(c):
        nll, lse = jitted[c](x, labels)
        jax.block_until_ready((nll, lse))

    winner, ms, results = _time_best(cands, run)
    key = {"vocab": vocab}
    record("xent", key, {"chunk_cap": winner}, ms, path=path,
           candidates=len(cands))
    return {"kernel": "xent", "key": key, "winner": {"chunk_cap": winner},
            "ms": {str(k): round(v, 3) for k, v in results.items()}}


def autotune_paged_block(path: Optional[str] = None, num_slots: int = 4,
                         max_new: int = 16) -> dict:
    """Sweep the paged arena block size over a short served stream —
    block size trades table-walk length against gather/DMA granularity
    on every backend (CPU gathers included)."""
    import numpy as np
    import paddle_tpu as paddle
    from ...models.llama import LlamaForCausalLM, llama_tiny_config
    from ...serving import ContinuousBatchingEngine, Server

    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size,
                          (6 + (i % 3) * 5,)).astype(np.int32)
               for i in range(num_slots * 2)]
    cands = (8, 16, 32)
    engines = {}

    def run(bs):
        eng = engines.get(bs)
        if eng is None:
            eng = engines[bs] = ContinuousBatchingEngine(
                model, num_slots=num_slots, max_len=64,
                decode_block=4, block_size=bs,
                prefill_chunk=bs)
        eng.reset()
        srv = Server(eng)
        for p in prompts:
            srv.submit(p, max_new_tokens=max_new)
        srv.run_until_idle()

    winner, ms, results = _time_best(cands, run, reps=2)
    key = {"knob": "block_size"}
    record("paged_attention", key, {"block_size": winner}, ms,
           path=path, candidates=len(cands))
    return {"kernel": "paged_attention", "key": key,
            "winner": {"block_size": winner},
            "ms": {str(k): round(v, 2) for k, v in results.items()}}


def autotune_flash(seq: int = 1024, heads: int = 8, dim: int = 128,
                   path: Optional[str] = None) -> dict:
    """Sweep splash/flash block preferences on the REAL kernels — TPU
    only (off-TPU the kernels never dispatch, so there is nothing
    honest to time; recorded as skipped)."""
    import jax
    if jax.default_backend() != "tpu":
        return {"kernel": "flash", "skipped": "needs a TPU backend — "
                "the Pallas kernels do not dispatch off-TPU"}
    import jax.numpy as jnp
    import numpy as np
    from . import flash_attention as fa

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, seq, heads, dim).astype(np.float32))
    cands = (128, 256, 512, 1024)

    # no cache clearing needed: each candidate's BlockSizes produce a
    # DISTINCT compiled kernel, so the warm call compiles it and the
    # timed reps measure runtime, not compilation
    def run(blk):
        os.environ["PT_SPLASH_BLOCK"] = str(blk)
        os.environ["PT_JAX_FLASH_BLOCK"] = str(blk)
        try:
            out = fa.sdpa(q, q, q, is_causal=True)
            jax.block_until_ready(out)
        finally:
            os.environ.pop("PT_SPLASH_BLOCK", None)
            os.environ.pop("PT_JAX_FLASH_BLOCK", None)

    winner, ms, results = _time_best(cands, run, reps=2)
    key = {"seq": seq, "dim": dim}
    record("flash_attention", key, {"block_q": winner, "block_kv": winner},
           ms, path=path, candidates=len(cands))
    return {"kernel": "flash_attention", "key": key,
            "winner": {"block_q": winner, "block_kv": winner},
            "ms": {str(k): round(v, 2) for k, v in results.items()}}


def run_autotune(path: Optional[str] = None, rows: int = 256,
                 vocab: int = 8192) -> dict:
    """Run every sweep that is honest on this backend, persist the
    stamped table, and PROVE a kernel reads it at trace time (the xent
    chunk cap is re-derived through the production lookup path and
    compared against the recorded winner)."""
    path = path or table_path()
    out = {"autotune_table": path}
    xent_res = autotune_xent(rows=rows, vocab=vocab, path=path)
    out["autotune_xent"] = xent_res
    out["autotune_paged"] = autotune_paged_block(path=path)
    out["autotune_flash"] = autotune_flash(path=path)
    table = load_table(path)
    out["autotune_stamp"] = table.get("stamp") if table else None
    out["autotune_entries"] = len(table["entries"]) if table else 0
    # proof of trace-time consumption: the production helper must now
    # return the tuned cap, not the hard-coded default
    from .xent import _tuned_chunk_cap
    got = _tuned_chunk_cap(vocab)
    out["autotune_xent_consulted"] = (
        got == xent_res["winner"]["chunk_cap"])
    out["autotune_paged_default_consulted"] = (
        tuned_paged_block_size()
        == out["autotune_paged"]["winner"]["block_size"])
    return out
