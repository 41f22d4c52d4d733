#!/usr/bin/env python3
"""On-chip readings for the limits of ``correct`` in ``dots3-longdoc-decode``
(``benchmark/reference/dots3_note.py``, ``kinds/serve_sparse_latent_moe.py``),
at the published widths. Needs a TPU.

    python3 tools/sparse_limits_probe.py [--paths ...] [--seeds N ...]

``nocache``: the bf16 model's own no-cache forward on ``check_tokens``
(4,096) tokens against the reference as it is. ``cached``: ``check_context``
(8,192) tokens prefilled in the deployment's chunks and decoded through both
groups' caches (what check (b) reads) against the reference as it is.
``mutations``: the no-cache comparison with the reference in 8-bit floats
(its own routing too: ``picks_agree``) and with each published term taken
out (``MUTATIONS``). ``nearest``: of those only the two nearest the limits,
8-bit floats and the ReLU dropped (more seeds for less time). ``streams``:
through the emitted tokens' statistic, a stream of random tokens after a
4,096-token prompt, and, teacher-forced, what a system with no indexer
(every token visible) would emit at the last 512 of 4,096 positions.
``timed``: ``reference.timed_context`` at the context the cell is timed at
(a seeded 33,168-token prompt + 400 tokens: the "emitted" ones are random,
so the emitted statistic reads a wrong stream's), the cache path's outputs
taken once and held against the reference as it is, in 8-bit floats,
without the indexer and without its ReLU. One JSON line a seed.

The calls behind PERF.md section 6's readings (PR 37), each one call to the
chip: ``--paths nocache cached --seeds`` 7 seeds; ``--paths mutations
--seeds`` 2 seeds; ``--paths streams --seeds 3400000013``; in one call
(after ``tools/sparse_latent_probe.py --index-only``) ``--paths timed
nearest --seeds 5200000011`` and ``--paths nearest --seeds 5300000017
5400000023``. The cell's runs: ``python3 benchmark/run.py --workload
dots3-longdoc-decode --seed <n> --trace <0|1>``, one after another in a
call, and ``python3 tools/churned_pool_cell.py --seed <n> --trace 0``; the
seeds are in PERF.md section 6.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from benchmark import common, weights_by_class               # noqa: E402
from paddle_tpu.utils.compile_cache import (                  # noqa: E402
    configure_compile_cache)


def blocks(c):
    dep = c["deployment"]
    return dict(block=int(dep["check_block"]),
                head_group=int(dep["check_head_group"]))


def nocache(ref, model, params, c, seed, mutate, only=None):
    n = int(c["deployment"]["check_tokens"])
    ids = np.random.default_rng([seed & 0xFFFFFFFF, 37]).integers(
        0, c["vocab_size"], n, np.int32)
    at = np.unique(np.append(np.arange(7, n, 8), n - 1))   # check (a)'s rows
    logits, picks, sel = ref.model_outputs(model, ids, at=at)
    kw = dict(blocks(c), logits_at=at)
    out = {"as_is": ref.compare(logits, picks, sel, params, c, ids, **kw)}
    if mutate:
        out["float8"] = ref.compare(
            logits, picks, sel, params, c, ids,
            matmul_dtype=jnp.float8_e4m3fn, **kw)
        for m in only or ref.MUTATIONS:
            out[m] = ref.compare(logits, picks, sel, params, c, ids,
                                 own_routing=False, mutate=(m,), **kw)
    return out


def timed(ref, model, params, c, seed):
    """``timed_context`` as the kind calls it, on a seeded sequence as long
    as the cell's requests, and with the reference broken three ways."""
    kind = common.load_module("kinds", "serve_sparse_latent_moe.py")
    dep = c["deployment"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 43])
    doc = 32768
    prompt = rng.integers(0, c["vocab_size"], doc + 400, np.int32)
    tokens = rng.integers(0, c["vocab_size"], 400, np.int32)
    kw = dict(chunk=int(c["overrides"]["prefill_chunk"]["value"]),
              table_len=int(dep["max_len"]), past=doc,
              **kind.REFERENCE_BLOCKS)
    t = time.time()
    got = ref.cached_outputs(
        model, np.concatenate([prompt, tokens])[:-1], chunk=kw["chunk"],
        decode=ref.TIMED_DECODE, table_len=kw["table_len"])
    out = {"cache_path_s": time.time() - t}
    for name, extra in (("as_is", {}),
                        ("float8", {"matmul_dtype": jnp.float8_e4m3fn}),
                        ("indexer", {"mutate": ("indexer",)}),
                        ("relu", {"mutate": ("relu",)})):
        t = time.time()
        out[name] = ref.timed_context(model, params, c, prompt, tokens,
                                      got=got, **kw, **extra)
        out[name]["seconds"] = time.time() - t
        out[name]["peak_gib"] = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0) / 2 ** 30
    return out


def cached(ref, model, params, c, seed):
    n = int(c["deployment"]["check_context"])
    chunk = int(c["overrides"]["prefill_chunk"]["value"])
    ids = np.random.default_rng([seed & 0xFFFFFFFF, 41]).integers(
        0, c["vocab_size"], n, np.int32)
    rows, got, picks, sel = ref.cached_outputs(model, ids, chunk=chunk,
                                               decode=8)
    return {"rows": len(rows),
            "as_is": ref.compare(got, picks, sel, params, c, ids,
                                 logits_at=rows, sel_rows=rows, **blocks(c))}


def streams(ref, params, c, seed):
    """Through the timed path's check: a stream of random tokens; and what
    a system WITHOUT the indexer would emit. (The model's own stream is
    what every run of the cell reads; ``generate()`` cannot make one on a
    chip: its one block a row hands the window walk a page of ``max_len``
    rows.) The second is teacher-forced: at each of the last 512 positions
    of a 4,096-token sequence, the argmax of the reference with every
    token visible on full layers, judged by the reference as it is."""
    rng = np.random.default_rng(seed & 0xFFFF)
    prompt = rng.integers(0, c["vocab_size"], 4096, np.int32)
    kw = blocks(c)
    seq = np.concatenate([prompt, rng.integers(0, c["vocab_size"], 128,
                                               np.int32)])
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    logits, _ = ref.forward(params, c, seq[:-1], logits_at=at, **kw)
    logits = np.asarray(logits, np.float32)
    below = (logits.max(-1) - logits[np.arange(len(at)), seq[len(prompt):]]) \
        / logits.std(-1)
    out = {"random": (float(below.max()), float(below.mean()))}
    at = np.arange(len(prompt) - 512, len(prompt))
    own, picks = ref.forward(params, c, prompt, logits_at=at, **kw)
    dense, _ = ref.forward(params, c, prompt, logits_at=at,
                           forced_picks=picks, mutate=("indexer",), **kw)
    own, dense = np.asarray(own, np.float32), np.asarray(dense, np.float32)
    emitted = dense.argmax(-1)
    below = (own.max(-1) - own[np.arange(len(at)), emitted]) / own.std(-1)
    out["no_indexer"] = {"max_sd": float(below.max()),
                         "mean_sd": float(below.mean()),
                         "same_argmax": float(np.mean(below == 0))}
    return out


def main():
    ap = argparse.ArgumentParser()
    paths = ["nocache", "cached", "mutations", "nearest", "streams", "timed"]
    ap.add_argument("--paths", nargs="+", default=paths[:2], choices=paths)
    ap.add_argument("--seeds", nargs="+", type=int, default=[3000000019])
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("sparse_limits_probe: needs a TPU (the published widths do "
                 "not fit a CPU run)")
    configure_compile_cache(0.0)
    c = common.load_json("configs", "dots3-note-prev.json")
    ref = common.load_module("reference", "dots3_note.py")

    def cfg_of(**extra):
        return weights_by_class.model_config(
            c, n_routed_experts=c["n_routed_experts_published"],
            experts_held=tuple(c["experts_held"]), **extra)
    for seed in args.seeds:
        t = time.time()
        model = weights_by_class.build_lazy(cfg_of(), seed)
        model.eval()
        params = {k: p._value for k, p in model.named_parameters()}
        out = {"seed": seed}
        def peak():
            return (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use", 0) / 2 ** 30
        out["peak_gib_weights"] = peak()
        if {"nocache", "mutations", "nearest"} & set(args.paths):
            broken = {"mutations", "nearest"} & set(args.paths)
            out["nocache"] = nocache(
                ref, model, params, c, seed, bool(broken),
                None if "mutations" in broken else ("relu",))
            out["peak_gib_nocache"] = peak()
        if "timed" in args.paths:
            out["timed"] = timed(ref, model, params, c, seed)
            out["peak_gib_timed"] = peak()
        if "cached" in args.paths:
            out["cached"] = cached(ref, model, params, c, seed)
        if "streams" in args.paths:
            out["streams"] = streams(ref, params, c, seed)
        out["seconds"] = time.time() - t
        out["peak_gib"] = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0) / 2 ** 30
        print(json.dumps(out), flush=True)
        del model, params


if __name__ == "__main__":
    main()
