#!/usr/bin/env python3
"""On-chip A/B of the paged decode read at the served shape: the Pallas
walk (``paged_attention_decode`` and its int8 twin) against the gathered
reference path, per call, for three length mixes. Needs a TPU.

    python3 tools/paged_kernel_probe.py [--chunk-rows N ...]

Defaults are the benchmark's ``mistral7b-decode-sat`` shape (32 slots,
32 query heads over 8 KV heads x 128, KV block 16, a 256-entry table,
5,121 blocks, bf16) and its mean live length (18,029 live tokens). Each
timing is REPS calls chained inside one program (a call's output is the
next call's q), median of 10 runs, so dispatch is amortised; ``err`` is
the largest absolute difference from the reference on the same inputs.
``--chunk-rows`` re-times the walk with ``_CHUNK_ROWS`` set from outside
(how PR 26 chose the constant); the last line is one JSON object.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from paddle_tpu.ops.pallas import paged_attention as pa       # noqa: E402

REPS = 32


def make_inputs(a, lengths, seed=11):
    rs = np.random.RandomState(seed)
    kv = (a.blocks, a.block, a.kv_heads, a.head_dim)
    k, v, q = (jax.random.normal(jax.random.PRNGKey(seed + i), shape,
                                 jnp.bfloat16)
               for i, shape in enumerate(
                   (kv, kv, (a.slots, a.heads, a.head_dim))))
    perm = 1 + rs.permutation(a.blocks - 1)
    tbl = np.zeros((a.slots, a.table), np.int32)
    o = 0
    for i, n_tok in enumerate(lengths):
        n = -(-int(n_tok) // a.block)
        tbl[i, :n] = perm[(o + np.arange(n)) % len(perm)]
        o += n
    return q, k, v, jnp.asarray(tbl), jnp.asarray(lengths, jnp.int32)


def per_call_ms(fn, q, *rest, runs=10):
    def prog(q0, *r):
        return jax.lax.fori_loop(
            0, REPS, lambda _, qq: fn(qq, *r).astype(qq.dtype), q0)
    j = jax.jit(prog)
    j(q, *rest).block_until_ready()
    ts = []
    for _ in range(runs):
        t0 = time.perf_counter()
        j(q, *rest).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(ts)) / REPS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--table", type=int, default=256)
    ap.add_argument("--blocks", type=int, default=5121)
    ap.add_argument("--live-tokens", type=int, default=18029)
    ap.add_argument("--chunk-rows", type=int, nargs="*", default=[])
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("paged_kernel_probe: needs a TPU (this is a "
                 f"{dev.platform}); nothing measured")
    scale = a.head_dim ** -0.5
    rs = np.random.RandomState(7)
    cell = rs.randint(64, 1063, (a.slots,)).astype(np.float64)
    cell = np.maximum(1, cell * a.live_tokens / cell.sum()).astype(np.int32)
    mixes = {"cell": cell,
             "full": np.full((a.slots,), a.table * a.block, np.int32),
             "short": np.full((a.slots,), 40, np.int32)}
    out = {"device": dev.device_kind, "reps": REPS,
           "chunk_rows": pa._CHUNK_ROWS}

    def ref(q, k, v, t, ln):
        return pa.paged_attention_reference(q[:, None], k, v, t, ln,
                                            scale=scale)[:, 0]

    def ref8(q, kc, vc, ks, vs, t, ln):
        return pa.paged_attention_int8_reference(
            q[:, None], kc, vc, ks, vs, t, ln, scale=scale)[:, 0]

    walk = functools.partial(pa.paged_attention_decode, scale=scale)
    walk8 = functools.partial(pa.paged_attention_decode_int8, scale=scale)
    for name, lengths in mixes.items():
        q, k, v, tbl, lens = make_inputs(a, lengths)
        assert pa._kernel_ok(k), "the walk is not routed here"
        live, copied, chunks = pa.walk_counts(
            lengths, a.table, a.block,
            pa._pages_per_chunk(
                a.table, [(pa._page_view(k.shape)[1:], k.dtype)] * 2))
        want = np.asarray(jax.jit(ref)(q, k, v, tbl, lens), np.float32)
        got = np.asarray(jax.jit(walk)(q, k, v, tbl, lens), np.float32)
        row = {"live_tokens": int(np.sum(lengths)), "live_pages": live,
               "copied_pages": copied, "chunks": chunks,
               "err": float(np.abs(got - want).max()),
               "reference_ms": per_call_ms(ref, q, k, v, tbl, lens),
               "walk_ms": per_call_ms(walk, q, k, v, tbl, lens)}
        kv_bytes = 2 * copied * a.block * a.kv_heads * a.head_dim * 2
        row["walk_hbm_roofline_pct"] = \
            100 * kv_bytes / 819e9 / (row["walk_ms"] / 1e3)
        kc, ks = pa.quantize_kv(k)
        vc, vs = pa.quantize_kv(v)
        assert pa._kernel_ok_int8(kc)
        want8 = np.asarray(jax.jit(ref8)(q, kc, vc, ks, vs, tbl, lens),
                           np.float32)
        got8 = np.asarray(jax.jit(walk8)(q, kc, vc, ks, vs, tbl, lens),
                          np.float32)
        row["int8_err"] = float(np.abs(got8 - want8).max())
        row["int8_walk_ms"] = per_call_ms(walk8, q, kc, vc, ks, vs, tbl,
                                          lens)
        if name == "cell":
            row["int8_reference_ms"] = per_call_ms(ref8, q, kc, vc, ks, vs,
                                                   tbl, lens)
        for n in a.chunk_rows:
            pa._CHUNK_ROWS = n
            row[f"walk_ms.chunk_rows_{n}"] = per_call_ms(
                functools.partial(pa.paged_attention_decode, scale=scale),
                q, k, v, tbl, lens)
            pa._CHUNK_ROWS = out["chunk_rows"]
        out[name] = row
        print(name, json.dumps(row), flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
