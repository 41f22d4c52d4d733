#!/usr/bin/env python3
"""On-chip probe of the two mechanisms of a latent-cache, routed-expert
model, each alone, at the shape the ``kanana2-docqa-decode`` cell serves.
Needs a TPU.

    python3 tools/latent_moe_probe.py [--chunk-rows N ...]

``latent``: the s = 1 read ``mla_paged_attention_decode`` (64 slots, 32
heads against one shared 640-wide row, KV block 16, a 320-entry table,
16,385 blocks, bf16) against the gathered read, per call, for three length
mixes; each timing is REPS calls chained inside one program (a call's
output feeds the next call's q), median of 10 runs. ``experts``:
``dropless_expert_mix`` over all 128 experts (2048 x 768 SwiGLU, top-6) for
a decode step's 64 tokens and a prefill chunk's 32, against the bytes of
the experts the routing hits. The last line is one JSON object.
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

from paddle_tpu.incubate.distributed.models.moe import (      # noqa: E402
    dropless_expert_mix)
from paddle_tpu.ops.pallas import paged_attention as pa       # noqa: E402

REPS = 32
HBM = 819e9


def median_ms(j, *args, runs=10, reps=1):
    jax.block_until_ready(j(*args))
    ts = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(j(*args))
        ts.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(ts)) / reps


def chained(fn, rank):
    """REPS calls in one program: the output (b, h, rank) is written back
    into q's first ``rank`` columns, so no call can be dropped."""
    def prog(q0, *r):
        def body(_, q):
            return q.at[..., :rank].set(fn(q, *r).astype(q.dtype))
        return jax.lax.fori_loop(0, REPS, body, q0)
    return jax.jit(prog)


def latent(a, out):
    rs = np.random.RandomState(7)
    scale = 192 ** -0.5
    cell = rs.randint(3136, 4736, (a.slots,)).astype(np.int32)
    mixes = {"cell": cell,
             "full": np.full((a.slots,), a.table * a.block, np.int32),
             "short": np.full((a.slots,), 40, np.int32)}
    arena = jax.random.normal(jax.random.PRNGKey(1),
                              (a.blocks, a.block, a.width), jnp.bfloat16)
    arena = arena.at[..., a.rank + 64:].set(0)
    q = jax.random.normal(jax.random.PRNGKey(2), (a.slots, a.heads, a.width),
                          jnp.bfloat16)
    assert pa._kernel_ok(arena), "the walk is not routed here"
    walk = functools.partial(pa.mla_paged_attention_decode, scale=scale,
                             rank=a.rank)

    def ref(q, ar, t, ln):
        return pa.mla_paged_attention_reference(
            q[:, None], ar, t, ln, scale=scale, rank=a.rank)[:, 0]

    for name, lengths in mixes.items():
        perm = 1 + rs.permutation(a.blocks - 1)
        tbl = np.zeros((a.slots, a.table), np.int32)
        o = 0
        for i, n_tok in enumerate(lengths):
            n = -(-int(n_tok) // a.block)
            tbl[i, :n] = perm[(o + np.arange(n)) % len(perm)]
            o += n
        tbl, lens = jnp.asarray(tbl), jnp.asarray(lengths, jnp.int32)
        live, copied, chunks = pa.walk_counts(
            lengths, a.table, a.block, pa._pages_per_chunk(
                a.table, [(arena.shape[1:], arena.dtype)]))
        want = np.asarray(jax.jit(ref)(q, arena, tbl, lens), np.float32)
        got = np.asarray(jax.jit(walk)(q, arena, tbl, lens), np.float32)
        row = {"live_tokens": int(lengths.sum()), "live_pages": live,
               "copied_pages": copied, "chunks": chunks,
               "err": float(np.abs(got - want).max()),
               "walk_ms": median_ms(chained(walk, a.rank), q, arena, tbl,
                                    lens, reps=REPS),
               "reference_ms": median_ms(chained(ref, a.rank), q, arena,
                                         tbl, lens, reps=REPS)}
        # stored bytes (the padded row) and required bytes (576 values)
        row["walk_stored_roofline_pct"] = 100 * copied * a.block \
            * a.width * 2 / HBM / (row["walk_ms"] / 1e3)
        row["walk_required_roofline_pct"] = 100 * int(lengths.sum()) \
            * (a.rank + 64) * 2 / HBM / (row["walk_ms"] / 1e3)
        for n in a.chunk_rows:
            keep, pa._CHUNK_ROWS = pa._CHUNK_ROWS, n
            row[f"walk_ms.chunk_rows_{n}"] = median_ms(
                chained(functools.partial(
                    pa.mla_paged_attention_decode, scale=scale,
                    rank=a.rank), a.rank), q, arena, tbl, lens, reps=REPS)
            pa._CHUNK_ROWS = keep
        out["latent." + name] = row
        print("latent", name, json.dumps(row), flush=True)


def experts(a, out):
    e, h, ff, k = 128, 2048, 768, 6
    ws = [jax.random.normal(jax.random.PRNGKey(10 + i), s, jnp.bfloat16)
          * 0.03 for i, s in enumerate(((e, h, ff), (e, h, ff), (e, ff, h)))]
    for name, t in (("decode_64", 64), ("chunk_32", 32), ("rows_512", 512)):
        x = jax.random.normal(jax.random.PRNGKey(3), (t, h), jnp.bfloat16)
        idx = jnp.asarray(np.stack([
            np.random.RandomState(5 + i).permutation(e)[:k]
            for i in range(t)]), jnp.int32)
        w = jnp.full((t, k), 0.4, jnp.float32)
        hit = len(np.unique(np.asarray(idx)))

        def prog(x0, idx, w, *ws):
            def body(_, xx):
                y, _ = dropless_expert_mix(xx, idx, w, *ws)
                return (xx + 1e-3 * y).astype(xx.dtype)
            return jax.lax.fori_loop(0, 8, body, x0)
        ms = median_ms(jax.jit(prog), x, idx, w, *ws, reps=8)
        row = {"tokens": t, "experts_hit": hit, "ms": ms,
               "hit_bytes_roofline_pct":
               100 * hit * 3 * h * ff * 2 / HBM / (ms / 1e3)}
        out["experts." + name] = row
        print("experts", name, json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--rank", type=int, default=512)
    ap.add_argument("--width", type=int, default=640)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--table", type=int, default=320)
    ap.add_argument("--blocks", type=int, default=16385)
    ap.add_argument("--chunk-rows", type=int, nargs="*", default=[])
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("latent_moe_probe: needs a TPU (this is a "
                 f"{dev.platform}); nothing measured")
    out = {"device": dev.device_kind, "reps": REPS,
           "chunk_rows": pa._CHUNK_ROWS}
    latent(a, out)
    experts(a, out)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
