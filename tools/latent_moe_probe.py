#!/usr/bin/env python3
"""On-chip probe of the two mechanisms of a latent-cache, routed-expert
model, each alone, at the shape the ``kanana2-docqa-decode`` cell serves.
Needs a TPU.

    python3 tools/latent_moe_probe.py [--chunk-rows N ...]
        [--orders shuffled falling rising]
        [--experts-only | --latent-only | --chunks-only]

``latent``: the s = 1 read ``mla_paged_attention_decode`` (64 slots, 32
heads against one shared 640-wide row, KV block 16, a 320-entry table,
16,385 blocks, bf16) against the gathered read, per call, for three length
mixes and each table order of ``--orders``: ``shuffled`` (every slot's
blocks drawn at random from the pool: every page its own copy), ``falling``
(each slot's blocks consecutive and falling, as one allocation from a fresh
pool hands them out: aligned groups of 8 move as one copy) or ``rising``;
each row says what share of its live pages moved in run copies. Each timing
is REPS calls chained inside one program (a call's output feeds the next
call's q), median of 10 runs. ``experts``:
a decode step's 64 tokens through all 128 experts (2048 x 768 SwiGLU,
top-6) in three forms: the kernel ``moe_hit_experts_decode``, XLA's grouped
matmuls and every row on every expert, ms a layer and the share of the hit
experts' bytes, on the cell's router and on pools of 64, 100 and 128
experts. ``chunks``: a prefill chunk's 512 rows through the expert layer of
each MoE configuration the benchmark serves (its experts held, top-k,
hidden and ff widths), in two forms: the kernel
``moe_grouped_experts_prefill`` and XLA's grouped matmuls
(``ragged-dot``), ms a layer, the share of the hit experts' bytes and the
largest difference between the two; and the LFM2 shape with 412 pad rows
that all pick the same 4 experts. ``--experts-only`` skips the latent
read, ``--latent-only`` the experts and the chunks, ``--chunks-only``
runs the chunks alone. The last line is one JSON object.
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

from paddle_tpu.incubate.distributed.models import moe       # noqa: E402
from paddle_tpu.ops.pallas import moe_experts as me           # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa       # noqa: E402

REPS = 32
HBM = 819e9
# the cell's expert layer: 128 experts of 2048 x 768, top-6; a chunk's rows
EXPERTS, HIDDEN, FF, TOP_K, CHUNK = 128, 2048, 768, 6, 512
# each MoE configuration's expert layer as its cell holds it: (experts
# held, the router's experts, top-k, hidden, ff)
CHUNK_SHAPES = {
    "LFM2-24B-A2B": (64, 64, 4, 2048, 1536),
    "kanana-2-30b-a3b-instruct-2601": (128, 128, 6, 2048, 768),
    "Laguna-S-2.1": (128, 256, 10, 3072, 1024),
    "MiMo-V2-Flash": (16, 256, 8, 4096, 2048),
    "dots3-note-prev": (16, 256, 8, 5120, 1536),
}


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


def chained_mix(fn):
    """8 calls of an expert mix in one program: each call's output is added
    into the next call's rows, so no call can be dropped."""
    def prog(x0, idx, w, *ws):
        def body(_, xx):
            y, _ = fn(xx, idx, w, *ws)
            return (xx + 1e-3 * y).astype(xx.dtype)
        return jax.lax.fori_loop(0, 8, body, x0)
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

    pages = [(arena.shape[1:], arena.dtype)]
    # a checkout whose walk copies page by page has no run helpers: its
    # chunk is _pages_per_chunk's and its run share None
    ppc = pa._walk_chunk(a.table, pages)[0] if hasattr(pa, "_walk_chunk") \
        else pa._pages_per_chunk(a.table, pages)
    for (name, lengths), order in ((m, o) for m in mixes.items()
                                   for o in a.orders):
        blocks = {"shuffled": 1 + rs.permutation(a.blocks - 1),
                  "falling": np.arange(a.blocks - 1, 0, -1),
                  "rising": np.arange(1, a.blocks)}[order]
        tbl = np.zeros((a.slots, a.table), np.int32)
        o = 0
        for i, n_tok in enumerate(lengths):
            n = min(-(-int(n_tok) // a.block), a.table)
            tbl[i, :n] = blocks[(o + np.arange(n)) % len(blocks)]
            o += n
        in_runs = pa.walk_run_pages(pa.run_groups(tbl), lengths, a.block) \
            if hasattr(pa, "run_groups") else None
        tbl, lens = jnp.asarray(tbl), jnp.asarray(lengths, jnp.int32)
        live, copied, chunks = pa.walk_counts(lengths, a.table, a.block, ppc)
        want = np.asarray(jax.jit(ref)(q, arena, tbl, lens), np.float32)
        got = np.asarray(jax.jit(walk)(q, arena, tbl, lens), np.float32)
        row = {"order": order, "live_tokens": int(lengths.sum()),
               "live_pages": live, "copied_pages": copied, "chunks": chunks,
               "run_page_share": None if in_runs is None else in_runs / live,
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
        out[f"latent.{name}.{order}"] = row
        print("latent", name, order, json.dumps(row), flush=True)


def experts(a, out):
    """A decode step's expert layer at the cell's shape, three forms on the
    same routing: the kernel ``moe_hit_experts_decode`` (what a step on all
    the experts takes on a TPU), XLA's grouped matmuls (``ragged-dot``, the
    form before it; a prefill chunk's) and every row on every expert held
    (a share's form); and the grouped form at a chunk's 512 rows."""
    e, h, ff, k, t = EXPERTS, HIDDEN, FF, TOP_K, a.slots
    expert_bytes = 3 * h * ff * 2
    ws = [jax.random.normal(jax.random.PRNGKey(10 + i), s, jnp.bfloat16)
          * 0.03 for i, s in enumerate(((e, h, ff), (e, h, ff), (e, ff, h)))]
    x = jax.random.normal(jax.random.PRNGKey(3), (t, h), jnp.bfloat16)
    # the cell's router: a gate drawn as the model's (Xavier normal, 2048 ->
    # 128), a selection bias drawn N(0, 0.05), sigmoid scores, top-6,
    # normalised, x 2.448
    gate = jax.random.normal(jax.random.PRNGKey(4), (h, e)) \
        * np.sqrt(2.0 / (h + e))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (e,))
    idx, w = moe.route_topk(x.astype(jnp.float32) @ gate, bias, top_k=k,
                            scaling=2.448)
    routings = {"router": (idx, w)}
    # the same weights over pools of experts: the cell's routers hit 0.78 of
    # 128 (100 a layer), random rows through a fresh router more
    for pool in sorted({e // 2, e * 25 // 32, e}):
        rs = np.random.RandomState(5 + pool)
        held = rs.permutation(e)[:pool]
        routings[f"pool_{pool}"] = (jnp.asarray(np.stack(
            [rs.permutation(held)[:k] for _ in range(t)]), jnp.int32), w)

    forms = {
        "kernel": me.moe_hit_experts_decode,
        "grouped": lambda *v: moe._grouped_expert_mix(*v, 0, False),
        "every_expert": lambda *v: moe._every_row_on_every_expert_held(*v,
                                                                       0)}
    for name, (idx, w) in routings.items():
        hit = len(np.unique(np.asarray(idx)))
        row = {"tokens": t, "experts_hit": hit}
        want = np.asarray(forms["grouped"](x, idx, w, *ws)[0], np.float32)
        got = np.asarray(forms["kernel"](x, idx, w, *ws)[0], np.float32)
        row["kernel_vs_grouped_max_abs"] = float(np.abs(got - want).max())
        row["grouped_max_abs"] = float(np.abs(want).max())
        for form, fn in forms.items():
            ms = median_ms(chained_mix(fn), x, idx, w, *ws, reps=8)
            row[f"{form}_ms"] = ms
            row[f"{form}_hit_bytes_roofline_pct"] = \
                100 * hit * expert_bytes / HBM / (ms / 1e3)
        # the ff axis in tiles of half an expert (the budget at 384 lanes);
        # the tile is the kernel call's static argument, so ``chained``'s
        # fresh program traces and compiles the tiled kernel anew
        keep, me._WEIGHT_VMEM = me._WEIGHT_VMEM, 6 * h * (ff // 2) * 2
        row["half_expert_tile"] = me._ff_tile(h, ff, 2)
        row["kernel_ms.half_expert_tiles"] = median_ms(
            chained_mix(me.moe_hit_experts_decode), x, idx, w, *ws, reps=8)
        me._WEIGHT_VMEM = keep
        out["experts." + name] = row
        print("experts", name, json.dumps(row), flush=True)


def chunks(a, out):
    """A prefill chunk's expert layer at each configuration's shape, two
    forms on the same routing (random distinct picks over the router's
    experts; a share holds the first of them): the kernel
    ``moe_grouped_experts_prefill`` (what a chunk takes on a TPU) and XLA's
    grouped matmuls (the form before it, ``_grouped_expert_mix``)."""
    t = CHUNK
    cases = [(name, shape, None) for name, shape in CHUNK_SHAPES.items()]
    # pad columns: all the rows past a short prompt's pick the same experts
    cases.append(("LFM2-24B-A2B.pad_412", CHUNK_SHAPES["LFM2-24B-A2B"], 100))
    for name, (e, routed, k, h, ff), real in cases:
        ws = [jax.random.normal(jax.random.PRNGKey(10 + i), s, jnp.bfloat16)
              * 0.03 for i, s in enumerate(((e, h, ff), (e, h, ff),
                                             (e, ff, h)))]
        x = jax.random.normal(jax.random.PRNGKey(3), (t, h), jnp.bfloat16)
        rs = np.random.RandomState(5)
        idx = np.stack([rs.permutation(routed)[:k] for _ in range(t)])
        if real is not None:
            idx[real:] = np.arange(k)
            x = x.at[real:].set(x[real])
        idx = jnp.asarray(idx, jnp.int32)
        w = jnp.asarray(rs.uniform(0.1, 0.5, (t, k)), jnp.float32)
        partial = e < routed
        forms = {
            "kernel": me.moe_grouped_experts_prefill,
            "grouped": lambda *v, _p=partial: moe._grouped_expert_mix(
                *v, 0, _p)}
        held = np.asarray(idx)[np.asarray(idx) < e]
        hit = len(np.unique(held))
        expert_bytes = 3 * h * ff * 2
        want = np.asarray(forms["grouped"](x, idx, w, *ws)[0], np.float32)
        got = np.asarray(forms["kernel"](x, idx, w, *ws)[0], np.float32)
        row = {"tokens": t, "experts_held": e, "top_k": k, "hidden": h,
               "ff": ff, "picks_held": int(held.size), "experts_hit": hit,
               "largest_group": int(np.bincount(held).max()),
               "ff_tile": me._ff_tile(h, ff, 2),
               "kernel_vs_grouped_max_abs": float(np.abs(got - want).max()),
               "grouped_max_abs": float(np.abs(want).max())}
        for form, fn in forms.items():
            ms = median_ms(chained_mix(fn), x, idx, w, *ws, reps=8)
            row[f"{form}_ms"] = ms
            row[f"{form}_hit_bytes_roofline_pct"] = \
                100 * hit * expert_bytes / HBM / (ms / 1e3)
        out["chunks." + name] = row
        print("chunks", name, json.dumps(row), flush=True)
        del ws


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
    ap.add_argument("--orders", nargs="+", default=["shuffled"],
                    choices=["shuffled", "falling", "rising"])
    only = ap.add_mutually_exclusive_group()
    only.add_argument("--experts-only", action="store_true")
    only.add_argument("--latent-only", action="store_true")
    only.add_argument("--chunks-only", action="store_true")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("latent_moe_probe: needs a TPU (this is a "
                 f"{dev.platform}); nothing measured")
    out = {"device": dev.device_kind, "reps": REPS,
           "chunk_rows": pa._CHUNK_ROWS}
    if not (a.experts_only or a.chunks_only):
        latent(a, out)
    if not (a.latent_only or a.chunks_only):
        experts(a, out)
    if not a.latent_only:
        chunks(a, out)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
