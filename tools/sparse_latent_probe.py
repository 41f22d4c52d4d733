#!/usr/bin/env python3
"""On-chip probe of the mechanisms of learned sparse attention over a
latent cache, each alone, at the shape the ``dots3-longdoc-decode`` cell
serves (64 slots at about 33.5k of context, KV block 16, a 2,304-entry
table, 49,153 blocks of latent rows 640 wide and index keys 128 wide; a ring
of 1,152-wide rows under a window of 513). Needs a TPU.

    python3 tools/sparse_latent_probe.py [--index-only]

Per call, median of 10 runs: the indexer's walk ``dsa_index_scores_decode``
against its gathered read, the exact selection ``lax.top_k`` (64 x 36,864 ->
2,048), the row gather, the Pallas latent step over the gathered rows
``dsa_sparse_mla_decode`` against the gathered read, the windowed walk
``swa_mla_paged_attention_decode`` against its gathered read; then the s =
512 forms a prefill chunk takes. Each with its required bytes over 819 GB/s.
Then a short profile of a program with the scopes ``dsa_select`` /
``dsa_read``, reduced by ``benchmark/trace_scopes.py`` with the program's
compiled text: what the metrics that read those scopes will find. The last line is one JSON object.
"""
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from paddle_tpu.ops.pallas import paged_attention as pa       # noqa: E402

HBM = 819e9
SLOTS, MB, BS, BLOCKS, WBLOCKS = 64, 2304, 16, 49153, 6401
TOPK, WINDOW = 2048, 513


def median_ms(j, *args, runs=10):
    jax.block_until_ready(j(*args))
    ts = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(j(*args))
        ts.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(ts))


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def main():
    out = {"device": jax.devices()[0].device_kind}
    rs = np.random.RandomState(3)
    bf = jnp.bfloat16
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    rows = jax.random.normal(k[0], (BLOCKS, BS, 640), bf)
    keys = jax.random.normal(k[1], (BLOCKS, BS, 128), bf)
    ring = jax.random.normal(k[2], (WBLOCKS, BS, 1152), bf)
    lens = jnp.asarray(rs.randint(32900, 34400, SLOTS), jnp.int32)
    # 4 documents of 2,048 blocks shared, each slot's own blocks after them
    table = np.zeros((SLOTS, MB), np.int32)
    for s in range(SLOTS):
        d = s % 4
        table[s, :2048] = 1 + d * 2048 + np.arange(2048)
        table[s, 2048:2160] = 1 + 8192 + s * 112 + np.arange(112)
    table = jnp.asarray(table)
    ring_cols = 1 + (np.arange(MB)[None] % 65) + 65 * np.arange(SLOTS)[:, None]
    wtable = jnp.asarray(ring_cols.astype(np.int32))
    live = float(np.asarray(lens).sum())

    # -- the indexer ------------------------------------------------------
    qi = jax.random.normal(k[3], (SLOTS, 64, 128), bf)
    wi = jax.random.normal(k[4], (SLOTS, 64)) / 90.5
    seen = jnp.arange(MB * BS)[None] < lens[:, None]
    idx = jax.jit(pa.dsa_index_scores_decode)
    ref = jax.jit(lambda *a: pa.dsa_index_scores_reference(
        a[0][:, None], a[1][:, None], *a[2:], key_block=1024)[:, 0])
    got = jnp.where(seen, idx(qi, wi, keys, table, lens), 0.0)
    want = jnp.where(seen, ref(qi, wi, keys, table), 0.0)
    ms = median_ms(idx, qi, wi, keys, table, lens)
    out["index"] = {"ms": ms, "gathered_ms": median_ms(ref, qi, wi, keys,
                                                       table),
                    "err": rel_err(got, want),
                    "roofline_pct": live * 256 / HBM / (ms / 1e3) * 100}
    # the same walk by the order the pool handed the blocks out: FALLING (a
    # fresh pool's free list popped from its end: what the cell's ingestion
    # gets), and SHUFFLED (a pool after churn: no run of 8, every page a
    # copy of its own; the cliff a deployment falls off, PERF.md section 7)
    for name, other in (
            ("falling", np.where(np.asarray(table) > 0,
                                 BLOCKS - np.asarray(table), 0)),
            ("shuffled", 1 + rs.permutation(BLOCKS - 1)[
                np.asarray(table) - 1])):
        other = jnp.asarray(other.astype(np.int32))
        ms = median_ms(idx, qi, wi, keys, other, lens)
        out["index"][name] = {
            "ms": ms, "roofline_pct": live * 256 / HBM / (ms / 1e3) * 100,
            "err": rel_err(jnp.where(seen, idx(qi, wi, keys, other, lens), 0),
                           jnp.where(seen, ref(qi, wi, keys, other), 0))}
    if "--index-only" in sys.argv:
        print(json.dumps(out))
        return
    # -- the selection ----------------------------------------------------
    scores = jnp.where(seen, want, -jnp.inf)
    topk = jax.jit(lambda s: jax.lax.top_k(s, TOPK)[1])
    out["select"] = {"ms": median_ms(topk, scores)}
    ids = topk(scores)
    n_valid = jnp.minimum(lens, TOPK)
    # -- the selected read ------------------------------------------------
    q = jax.random.normal(k[5], (SLOTS, 128, 640), bf)
    kw = dict(scale=192 ** -0.5, rank=512)
    gather = jax.jit(lambda a, t, i: pa.selected_rows(a, t, i))
    sparse = jax.jit(lambda *a: pa.dsa_sparse_mla_decode(*a, **kw))
    sref = jax.jit(lambda q, a, t, i, n: pa.dsa_sparse_mla_reference(
        q[:, None], a, t, i[:, None], n[:, None], **kw)[:, 0])
    g_ms = median_ms(gather, rows, table, ids)
    ms = median_ms(sparse, q, rows, table, ids, n_valid)
    out["sparse_read"] = {
        "gather_ms": g_ms, "ms": ms,
        "gathered_ms": median_ms(sref, q, rows, table, ids, n_valid),
        "err": rel_err(sparse(q, rows, table, ids, n_valid),
                       sref(q, rows, table, ids, n_valid)),
        "roofline_pct": SLOTS * TOPK * 1152 / HBM / (ms / 1e3) * 100}
    # -- the windowed latent read -----------------------------------------
    qw = jax.random.normal(k[6], (SLOTS, 64, 1152), bf)
    kw = dict(scale=256 ** -0.5, rank=1024, window=WINDOW)
    swa = jax.jit(lambda *a: pa.swa_mla_paged_attention_decode(*a, **kw))
    wref = jax.jit(lambda q, *a: pa.mla_paged_attention_reference(
        q[:, None], *a, **kw)[:, 0])
    ms = median_ms(swa, qw, ring, wtable, lens)
    out["window_read"] = {
        "ms": ms, "gathered_ms": median_ms(wref, qw, ring, wtable, lens),
        "err": rel_err(swa(qw, ring, wtable, lens),
                       wref(qw, ring, wtable, lens)),
        "roofline_pct": SLOTS * WINDOW * 2176 / HBM / (ms / 1e3) * 100}
    print(json.dumps(out), flush=True)
    # -- a prefill chunk's forms (one slot, 512 queries at 33k) -----------
    c = 512
    qc = jax.random.normal(k[7], (1, c, 64, 128), bf)
    wc = jax.random.normal(k[4], (1, c, 64)) / 90.5
    cref = jax.jit(lambda *a: pa.dsa_index_scores_reference(
        *a, key_block=1024))
    t1 = table[:1]
    sc = cref(qc, wc, keys, t1)
    posn = 33000 + jnp.arange(c)
    sc = jnp.where(jnp.arange(MB * BS)[None, None] <= posn[None, :, None],
                   sc, -jnp.inf)
    ctop = jax.jit(lambda s: jax.lax.top_k(s, TOPK)[1])
    cid = ctop(sc)
    qq = jax.random.normal(k[5], (1, c, 128, 640), bf)
    nv = jnp.full((1, c), TOPK, jnp.int32)
    cread = jax.jit(lambda *a: pa.dsa_sparse_mla_reference(
        *a, scale=192 ** -0.5, rank=512, q_block=128))
    out["chunk"] = {"index_ms": median_ms(cref, qc, wc, keys, t1, runs=5),
                    "select_ms": median_ms(ctop, sc, runs=5),
                    "sparse_read_ms": median_ms(cread, qq, rows, t1, cid, nv,
                                                runs=5)}
    # -- what trace_scopes finds ------------------------------------------
    from jax.profiler import ProfileData
    from benchmark import trace_reduce, trace_scopes

    def step(q, a, t, s, n):
        with jax.named_scope("dsa_select"):
            i = jax.lax.top_k(s, TOPK)[1]
        with jax.named_scope("dsa_read"):
            return pa.dsa_sparse_mla_decode(q, a, t, i, n, scale=0.07,
                                            rank=512)
    args = (q, rows, table, scores, n_valid)
    text = jax.jit(step).lower(*args).compile().as_text()
    step = jax.jit(step)
    jax.block_until_ready(step(*args))
    d = os.path.join("chiprun_out", "probe-trace")
    shutil.rmtree(d, ignore_errors=True)
    jax.profiler.start_trace(d)
    for _ in range(3):
        jax.block_until_ready(step(*args))
    jax.profiler.stop_trace()
    loaded = trace_reduce.load(ProfileData.from_file(
        trace_reduce.find_xplane(d)))
    out["scopes"] = trace_scopes.seconds_by_scope(
        loaded, "jit_step", text, ("dsa_select", "dsa_read"))
    out["modules"] = trace_reduce.reduce(loaded).get("modules")
    shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
