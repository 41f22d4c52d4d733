"""A serving cell of a model whose full layers read a latent cache through
a learned indexer (scores over a second arena of the same blocks, an exact
top-k, a read of the selected rows only), whose sliding layers read a
second, wider latent cache through a ring, and whose routed experts are a
chip's share, measured with the SAME loop as the other serving cells.

As ``kinds/serve_hybrid_moe.py`` does, ``kinds/serve.py`` is loaded by path
(a private copy) and the names on it that know the model are replaced:

  ``weights``              a shim that builds the class the configuration
                           file names (``weights_by_class``), with the
                           router's published width and the share held
  ``kernel_vs_reference``  the probe: the THREE named kernels
                           (``dsa_index_scores_decode`` over a full layer's
                           key arena, ``dsa_sparse_mla_decode`` over its
                           latent rows at the ids an exact top-k of those
                           scores gives, ``swa_mla_paged_attention_decode``
                           over a sliding layer's ring) against their
                           gathered reads, on the engine's own arenas and
                           live tables
  ``build_engine``         builds the hybrid engine with both pools' sizes
                           and INGESTS THE DOCUMENTS: each of the mix's
                           shared prefixes is served once as a prompt of its
                           own (one token out), which leaves every block of
                           the full group and the window group's tail in the
                           prefix index, as a deployment that answers
                           questions of a few long documents holds them.
                           (Without it the first wave's 64 requests are
                           admitted in one tick, before any of them has
                           registered a block: two dozen cold prefills of
                           33k tokens.) Counted as set-up.
  ``Streams``              extended, to keep what each finished stream
                           emitted (the timed path's tokens)

then its ``run`` runs. Around it this kind reads the engine's counters when
the window opens and after it closes, sums the named kernels' device time
over their sites (``trace_kernels``) and the device time under the scopes
``dsa_select`` / ``dsa_read`` / ``dsa_index`` (``trace_scopes``), and adds
to ``correct``: the mix's two premises (slots full, prefixes served in BOTH
groups), the indexers' counters (every live context scored, ``index_topk``
rows read a full layer a token), the three Pallas calls in the lowered
decode block, and, on what the TIMED path produced (prompt + emitted tokens
of a request that completed inside the window), ``reference.timed_context``:
the sequence through the model's cache path at the deployment's chunk and
table width against the plain reference's full forward over it (logits and
selected set at the rows past the document), and the emitted tokens against
the same forward.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import time
import types

import numpy as np

from benchmark import flops_sparse_latent_moe as flops
from benchmark import weights_by_class
from benchmark.common import load_module

# The timed path's limits, on ONE request that completed inside the window
# (its prompt + what the engine emitted for it, 33.2k-34.4k tokens), through
# ``reference.timed_context``: the sequence teacher-forced through the
# model's cache path at the deployment's chunk and table width, against one
# forward of the reference over it.
#
# At the rows past the document (the question's chunk and the last 64 tokens,
# a decode step each): the logits' relative rms within the reference's
# ``TIMED_LOGITS_TOLERANCE`` and the share of the reference's selected set
# that the system selected at least ``TIMED_SELECTED_FLOOR``. This is where
# the indexer, the exact top-k over 36,864 scores and the selected rows' gather
# are held to the reference AT THE CONTEXT THE CELL IS TIMED AT, 16 times
# ``index_topk`` (the set-up checks stop at 8k). The readings and the broken
# variants that fail each limit: PERF.md section 6, PR 37
# (``tools/sparse_limits_probe.py --paths timed``).
#
# On what the engine itself emitted (64 slots at a time): each emitted
# token's reference logit within EMITTED_MARGIN standard deviations (of that
# position's logits) of the reference's largest, EMITTED_MEAN_MARGIN on
# average (``serve_latent_moe`` explains the check; the reference is held to
# the system's expert picks here). 25 requests over 25 seeds read 0.000-0.001
# sd on average, 0.031-0.317 at worst; a stream of
# random tokens 4.44 / 6.81, and ONE wrong token is such a draw. The mean
# fails when a seventeenth of the tokens are wrong. This statistic canNOT see
# the indexer (random weights attend diffusely: what a system with none would
# emit reads 0.0002 / 0.017): the two limits above are what holds it.
EMITTED_MEAN_MARGIN = 0.25
EMITTED_MARGIN = 2.0
CHECKED_REQUESTS = 1
OCCUPANCY_FLOOR = 0.95
PREFIX_FLOOR = 0.8
KERNELS = ("dsa_index_scores_decode", "dsa_sparse_mla_decode",
           "swa_mla_paged_attention_decode")
SCOPES = ("dsa_index", "dsa_select", "dsa_read")
# the reference's blocks at a 34k-token sequence: 64 query rows against
# every key for 4 heads at a time, the attention layer's program then 2.44
# GiB (compile-only, PR 37; 2.79 at 8 heads). The peak a run reports is the
# process's: with these the timed check reads 11.2-11.35 GiB where the
# serving path's own peak is 10.44 (logged by phase; PERF.md section 7)
REFERENCE_BLOCKS = dict(block=64, head_group=4)


def sparse_probe(engine, seed: int, cfg: dict):
    """The three s=1 Pallas reads on the engine's OWN arenas and live
    tables against their gathered reads, on the device. Returns (largest
    error over the slots armed so far and the three kernels, each relative
    to its reference's largest value; how many slots)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    state, cache, mb = engine._state, engine._cache, engine.max_blocks
    groups = engine.backend.leaf_group
    full = [a for a, g in zip(cache, groups) if g == flops.FULL]
    ring = [a for a, g in zip(cache, groups) if g == flops.WINDOW][0]
    rows, keys = full[0], full[1]                 # layer 0's pair
    live_np = np.asarray(state["pos"]) > 0
    lengths = jnp.maximum(state["pos"], 1).astype(jnp.int32)
    ftable, wtable = state["table"][:, :mb], state["table"][:, mb:]
    b = len(live_np)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    n, d = cfg["index_n_heads"], cfg["index_head_dim"]
    topk = min(cfg["index_topk"], mb * int(rows.shape[1]))

    def worst(got, want):
        got = np.asarray(got, np.float32)[live_np]
        want = np.asarray(want, np.float32)[live_np]
        return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-9))

    # the indexer's walk over the key arena
    qi = jax.random.normal(k1, (b, n, d)).astype(keys.dtype)
    wi = jax.random.normal(k2, (b, n)) / math.sqrt(n * d)
    seen = jnp.arange(mb * keys.shape[1])[None] < lengths[:, None]
    got = jnp.where(seen, jax.jit(pa.dsa_index_scores_decode)(
        qi, wi, keys, ftable, lengths), 0.0)
    want = jnp.where(seen, jax.jit(
        lambda *a: pa.dsa_index_scores_reference(*a, key_block=1024))(
        qi[:, None], wi[:, None], keys, ftable)[:, 0], 0.0)
    errs = [worst(got, want)]
    # the selected read at the ids those scores give
    ids = jax.lax.top_k(jnp.where(seen, want, -jnp.inf), topk)[1]
    n_valid = jnp.minimum(lengths, topk)
    ids = jnp.where(jnp.arange(topk)[None] < n_valid[:, None], ids, 0)
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    q = jax.random.normal(k3, (b, heads, int(rows.shape[-1]))).astype(
        rows.dtype)
    kw = dict(scale=1.0 / math.sqrt(cfg["qk_nope_head_dim"]
                                    + cfg["qk_rope_head_dim"]), rank=rank)
    got = jax.jit(lambda *a: pa.dsa_sparse_mla_decode(*a, **kw))(
        q, rows, ftable, ids, n_valid)
    want = jax.jit(lambda *a: pa.dsa_sparse_mla_reference(*a, **kw))(
        q[:, None], rows, ftable, ids[:, None], n_valid[:, None])[:, 0]
    errs.append(worst(got, want))
    # the windowed latent read over the ring
    heads, rank = cfg["swa_num_attention_heads"], cfg["swa_kv_lora_rank"]
    q = jax.random.normal(k4, (b, heads, int(ring.shape[-1]))).astype(
        ring.dtype)
    kw = dict(scale=1.0 / math.sqrt(cfg["swa_qk_nope_head_dim"]
                                    + cfg["swa_qk_rope_head_dim"]),
              rank=rank, window=int(cfg["sliding_window_size"]))
    got = jax.jit(lambda *a: pa.swa_mla_paged_attention_decode(*a, **kw))(
        q, ring, wtable, lengths)
    want = jax.jit(lambda *a: pa.mla_paged_attention_reference(*a, **kw))(
        q[:, None], ring, wtable, lengths)[:, 0]
    errs.append(worst(got, want))
    return max(errs), int(live_np.sum())


def ingest_documents(engine, cell: dict, seed: int, vocab: int, say):
    """Serve each shared prefix of the mix once as a prompt of its own, one
    token out: its retirement registers every block in the full group and
    the tail before its last block in the window group, so that every
    question of it hits in both."""
    from paddle_tpu.serving import Scheduler, Server
    gen = load_module("traffic", cell["generator"] + ".py")
    docs = gen.Traffic(cell["traffic"], seed, vocab)._prefixes
    if docs is None:
        return
    t = time.perf_counter()
    srv = Server(engine, Scheduler())
    rids = [srv.submit(np.asarray(d, np.int32), max_new_tokens=1)
            for d in docs]
    srv.run_until_idle()
    assert all(len(srv.results[r]) == len(d) + 1 for r, d in zip(rids, docs))
    say(f"documents: {len(docs)} of {docs.shape[1]} tokens ingested in "
        f"{engine.prefill_chunks} chunks, {time.perf_counter() - t:.1f} s; "
        f"retained blocks full / window {len(engine.manager._cached)} / "
        f"{len(engine.window_manager._cached)}")


def run(ctx) -> dict:
    serve = load_module("kinds", "serve.py")
    cfg, t_loaded, plain_say = ctx.config, time.perf_counter(), ctx.say
    ctx.say = say = lambda msg: plain_say(
        f"[{time.perf_counter() - t_loaded:6.1f} s] {msg}")
    kept = {}
    ref = load_module("reference", ctx.cell["reference"] + ".py")

    def peak(phase):
        """The process's peak device memory so far (it never falls: what
        ``hbm_peak_gib.serve`` reads is the largest phase's)."""
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            say(f"device memory after {phase}: peak "
                f"{stats['peak_bytes_in_use'] / 2 ** 30:.3f} GiB, in use "
                f"{stats.get('bytes_in_use', 0) / 2 ** 30:.3f} GiB")

    def reference_check(model):
        peak("the weights")
        out = ref.check(model, ctx)
        peak("the set-up checks (a), (b)")
        return out
    ctx.reference = reference_check

    serve.weights = types.SimpleNamespace(
        llama_config=lambda c: weights_by_class.model_config(
            c, n_routed_experts=flops.router_width(c),
            experts_held=tuple(c["experts_held"])),
        build_lazy=weights_by_class.build_lazy)
    serve.kernel_vs_reference = lambda engine, seed, heads: sparse_probe(
        engine, seed, cfg)

    def build_engine(model, c):
        from paddle_tpu.serving import ContinuousBatchingEngine
        dep = c["deployment"]
        args = dict(paged=True, num_slots=dep["num_slots"],
                    max_len=dep["max_len"], num_blocks=dep["num_blocks"],
                    window_blocks=dep["window_blocks"])
        args.update({k: v["value"] for k, v in c.get("overrides",
                                                     {}).items()})
        kept["model"] = model
        kept["engine"] = ContinuousBatchingEngine(model, **args)
        t = time.perf_counter()
        ingest_documents(kept["engine"], ctx.cell, ctx.seed, c["vocab_size"],
                         say)
        ctx.split["documents_s"] = time.perf_counter() - t
        peak("the engine's pools and the documents")
        return kept["engine"]
    serve.build_engine = build_engine

    class Streams(serve.Streams):
        def __init__(self):
            super().__init__()
            self.emitted = {}        # rid -> tokens, once it completed
            kept["streams"] = self

        def sink(self, rid, tokens, done, failure):
            super().sink(rid, tokens, done, failure)
            if done and not failure and tokens is not None:
                self.emitted[rid] = np.asarray(tokens, np.int32)
    serve.Streams = Streams

    def counters():
        e = kept["engine"]
        out = {k: getattr(e, k, 0) for k in (
            "steps", "decode_tokens", "prompt_tokens", "shared_tokens",
            "moe_picks", "moe_expert_hits", "moe_max_load",
            "dsa_tokens_scored", "dsa_tokens_selected", "kv_pages_live",
            "kv_pages_copied", "window_kv_pages_live",
            "window_kv_pages_copied", "kv_rows_live", "window_kv_rows_live")}
        blocks, tokens = e.window_kv_resident()
        out["resident"] = blocks * e.kv_block_size / max(1, tokens)
        return out

    window_opens = ctx.window_opens

    def opens(t):
        kept["t0"], kept["c0"] = t, counters()
        window_opens(t)
    ctx.window_opens = opens

    def stop_trace(span_names):
        """``Context.stop_trace``, with the profile kept long enough to sum
        the named kernels' sites and the scopes' operations."""
        import jax
        from jax.profiler import ProfileData
        from benchmark import trace_kernels, trace_reduce, trace_scopes
        ctx.trace_window_s = time.perf_counter() - ctx._trace_t0
        jax.profiler.stop_trace()
        loaded = trace_reduce.load(ProfileData.from_file(
            trace_reduce.find_xplane(ctx._trace_dir)), span_names)
        ctx.trace_summary = trace_reduce.reduce(loaded)
        ctx.kernel_seconds = trace_kernels.seconds_by_prefix(loaded, KERNELS)
        # the decode block's compiled text names each instruction's scope
        # (the programs are compiled: this loads it from the cache)
        e, be = kept["engine"], kept["engine"].backend
        text = be._block_jit.lower(be._pv, be._bv, e._cache,
                                   e._state).compile().as_text()
        ctx.scope_seconds = trace_scopes.seconds_by_scope(
            loaded, "jit_block_fn", text, SCOPES)
        say(f"trace: {ctx.trace_window_s:.3f} s traced; modules "
            f"{ctx.trace_summary.get('modules')}; kernels "
            f"{ctx.kernel_seconds}; scopes {ctx.scope_seconds}")
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(ctx._trace_dir, ignore_errors=True)
    ctx.stop_trace = stop_trace

    result = serve.run(ctx)
    peak("the window")

    # -- what the window held, from the engine's counters --------------------
    c0, c1 = kept["c0"], counters()
    d = {k: c1[k] - c0[k] for k in c1}
    engine = kept["engine"]
    held = flops.experts_held(cfg)
    moe_layers = sum(m for _, m in flops.layer_kinds(cfg))
    n_full = flops.layers_of(cfg, flops.FULL)
    steps = max(1, d["steps"])
    w = result["window"]
    w["slots"] = engine.num_slots * w["slot_occupancy"]
    w["prefix_hit_share"] = d["shared_tokens"] / max(1, d["prompt_tokens"])
    w["moe_expert_hits_per_step"] = d["moe_expert_hits"] / steps
    w["moe_experts_hit_share"] = d["moe_expert_hits"] \
        / (steps * moe_layers * held)
    w["kv_rows_per_step"] = d["kv_rows_live"] / steps
    w["window_kv_rows_per_step"] = d["window_kv_rows_live"] / steps
    w["dsa_selected_rows_per_step"] = d["dsa_tokens_selected"] / steps \
        / n_full
    w["dsa_selected_share"] = d["dsa_tokens_selected"] \
        / max(1, d["dsa_tokens_scored"])
    # when the window opened and after its last tick: the mean of the two
    w["swa_kv_resident_share"] = (c0["resident"] + c1["resident"]) / 2
    say(f"window counters: decode steps {d['steps']}, tokens "
        f"{d['decode_tokens']}; indexers scored {d['dsa_tokens_scored']} "
        f"tokens, reads attended to {d['dsa_tokens_selected']} "
        f"({w['dsa_selected_share']:.4f}); routed picks {d['moe_picks']}, on "
        f"experts held {d['moe_expert_hits']} hits "
        f"({w['moe_experts_hit_share']:.4f} of those held a layer a step); "
        f"full layers: rows a step {w['kv_rows_per_step']:.0f}, pages live / "
        f"copied {d['kv_pages_live']} / {d['kv_pages_copied']}; sliding "
        f"layers: rows a step {w['window_kv_rows_per_step']:.0f}, pages "
        f"live / copied {d['window_kv_pages_live']} / "
        f"{d['window_kv_pages_copied']}, resident share "
        f"{c0['resident']:.4f} -> {c1['resident']:.4f}; prompt tokens "
        f"{d['prompt_tokens']}, from the prefix index {d['shared_tokens']} "
        f"({w['prefix_hit_share']:.4f}); evictions full / window "
        f"{engine.manager.evictions} / {engine.window_manager.evictions}")

    ticks = sorted(w["tick_s"], reverse=True)
    say(f"ticks: {len(ticks)} in {w['elapsed_s']:.3f} s, their sum "
        f"{sum(ticks):.3f} s; median {ticks[len(ticks) // 2] * 1e3:.1f} ms, "
        f"the five longest {[round(t * 1e3, 1) for t in ticks[:5]]} ms; "
        f"stalled syncs {engine.sync_stalls} "
        f"({engine.sync_stall_ns / 1e9:.3f} s beyond their median, the "
        f"whole run)")
    checks = result["checks"]
    reasons = {}
    for ts in kept["streams"].terminals.values():
        reasons[ts[0][1]] = reasons.get(ts[0][1], 0) + 1
    say(f"terminals by reason: {reasons}")
    checks[f"the window's decode steps kept the slots full: slot_occupancy "
           f"{w['slot_occupancy']:.4f} >= {OCCUPANCY_FLOOR}"] = \
        w["slot_occupancy"] >= OCCUPANCY_FLOOR
    checks[f"the prefix index served the documents in both groups: "
           f"{w['prefix_hit_share']:.4f} of the window's prompt tokens >= "
           f"{PREFIX_FLOOR}"] = w["prefix_hit_share"] >= PREFIX_FLOOR
    topk = cfg["index_topk"]
    checks[f"every decoded token read index_topk rows a full layer: "
           f"dsa_tokens_selected {d['dsa_tokens_selected']} = {topk} x "
           f"{n_full} x {d['decode_tokens']} decoded tokens"] = \
        d["dsa_tokens_selected"] == topk * n_full * d["decode_tokens"] > 0
    checks[f"the indexers scored every live token: dsa_tokens_scored "
           f"{d['dsa_tokens_scored']} = {n_full} x the window's summed live "
           f"context {d['kv_rows_live']}"] = \
        d["dsa_tokens_scored"] == n_full * d["kv_rows_live"] > 0
    checks[f"the routers' picks were counted on the experts held: "
           f"{d['moe_picks']} made"] = \
        0 < d["moe_picks"] <= d["steps"] * engine.num_slots * moe_layers \
        * cfg["num_experts_per_tok"]
    engine.manager.assert_consistent()
    engine.window_manager.assert_consistent()
    checks["both pools' block accounting is consistent"] = True
    if not ctx.rehearse:
        be = engine.backend
        text = be._block_jit.lower(be._pv, be._bv, engine._cache,
                                   engine._state).as_text()
        sites = {k: text.count(k) for k in KERNELS}
        checks[f"the lowered decode block holds the three Pallas calls "
               f"{sites}"] = all(sites.values())

    # -- the timed path's own tokens against the reference -------------------
    t = time.perf_counter()
    streams = kept["streams"]
    t0, t1 = kept["t0"], kept["t0"] + w["elapsed_s"]
    ended = sorted((len(streams.req[rid]["prompt"])
                    + len(streams.emitted[rid]), rid)
                   for rid, ts in streams.terminals.items()
                   if t0 <= ts[0][0] < t1 and rid in streams.emitted)
    params = {k: p._value for k, p in kept["model"].named_parameters()}
    # the window is over: give the arenas back, so that the peak the run
    # reports is the serving path's and not this check's
    engine._cache = engine._state = None
    gc.collect()
    read = []
    blocks = dict(REFERENCE_BLOCKS, **ctx.cell.get("reference_blocks", {}))
    n_checked = int(ctx.cell.get("checked_requests", CHECKED_REQUESTS))
    shared = ctx.cell["traffic"].get("shared_prefix") or {}
    for _, rid in ended[:n_checked]:
        read.append(ref.timed_context(
            kept["model"], params, cfg, streams.req[rid]["prompt"],
            streams.emitted[rid], chunk=engine.prefill_chunk_len,
            table_len=engine.max_len, past=int(shared.get("len", 0)),
            say=say, **blocks))
    peak("the timed path's check")

    def worst(key, pick=max):
        return pick((r[key] for r in read), default=math.nan)
    checks[f"timed context: {len(read)} request(s) that completed inside the "
           f"window, teacher-forced through the cache path at the "
           f"deployment's chunk and table width, against the reference at "
           f"the rows past the document: logits relative rms "
           f"{worst('logits_err'):.4f} <= {ref.TIMED_LOGITS_TOLERANCE}, "
           f"selected share {worst('selected_share', min):.4f} >= "
           f"{ref.TIMED_SELECTED_FLOOR}"] = \
        len(read) == n_checked \
        and worst("logits_err") <= ref.TIMED_LOGITS_TOLERANCE \
        and worst("selected_share", min) >= ref.TIMED_SELECTED_FLOOR
    checks[f"timed path: the tokens the engine emitted for them lie within "
           f"{EMITTED_MARGIN} sd each, {EMITTED_MEAN_MARGIN} sd on average, "
           f"of the reference's largest logit (largest "
           f"{worst('below_max'):.3f} sd, largest mean "
           f"{worst('below_mean'):.3f} sd; both checked in "
           f"{time.perf_counter() - t:.1f} s)"] = \
        len(read) == n_checked and worst("below_max") <= EMITTED_MARGIN \
        and worst("below_mean") <= EMITTED_MEAN_MARGIN
    return result
