"""A serving cell of a model whose paged cache carries a conv state under
each block id beside the keys and values of its attention layers, and whose
routed experts are all held, measured with the SAME loop as the other
serving cells.

As ``kinds/serve_latent_moe.py`` does, ``kinds/serve.py`` is loaded by path
(a private copy) and the names on it that know the model are replaced:

  ``weights``              a shim that builds the class the configuration
                           file names (``weights_by_class``)
  ``kernel_vs_reference``  the probe: ``paged_attention_decode`` over the
                           first attention layer's packed arena (``[v 64 |
                           k 64]`` rows) against the gathered read, on the
                           engine's own arena and live tables
  ``build_engine``         builds the engine and INGESTS THE PREFIXES (each
                           served once as a prompt of its own, one token
                           out: its retirement registers every block, conv
                           entries with them), so that every turn hits;
                           counted as set-up
  ``Streams``              extended, to keep what each finished stream
                           emitted (the timed path's tokens)

then its ``run`` runs. Around it this kind reads the engine's counters when
the window opens and after it closes, sums the device time under the scope
``short_conv`` (``trace_scopes``) in the traced decode blocks, and adds to
``correct``: the mix's two premises (every admission in the window starts
from a shared prefix block's conv state, and the slots stay full), the
routers' picks counted, both kernels in the decode block, and, on what the
TIMED path produced (prompt + emitted tokens of requests that completed
inside the window, 4.4k-5.6k tokens: past a prefix cut and eight
512-token chunk boundaries), the plain reference's full forward
(``serve_latent_moe``'s ``emitted_vs_reference``).
"""
from __future__ import annotations

import gc
import math
import os
import re
import shutil
import time
import types

import numpy as np

from benchmark import flops_conv_moe as flops
from benchmark import weights_by_class, weights_quantiles
from benchmark.common import load_module

# The timed path's limit (``serve_latent_moe`` explains the check): the
# emitted tokens' reference logits lie EMITTED_MEAN_MARGIN standard
# deviations (of each position's logits) below the reference's largest on
# average, the reference routing for itself. Readings on the chip (PERF.md
# section 6): the system's own tokens 0.047-0.086 (12 requests of
# 4,431-4,480 tokens in six runs, and 128 greedy tokens of
# ``tools/limits_probe.py``: 0.082), the reference in bf16 0.053, in 8-bit
# floats 0.376, without the selection bias 1.06, without QK-norm 0.233,
# random tokens 4.41; with the bias the quantiles of N(0, 0.05) in a
# seed's order (``init_quantiles``) the system's own 0.075-0.092 over seven
# runs, the 8-bit reference 0.367, no selection bias 0.976. The limit is
# over twice the largest sound reading and under the 8-bit reference's.
# The LARGEST emitted token's distance is logged and not held: its sound
# readings (1.15-2.21 sd) and the 8-bit reference's (2.27) leave no room
# for a limit between them. A conv state
# zeroed at the prefix cut reads 0.070 here: the conv remembers two tokens,
# so a turn's emitted tokens 64-512 positions past the cut do not see it;
# the set-up check's (d) reads the first token after the cut and fails it.
EMITTED_MEAN_MARGIN = 0.2
CHECKED_REQUESTS = 2
OCCUPANCY_FLOOR = 0.95
PREFIX_FLOOR = 0.85
KERNELS = ("paged_attention_decode", "moe_hit_experts_decode")
SCOPES = ("short_conv",)
SLOTS_A_CALL = 8


def packed_probe(engine, seed: int, cfg: dict):
    """The s=1 Pallas read on the engine's OWN first attention arena and
    live tables against the gathered read, on the device, at the model's
    query heads. Returns (max |diff| over the slots armed so far, how
    many)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    # the cache's first leaf is the first attention layer's arena
    state, arena = engine._state, engine._cache[0]
    table, pos = state["table"], state["pos"]
    live_np = np.asarray(pos) > 0
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, w = flops.head_dim(cfg), int(arena.shape[-1])
    lengths = jnp.maximum(pos, 1).astype(jnp.int32)
    q = jnp.pad(jax.random.normal(jax.random.PRNGKey(seed),
                                  (len(live_np), heads, d)),
                ((0, 0), (0, 0), (d, w - 2 * d))).astype(arena.dtype)
    kw = dict(scale=1.0 / math.sqrt(d), kvh=kvh, dv=d)
    got = jax.jit(lambda *a: pa.packed_paged_attention_decode(*a, **kw))(
        q, arena, table, lengths)
    read = jax.jit(lambda *a: pa.packed_paged_attention_reference(
        *a, **kw)[:, 0])
    ref = jnp.concatenate([
        read(q[i:i + SLOTS_A_CALL, None], arena, table[i:i + SLOTS_A_CALL],
             lengths[i:i + SLOTS_A_CALL])
        for i in range(0, len(live_np), SLOTS_A_CALL)])
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(ref, np.float32))
    return float(diff[live_np].max()), int(live_np.sum())


def ingest_prefixes(engine, cell: dict, seed: int, vocab: int, say):
    """Serve each shared prefix of the mix once as a prompt of its own, one
    token out: its retirement registers every block, so that every turn
    on it hits and starts from the conv state at the cut."""
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
    say(f"prefixes: {len(docs)} of {docs.shape[1]} tokens ingested in "
        f"{engine.prefill_chunks} chunks, {time.perf_counter() - t:.1f} s; "
        f"retained blocks {len(engine.manager._cached)}")


def run(ctx) -> dict:
    serve = load_module("kinds", "serve.py")
    latent = load_module("kinds", "serve_latent_moe.py")
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
        peak("the set-up checks (a)-(d)")
        return out
    ctx.reference = reference_check

    def build_lazy(c, seed):
        model = weights_by_class.build_lazy(c, seed)
        say(f"weights: {len(weights_quantiles.apply(model, cfg, seed))} "
            f"parameters set to a seed's order of a fixed set of values "
            f"(init_quantiles)")
        return model
    serve.weights = types.SimpleNamespace(
        llama_config=weights_by_class.model_config, build_lazy=build_lazy)
    serve.kernel_vs_reference = lambda engine, seed, heads: packed_probe(
        engine, seed, cfg)
    build_engine = serve.build_engine

    def build_and_ingest(model, c):
        kept["model"], kept["engine"] = model, build_engine(model, c)
        t = time.perf_counter()
        ingest_prefixes(kept["engine"], ctx.cell, ctx.seed, c["vocab_size"],
                        say)
        ctx.split["prefixes_s"] = time.perf_counter() - t
        peak("the engine's pool and the prefixes")
        return kept["engine"]
    serve.build_engine = build_and_ingest

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
        return {k: getattr(e, k, 0) for k in (
            "steps", "decode_tokens", "prompt_tokens", "shared_tokens",
            "moe_picks", "moe_expert_hits", "moe_max_load", "kv_pages_live",
            "kv_pages_copied", "kv_rows_live", "conv_warm_admissions",
            "conv_cold_admissions", "device_starved_ns", "sync_stalls",
            "sync_stall_ns")}

    window_opens = ctx.window_opens

    def opens(t):
        kept["t0"], kept["c0"] = t, counters()
        window_opens(t)
    ctx.window_opens = opens

    def stop_trace(span_names):
        """``Context.stop_trace``, with the profile kept long enough to sum
        the kernels' sites and the conv scope's operations."""
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
    moe_layers = flops.moe_layers(cfg)
    steps = max(1, d["steps"])
    w = result["window"]
    w["prefix_hit_share"] = d["shared_tokens"] / max(1, d["prompt_tokens"])
    w["moe_expert_hits_per_step"] = d["moe_expert_hits"] / steps
    w["moe_experts_hit_share"] = d["moe_expert_hits"] \
        / (steps * moe_layers * cfg["num_experts"])
    w["kv_rows_per_step"] = d["kv_rows_live"] / steps
    w["live_slots_per_step"] = d["decode_tokens"] / steps
    say(f"window counters: decode steps {d['steps']}, tokens "
        f"{d['decode_tokens']}; routed picks {d['moe_picks']}, experts hit "
        f"{d['moe_expert_hits']} ({w['moe_experts_hit_share']:.4f} of those "
        f"held a layer a step); largest load on one expert "
        f"{c1['moe_max_load']}; attention rows a step "
        f"{w['kv_rows_per_step']:.0f}, pages live / copied "
        f"{d['kv_pages_live']} / {d['kv_pages_copied']}; prompt tokens "
        f"{d['prompt_tokens']}, from the prefix index {d['shared_tokens']} "
        f"({w['prefix_hit_share']:.4f}); admissions from a prefix's conv "
        f"state / cold {d['conv_warm_admissions']} / "
        f"{d['conv_cold_admissions']}; evictions {engine.manager.evictions}")
    ticks = sorted(w["tick_s"], reverse=True)
    if ticks:
        say(f"ticks: {len(ticks)} in {w['elapsed_s']:.3f} s; median "
            f"{ticks[len(ticks) // 2] * 1e3:.1f} ms, the five longest "
            f"{[round(t * 1e3, 1) for t in ticks[:5]]} ms; the chip starved "
            f"{d['device_starved_ns'] / 1e6 / len(ticks):.3f} ms a tick; "
            f"stalled syncs {d['sync_stalls']} "
            f"({d['sync_stall_ns'] / 1e9:.3f} s beyond their median)")

    checks = result["checks"]
    checks[f"the window's decode steps kept the slots full: slot_occupancy "
           f"{w['slot_occupancy']:.4f} >= {OCCUPANCY_FLOOR}"] = \
        w["slot_occupancy"] >= OCCUPANCY_FLOOR
    checks[f"every admission in the window started from a shared prefix "
           f"block's conv state: {d['conv_warm_admissions']} from a prefix, "
           f"{d['conv_cold_admissions']} cold"] = \
        d["conv_warm_admissions"] > 0 and d["conv_cold_admissions"] == 0
    checks[f"the prefix index served the shared prefixes: "
           f"{w['prefix_hit_share']:.4f} of the window's prompt tokens >= "
           f"{PREFIX_FLOOR}"] = w["prefix_hit_share"] >= PREFIX_FLOOR
    checks[f"the routers' picks were counted: "
           f"{cfg['num_experts_per_tok']} a token a layer a step"] = \
        d["moe_picks"] == d["steps"] * engine.num_slots * moe_layers \
        * cfg["num_experts_per_tok"]
    engine.manager.assert_consistent()
    checks["the pool's block accounting is consistent"] = True
    if not ctx.rehearse:
        be = engine.backend
        text = be._block_jit.lower(be._pv, be._bv, engine._cache,
                                   engine._state).as_text()
        sites = {k: len(re.findall(rf"(?<![a-z_]){k}", text))
                 for k in KERNELS}
        checks[f"the lowered decode block holds both Pallas calls "
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
    # the window is over: give the pool back, so that the peak the run
    # reports is the serving path's and not this check's
    engine._cache = engine._state = None
    gc.collect()
    worst, mean, lengths = [], [], []
    for n, rid in ended[:CHECKED_REQUESTS]:
        w_, m_ = latent.emitted_vs_reference(
            ref, params, cfg, streams.req[rid]["prompt"],
            streams.emitted[rid], say)
        worst.append(w_)
        mean.append(m_)
        lengths.append(n)
    peak("the timed path's check")
    checks[f"timed path: the tokens {len(worst)} requests emitted inside the "
           f"window (contexts {lengths}) lie within {EMITTED_MEAN_MARGIN} sd "
           f"on average of the reference's largest logit (largest mean "
           f"{max(mean, default=math.nan):.3f} sd; the largest token's "
           f"{max(worst, default=math.nan):.3f} sd, not held; checked in "
           f"{time.perf_counter() - t:.1f} s)"] = \
        len(worst) == CHECKED_REQUESTS and max(mean) <= EMITTED_MEAN_MARGIN
    return result
