"""A serving cell of a model whose cache keeps two groups of grouped-query
layers (full and sliding-window attention, each kind with its own number
of query heads, a per-head output gate, YaRN on the full layers), and whose
routed experts are a chip's share beside one shared expert, measured with
the SAME loop as the other serving cells.

As ``kinds/serve_hybrid_moe.py`` does, ``kinds/serve.py`` is loaded by path
(a private copy) and the names on it that know the model are replaced:

  ``weights``              a shim that builds the class the configuration
                           file names (``weights_by_class``), with the
                           router's published width and the share held
  ``kernel_vs_reference``  the probe: BOTH named kernels
                           (``paged_attention_decode`` over a full layer's
                           packed arena at the full layers' query heads,
                           ``swa_paged_attention_decode`` over a sliding
                           layer's at the sliding layers', no sink) against
                           the gathered read, on the engine's own arenas
                           and live tables
  ``build_engine``         builds the hybrid engine with both pools' sizes
                           and INGESTS THE PREFIXES as
                           ``serve_sparse_latent_moe`` ingests its documents
                           (each served once as a prompt of its own, one
                           token out), so that every turn hits in both
                           groups; counted as set-up
  ``Streams``              extended, to keep what each finished stream
                           emitted (the timed path's tokens)

then its ``run`` runs. Around it this kind reads the engine's counters when
the window opens and after it closes, sums both kernels' device time over
their sites (``trace_kernels``) and the device time under the scope
``moe_experts`` (``trace_scopes``), and adds to ``correct``: the mix's two
premises (slots full, prefixes served in BOTH groups), the sliding layers'
bounded residency, the routers' picks counted, and, on what the TIMED path
produced (prompt + emitted tokens of requests that completed inside the
window, 8.3k-9.9k tokens: past YaRN's 8,192 original positions), the plain
reference's full forward (``serve_latent_moe``'s ``emitted_vs_reference``;
the limits here are this model's).
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

from benchmark import flops_gated_hybrid_moe as flops
from benchmark import weights_by_class
from benchmark.common import load_module

# The timed path's limit (``serve_latent_moe`` explains the check): the
# emitted tokens' reference logits lie EMITTED_MEAN_MARGIN standard
# deviations (of each position's logits) below the reference's largest on
# average, the reference routing for itself. Readings on the chip (PERF.md
# section 6, PR 39): the system's own tokens 0.002-0.010 (10 requests of
# 399-477 tokens, and 256 greedy tokens of ``tools/limits_probe.py``), the
# reference in 8-bit floats 0.083, random tokens 4.17. The LARGEST emitted
# token's distance is logged and not held: its sound readings (0.23-0.66 sd)
# and the 8-bit reference's (0.91) leave no room for a limit between them.
EMITTED_MEAN_MARGIN = 0.03
CHECKED_REQUESTS = 2
OCCUPANCY_FLOOR = 0.95
PREFIX_FLOOR = 0.9
RESIDENT_CEILING = 0.2
KERNELS = ("paged_attention_decode", "swa_paged_attention_decode")
SCOPES = ("moe_experts",)
SLOTS_A_CALL = 8


def gated_probe(engine, seed: int, cfg: dict):
    """Both s=1 Pallas reads on the engine's OWN arenas and live tables
    against the gathered read, on the device: the first full layer through
    the full group's table at its query heads, the first sliding layer
    through the window group's at its own, no sink. Returns (max |diff|
    over the slots armed so far and both kernels, how many slots)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    state, cache, mb = engine._state, engine._cache, engine.max_blocks
    pos, live_np = state["pos"], np.asarray(state["pos"]) > 0
    d, kvh = cfg["head_dim"], cfg["num_key_value_heads"]
    lengths = jnp.maximum(pos, 1).astype(jnp.int32)
    kinds = [k for k, _ in flops.layer_kinds(cfg)]
    worst = 0.0
    for kind in (flops.FULL, flops.WINDOW):
        layer = kinds.index(kind)
        arena = cache[layer]
        table = state["table"][:, mb:] if kind else state["table"][:, :mb]
        heads, w = flops.heads(cfg, layer), int(arena.shape[-1])
        q = jnp.pad(jax.random.normal(jax.random.PRNGKey(seed + kind),
                                      (len(live_np), heads, d)),
                    ((0, 0), (0, 0), (d, w - 2 * d))).astype(arena.dtype)
        kw = dict(scale=1.0 / math.sqrt(d), kvh=kvh, dv=d)
        if kind:
            kw["window"] = int(cfg["sliding_window"])
            got = jax.jit(lambda *a: pa.swa_paged_attention_decode(
                *a, None, **kw))(q, arena, table, lengths)
        else:
            got = jax.jit(lambda *a: pa.packed_paged_attention_decode(
                *a, **kw))(q, arena, table, lengths)
        # the gathered read SLOTS_A_CALL slots at a time: all 64 slots'
        # 640-column tables gathered at once (5.2 GB) do not fit beside
        # the weights and the pools
        read = jax.jit(lambda *a: pa.packed_paged_attention_reference(
            *a, **kw)[:, 0])
        ref = jnp.concatenate([
            read(q[i:i + SLOTS_A_CALL, None], arena,
                 table[i:i + SLOTS_A_CALL], lengths[i:i + SLOTS_A_CALL])
            for i in range(0, len(live_np), SLOTS_A_CALL)])
        diff = np.abs(np.asarray(got, np.float32)
                      - np.asarray(ref, np.float32))
        worst = max(worst, float(diff[live_np].max()))
    return worst, int(live_np.sum())


def run(ctx) -> dict:
    serve = load_module("kinds", "serve.py")
    latent = load_module("kinds", "serve_latent_moe.py")
    sparse = load_module("kinds", "serve_sparse_latent_moe.py")
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

    serve.weights = types.SimpleNamespace(
        llama_config=lambda c: weights_by_class.model_config(
            c, num_experts=flops.router_width(c),
            experts_held=tuple(c["experts_held"])),
        build_lazy=weights_by_class.build_lazy)
    serve.kernel_vs_reference = lambda engine, seed, heads: gated_probe(
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
        sparse.ingest_documents(kept["engine"], ctx.cell, ctx.seed,
                                c["vocab_size"], say)
        ctx.split["prefixes_s"] = time.perf_counter() - t
        peak("the engine's pools and the prefixes")
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
            "moe_picks", "moe_expert_hits", "moe_max_load", "kv_pages_live",
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
        both kernels' sites and the expert scope's operations."""
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
    moe_layers = flops.moe_layers(cfg)
    steps = max(1, d["steps"])
    w = result["window"]
    w["prefix_hit_share"] = d["shared_tokens"] / max(1, d["prompt_tokens"])
    w["moe_expert_hits_per_step"] = d["moe_expert_hits"] / steps
    w["moe_experts_hit_share"] = d["moe_expert_hits"] \
        / (steps * moe_layers * held)
    w["kv_rows_per_step"] = d["kv_rows_live"] / steps
    w["window_kv_rows_per_step"] = d["window_kv_rows_live"] / steps
    # when the window opened and after its last tick: the mean of the two
    w["swa_kv_resident_share"] = (c0["resident"] + c1["resident"]) / 2
    say(f"window counters: decode steps {d['steps']}, tokens "
        f"{d['decode_tokens']}; routed picks {d['moe_picks']}, on experts "
        f"held {d['moe_expert_hits']} hits ({w['moe_experts_hit_share']:.4f} "
        f"of those held a layer a step); largest load on one expert "
        f"{c1['moe_max_load']}; full layers: rows a step "
        f"{w['kv_rows_per_step']:.0f}, pages live / copied "
        f"{d['kv_pages_live']} / {d['kv_pages_copied']}; sliding layers: "
        f"rows a step {w['window_kv_rows_per_step']:.0f}, pages live / "
        f"copied {d['window_kv_pages_live']} / {d['window_kv_pages_copied']}, "
        f"resident share {c0['resident']:.4f} -> {c1['resident']:.4f}; "
        f"prompt tokens {d['prompt_tokens']}, from the prefix index "
        f"{d['shared_tokens']} ({w['prefix_hit_share']:.4f}); evictions "
        f"full / window {engine.manager.evictions} / "
        f"{engine.window_manager.evictions}")
    ticks = sorted(w["tick_s"], reverse=True)
    if ticks:
        say(f"ticks: {len(ticks)} in {w['elapsed_s']:.3f} s; median "
            f"{ticks[len(ticks) // 2] * 1e3:.1f} ms, the five longest "
            f"{[round(t * 1e3, 1) for t in ticks[:5]]} ms")

    checks = result["checks"]
    reasons = {}
    for ts in kept["streams"].terminals.values():
        reasons[ts[0][1]] = reasons.get(ts[0][1], 0) + 1
    say(f"terminals by reason: {reasons}")
    checks[f"the window's decode steps kept the slots full: slot_occupancy "
           f"{w['slot_occupancy']:.4f} >= {OCCUPANCY_FLOOR}"] = \
        w["slot_occupancy"] >= OCCUPANCY_FLOOR
    checks[f"the prefix index served the shared prefixes in both groups: "
           f"{w['prefix_hit_share']:.4f} of the window's prompt tokens >= "
           f"{PREFIX_FLOOR}"] = w["prefix_hit_share"] >= PREFIX_FLOOR
    checks[f"sliding layers keep a bounded share of the live tokens: "
           f"{w['swa_kv_resident_share']:.4f} <= {RESIDENT_CEILING}"] = \
        w["swa_kv_resident_share"] <= RESIDENT_CEILING
    made = d["steps"] * engine.num_slots * moe_layers \
        * cfg["num_experts_per_tok"]
    checks[f"the routers' picks were counted on the experts held: "
           f"{d['moe_picks']} of {made} made"] = 0 < d["moe_picks"] <= made
    engine.manager.assert_consistent()
    engine.window_manager.assert_consistent()
    checks["both pools' block accounting is consistent"] = True
    if not ctx.rehearse:
        be = engine.backend
        text = be._block_jit.lower(be._pv, be._bv, engine._cache,
                                   engine._state).as_text()
        sites = {k: len(re.findall(rf"(?<![a-z_]){k}", text))
                 for k in KERNELS}
        checks[f"the lowered decode block holds both Pallas reads "
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
