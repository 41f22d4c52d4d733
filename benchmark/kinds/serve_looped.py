"""A serving cell of a LOOPED model (one stack of layers run
``total_ut_steps`` times over the same weights, a key/value cache for every
pass), measured with the SAME loop as the other serving cells.

As ``kinds/serve_hybrid_moe.py`` does, ``kinds/serve.py`` is loaded by path
(a private copy) and the names on it that know the model are replaced:

  ``weights``              a shim that builds the class the configuration
                           file names (``weights_by_class``)
  ``kernel_vs_reference``  the probe: ``paged_attention_decode`` against the
                           gathered read on the engine's own arenas and live
                           tables, through the table of the FIRST pass and
                           of the LAST (the table plus ``u x num_blocks``)
  ``build_engine``         wrapped, to keep the engine's handle
  ``Streams``              extended, to keep what each finished stream
                           emitted (the timed path's tokens)

then its ``run`` runs. Around it this kind reads the engine's counters when
the window opens and after it closes, sums the kernel's device time over
its sites (``trace_kernels``), and adds to ``correct``: the mix's premise
(slots full), the passes the decode steps ran (``ut_steps`` = passes x
steps), the gate counted, each layer's Pallas read held ONCE in the decode
program (a loop in the program, not ``passes`` copies of the stack), and,
on what the TIMED path produced, the plain reference's full forward over
prompt + emitted tokens for two requests that completed inside the window
(``serve_latent_moe``'s ``emitted_vs_reference``, shared; the limits here
are this model's).
"""
from __future__ import annotations

import math
import os
import shutil
import time
import types

import numpy as np

from benchmark import flops_looped, weights_by_class
from benchmark.common import load_module

# The timed path's limits, as in ``serve_latent_moe`` (which explains the
# check): each emitted token's reference logit within EMITTED_MARGIN
# standard deviations (of that position's logits) of the reference's
# largest, EMITTED_MEAN_MARGIN on average. No routing here: the emitted
# token differs from the reference's own largest only where bf16 rounding
# reorders two near-equal logits. On the chip (PERF.md section 6, PR 33;
# 14 streams of 192 tokens over 14 seeds, and the served requests of the
# cell's own runs) the emitted token is the reference's own largest at
# 0.91-0.98 of positions and lies 0.0004-0.0024 sd below it on average,
# 0.033-0.084 at worst. A three-pass model (the same streams scored by a
# reference of three passes) reads 0.215-0.328 on average and 1.57-1.64 at
# worst; a wrong stream (random tokens) 4.08-4.12 and 7.6-7.8. Each token's
# limit is six times the worst reading and a third of a three-pass model's
# worst; the mean's is twenty times the worst reading and a quarter of a
# three-pass model's least.
EMITTED_MEAN_MARGIN = 0.05
EMITTED_MARGIN = 0.5
CHECKED_REQUESTS = 2
OCCUPANCY_FLOOR = 0.95
KERNEL = "paged_attention_decode"


def looped_probe(engine, seed: int, heads: int):
    """The s=1 Pallas read on the engine's OWN arenas (weight layer 0) and
    live tables against the gathered read, on the device, through the
    first pass's table and the last's. Returns (max |diff| over the slots
    armed so far and both passes, how many slots)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    state, cache = engine._state, engine._cache
    k_arena, v_arena = cache[0], cache[1]
    passes, blocks = engine.cache_passes, engine.num_kv_blocks
    # every slot armed so far, live or retired (a retired slot's row and
    # position stay in the state and both reads see the same arena)
    pos, armed = state["pos"], np.asarray(state["pos"]) > 0
    d = int(k_arena.shape[-1])
    lengths = jnp.maximum(pos, 1).astype(jnp.int32)
    scale, worst = 1.0 / math.sqrt(d), 0.0
    for u in sorted({0, passes - 1}):
        table = state["table"] + u * blocks
        q = jax.random.normal(jax.random.PRNGKey(seed + u),
                              (len(armed), heads, d),
                              jnp.float32).astype(k_arena.dtype)
        got = jax.jit(lambda *a: pa.paged_attention_decode(
            *a, scale=scale))(q, k_arena, v_arena, table, lengths)
        ref = jax.jit(lambda *a: pa.paged_attention_reference(
            *a, scale=scale))(q[:, None], k_arena, v_arena, table, lengths)
        diff = np.abs(np.asarray(got, np.float32)
                      - np.asarray(ref[:, 0], np.float32))
        worst = max(worst, float(diff[armed].max()))
    return worst, int(armed.sum())


def pallas_sites(jaxpr, name: str) -> int:
    """How many ``pallas_call`` equations of ``name`` a jaxpr holds,
    sub-jaxprs (jit, scan, ...) included, kernel bodies not."""
    n = 0
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == name
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    n += pallas_sites(sub, name)
    return n


def run(ctx) -> dict:
    serve = load_module("kinds", "serve.py")
    latent = load_module("kinds", "serve_latent_moe.py")
    cfg, t_loaded, plain_say = ctx.config, time.perf_counter(), ctx.say
    ctx.say = say = lambda msg: plain_say(
        f"[{time.perf_counter() - t_loaded:6.1f} s] {msg}")
    kept = {}
    ref = load_module("reference", ctx.cell["reference"] + ".py")
    ctx.reference = lambda model: ref.check(model, ctx)

    serve.weights = types.SimpleNamespace(
        llama_config=weights_by_class.model_config,
        build_lazy=weights_by_class.build_lazy)
    serve.kernel_vs_reference = looped_probe

    build_engine = serve.build_engine

    def keeping_engine(model, c):
        kept["model"], kept["engine"] = model, build_engine(model, c)
        return kept["engine"]
    serve.build_engine = keeping_engine

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
            "steps", "decode_tokens", "ut_steps", "ut_exit_step_milli",
            "prefill_ut_exit_step_milli", "kv_pages_live",
            "kv_pages_copied")}

    window_opens = ctx.window_opens

    def opens(t):
        kept["t0"], kept["c0"] = t, counters()
        window_opens(t)
    ctx.window_opens = opens

    def stop_trace(span_names):
        """``Context.stop_trace``, with the loaded trace kept long enough
        to sum the kernel's sites."""
        import jax
        from jax.profiler import ProfileData
        from benchmark import trace_kernels, trace_reduce
        ctx.trace_window_s = time.perf_counter() - ctx._trace_t0
        jax.profiler.stop_trace()
        loaded = trace_reduce.load(ProfileData.from_file(
            trace_reduce.find_xplane(ctx._trace_dir)), span_names)
        ctx.trace_summary = trace_reduce.reduce(loaded)
        ctx.kernel_seconds = trace_kernels.seconds_by_prefix(loaded,
                                                             (KERNEL,))
        say(f"trace: {ctx.trace_window_s:.3f} s traced; modules "
            f"{ctx.trace_summary.get('modules')}; kernels "
            f"{ctx.kernel_seconds}")
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(ctx._trace_dir, ignore_errors=True)
    ctx.stop_trace = stop_trace

    result = serve.run(ctx)

    # -- what the window held, from the engine's counters --------------------
    c0, c1 = kept["c0"], counters()
    d = {k: c1[k] - c0[k] for k in c1}
    engine = kept["engine"]
    passes = flops_looped.passes(cfg)
    w = result["window"]
    w["ut_steps_per_decode_step"] = d["ut_steps"] / max(1, d["steps"])
    w["ut_expected_exit_step"] = d["ut_exit_step_milli"] / 1000.0 \
        / max(1, d["decode_tokens"])
    say(f"window counters: decode steps {d['steps']}, passes run "
        f"{d['ut_steps']} ({w['ut_steps_per_decode_step']:.2f} a step); "
        f"cached-attention sites a step {engine.attn_sites}; decoded tokens "
        f"{d['decode_tokens']}, the gate's expected exit pass "
        f"{w['ut_expected_exit_step']:.4f} (prefill chunks' columns: "
        f"{d['prefill_ut_exit_step_milli']} milli); one site's pages live / "
        f"copied {d['kv_pages_live']} / {d['kv_pages_copied']}")

    checks = result["checks"]
    reasons = {}
    for ts in kept["streams"].terminals.values():
        reasons[ts[0][1]] = reasons.get(ts[0][1], 0) + 1
    say(f"terminals by reason: {reasons}")
    checks[f"the window's decode steps kept the slots full: slot_occupancy "
           f"{w['slot_occupancy']:.4f} >= {OCCUPANCY_FLOOR}"] = \
        w["slot_occupancy"] >= OCCUPANCY_FLOOR
    checks[f"every decode step ran all {passes} passes over "
           f"{cfg['num_hidden_layers']} layers: ut_steps {d['ut_steps']} = "
           f"{passes} x {d['steps']} steps, {engine.attn_sites} cached-"
           f"attention sites a step"] = \
        d["steps"] > 0 and d["ut_steps"] == passes * d["steps"] \
        and engine.attn_sites == flops_looped.cache_layers(cfg)
    checks[f"the exit gate was computed and counted: expected exit pass "
           f"{w['ut_expected_exit_step']:.4f} inside (0, {passes - 1})"] = \
        0.0 < w["ut_expected_exit_step"] < passes - 1
    if not ctx.rehearse:
        import jax
        t = time.perf_counter()
        be = engine.backend
        sites = pallas_sites(jax.make_jaxpr(be._block_jit)(
            be._pv, be._bv, engine._cache, engine._state), KERNEL)
        checks[f"the decode program holds each layer's Pallas read once, "
               f"the passes a loop in the program: {sites} sites of {KERNEL} "
               f"for {cfg['num_hidden_layers']} layers x {passes} passes "
               f"(traced in {time.perf_counter() - t:.1f} s)"] = \
            sites == cfg["num_hidden_layers"]
    engine.manager.assert_consistent()
    checks["the block pool's accounting is consistent"] = True

    # -- the timed path's own tokens against the reference -------------------
    t = time.perf_counter()
    streams = kept["streams"]
    t0, t1 = kept["t0"], kept["t0"] + w["elapsed_s"]
    # of those that completed inside the window, the shortest: the
    # reference's cost grows with the square of the length
    ended = sorted((len(streams.req[rid]["prompt"])
                    + len(streams.emitted[rid]), rid)
                   for rid, ts in streams.terminals.items()
                   if t0 <= ts[0][0] < t1 and rid in streams.emitted)
    params = {k: p._value for k, p in kept["model"].named_parameters()}
    # the window is over: give the arenas back, so that the peak the run
    # reports is the serving path's and not this check's
    engine._cache = engine._state = None
    worst, mean = [], []
    for _, rid in ended[:CHECKED_REQUESTS]:
        w_, m_ = latent.emitted_vs_reference(
            ref, params, cfg, streams.req[rid]["prompt"],
            streams.emitted[rid], say)
        worst.append(w_)
        mean.append(m_)
    checks[f"timed path: the tokens {len(worst)} requests emitted inside the "
           f"window lie within {EMITTED_MARGIN} sd each, {EMITTED_MEAN_MARGIN} "
           f"sd on average, of the reference's largest logit (largest "
           f"{max(worst, default=math.nan):.3f} sd, largest mean "
           f"{max(mean, default=math.nan):.3f} sd; checked in "
           f"{time.perf_counter() - t:.1f} s)"] = \
        len(worst) == CHECKED_REQUESTS and max(worst) <= EMITTED_MARGIN \
        and max(mean) <= EMITTED_MEAN_MARGIN
    return result
