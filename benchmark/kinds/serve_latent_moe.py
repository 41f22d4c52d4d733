"""A serving cell of a model with a latent cache and routed experts,
measured with the SAME loop as the other serving cells.

``kinds/serve.py`` is loaded by path (``common.load_module`` returns a
private copy) and the names on it that know the model are replaced:

  ``weights``              a shim whose ``llama_config`` / ``build_lazy``
                           build the class the configuration file names
                           (``weights_by_class``)
  ``kernel_vs_reference``  the latent probe: ``mla_paged_attention_decode``
                           against the gathered read on the engine's own
                           arena and live tables
  ``build_engine``         wrapped, to keep the engine's handle
  ``Streams``              extended, to keep what each finished stream
                           emitted (the timed path's tokens)

then its ``run`` runs: build, compile warm-up, warm traffic, window, checks
are serve.py's, line for line. Around it this kind reads the engine's
counters when the window opens and after it closes (``drain_s`` is 0: no
tick runs after the window's last), keeps the loaded trace until the latent
kernel's device time is summed over its sites (``trace_kernels``), and adds
to ``correct``:

- the window's decode steps kept the slots full and the prefix index
  served the documents (the mix's two premises);
- on what the TIMED path produced: for two requests that completed inside
  the window, the plain reference's full forward over prompt + emitted
  tokens; every emitted token's reference logit must lie within
  ``EMITTED_MARGIN`` standard deviations (of that position's logits) of the
  reference's maximum there, and within ``EMITTED_MEAN_MARGIN`` on average.
"""
from __future__ import annotations

import math
import os
import shutil
import time
import types

import numpy as np

from benchmark import weights_by_class
from benchmark.common import load_module

# A greedy stream emits the largest of ITS logits. Those differ from the
# reference's by bf16 rounding (1.3% of the logits' rms with the picks held,
# part (b) of the set-up check) and, far more, by the picks themselves: one
# pick in twenty flips (part (a): 0.95 agree), so nearly every token meets a
# flipped pick in some layer, each swapping a sixth of that layer's routed
# output. On the chip (PERF.md section 6, PR 27; 18 requests of 399-525
# tokens) the emitted token is the reference's own largest at 0.76-0.83 of
# positions and lies 0.094-0.138 standard deviations (of that position's
# logits) below it on average, 1.68-3.44 at worst (mean 2.74, sd 0.46 over
# the requests). A wrong token (another slot's, a stale row, a dropped page)
# is a draw from the vocabulary: 4.3-4.4 below on average, 6.4-7.6 at worst
# (random tokens through the same check, on the chip). So two limits. The
# MEAN, four times the reading and a ninth of a wrong stream's, fails when an
# eighth of the tokens are wrong. EACH token: six of the readings' standard
# deviations above their mean (an extreme-value tail over the driver's dozens
# of runs, where one false `correct` refuses a PR, must stay under it) and
# under the least a wrong stream read.
EMITTED_MEAN_MARGIN = 0.5
EMITTED_MARGIN = 5.5
CHECKED_REQUESTS = 2
OCCUPANCY_FLOOR = 0.95
PREFIX_FLOOR = 0.8


def latent_probe(engine, seed: int, heads: int, *, rank: int, scale: float):
    """The s=1 Pallas latent read on the engine's OWN arena and live block
    tables against the gathered read, on the device. Returns (max |diff|
    over live decoding slots, how many)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    state, arena = engine._state, engine._cache[0]          # layer 0
    table, pos, live = state["table"], state["pos"], state["live"]
    live_np = np.asarray(live)
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (int(table.shape[0]), heads, int(arena.shape[-1])),
                          jnp.float32).astype(arena.dtype)
    lengths = jnp.maximum(pos, 1).astype(jnp.int32)
    got = jax.jit(lambda *a: pa.mla_paged_attention_decode(
        *a, scale=scale, rank=rank))(q, arena, table, lengths)
    ref = jax.jit(lambda *a: pa.mla_paged_attention_reference(
        *a, scale=scale, rank=rank))(q[:, None], arena, table, lengths)
    diff = np.abs(np.asarray(got, np.float32)
                  - np.asarray(ref[:, 0], np.float32))
    return float(diff[live_np].max()), int(live_np.sum())


def emitted_vs_reference(ref, params, cfg, prompt, tokens, say):
    """The reference's full forward over ``prompt + tokens``; for every
    emitted token the distance of its reference logit below the
    reference's maximum at that position, in that position's standard
    deviations. Returns (largest, mean)."""
    seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    at = np.arange(len(prompt) - 1, len(seq) - 1)      # rows that emit
    logits, _ = ref.forward(params, cfg, seq[:-1], logits_at=at, block=256)
    logits = np.asarray(logits, np.float32)
    chosen = logits[np.arange(len(at)), seq[len(prompt):]]
    below = (logits.max(-1) - chosen) / logits.std(-1)
    say(f"  timed path: {len(prompt)} prompt + {len(tokens)} emitted tokens; "
        f"emitted logit below the reference's largest: max {below.max():.3f} "
        f"sd, mean {below.mean():.3f} sd; the reference's own argmax at "
        f"{float(np.mean(below == 0)):.3f} of positions")
    return float(below.max()), float(below.mean())


def run(ctx) -> dict:
    serve = load_module("kinds", "serve.py")
    cfg, t_loaded, plain_say = ctx.config, time.perf_counter(), ctx.say
    # set-up here is minutes in a first run: every line says when
    ctx.say = say = lambda msg: plain_say(
        f"[{time.perf_counter() - t_loaded:6.1f} s] {msg}")
    kept = {}
    # ONE copy of the reference for the set-up check and the timed path's
    # (run.py's own copy is private to its closure): they share programs
    ref = load_module("reference", ctx.cell["reference"] + ".py")
    ctx.reference = lambda model: ref.check(model, ctx)

    serve.weights = types.SimpleNamespace(
        llama_config=weights_by_class.model_config,
        build_lazy=weights_by_class.build_lazy)
    serve.kernel_vs_reference = lambda engine, seed, heads: latent_probe(
        engine, seed, heads, rank=cfg["kv_lora_rank"],
        scale=1.0 / math.sqrt(cfg["qk_nope_head_dim"]
                              + cfg["qk_rope_head_dim"]))

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
            "steps", "prompt_tokens", "shared_tokens", "moe_picks",
            "moe_expert_hits", "moe_max_load", "kv_pages_live",
            "kv_pages_copied")}

    window_opens = ctx.window_opens

    def opens(t):
        kept["t0"], kept["c0"] = t, counters()
        window_opens(t)
    ctx.window_opens = opens

    def stop_trace(span_names):
        """``Context.stop_trace``, with the loaded trace kept long enough
        to sum the latent kernel's sites."""
        import jax
        from jax.profiler import ProfileData
        from benchmark import trace_kernels, trace_reduce
        ctx.trace_window_s = time.perf_counter() - ctx._trace_t0
        jax.profiler.stop_trace()
        loaded = trace_reduce.load(ProfileData.from_file(
            trace_reduce.find_xplane(ctx._trace_dir)), span_names)
        ctx.trace_summary = trace_reduce.reduce(loaded)
        ctx.kernel_seconds = trace_kernels.seconds_by_prefix(
            loaded, ("mla_paged_attention_decode",))
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
    held = (cfg.get("experts_held") or (0, cfg["n_routed_experts"]))[1]
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    steps = max(1, d["steps"])
    w = result["window"]
    w["prefix_hit_share"] = d["shared_tokens"] / max(1, d["prompt_tokens"])
    w["moe_expert_hits_per_step"] = d["moe_expert_hits"] / steps
    w["moe_experts_hit_share"] = d["moe_expert_hits"] \
        / (steps * moe_layers * held)
    say(f"window counters: decode steps {d['steps']}; routed picks "
        f"{d['moe_picks']} ({d['moe_picks'] / steps:.1f} a step); experts "
        f"hit {d['moe_expert_hits']} ({w['moe_experts_hit_share']:.4f} of "
        f"those held); largest load on one expert {c1['moe_max_load']}; "
        f"latent pages live / copied {d['kv_pages_live']} / "
        f"{d['kv_pages_copied']}; prompt tokens {d['prompt_tokens']}, "
        f"from the prefix index {d['shared_tokens']} "
        f"({w['prefix_hit_share']:.4f})")

    checks = result["checks"]
    checks[f"the window's decode steps kept the slots full: slot_occupancy "
           f"{w['slot_occupancy']:.4f} >= {OCCUPANCY_FLOOR}"] = \
        w["slot_occupancy"] >= OCCUPANCY_FLOOR
    checks[f"the prefix index served the documents: "
           f"{w['prefix_hit_share']:.4f} of the window's prompt tokens >= "
           f"{PREFIX_FLOOR}"] = w["prefix_hit_share"] >= PREFIX_FLOOR
    checks[f"the routers' picks were counted: {cfg['num_experts_per_tok']} a token a layer a step"] = \
        d["moe_picks"] == d["steps"] * kept["engine"].num_slots \
        * moe_layers * cfg["num_experts_per_tok"]

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
    # the window is over: give the arena back, so that the peak the run
    # reports is the serving path's and not this check's
    kept["engine"]._cache = kept["engine"]._state = None
    worst, mean = [], []
    for _, rid in ended[:CHECKED_REQUESTS]:
        w_, m_ = emitted_vs_reference(ref, params, cfg,
                                      streams.req[rid]["prompt"],
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
