"""How a serving cell is set up, warmed, measured and checked.

``Server`` over ``ContinuousBatchingEngine(paged=True)``, driven as
``chip_smoke.py`` drives it: one tick at a time from one thread
(``run_until_idle(max_ticks=1)``), submitting between ticks whatever has
fallen due on the WALL clock (``serving/loadgen.replay`` advances a tick
clock, so a slow system would be offered less load). Deliveries are read
from ``Server.stream_sink``: after every tick it is handed each live run's
whole token list, so a delivery is the new suffix.

Phases: build -> compile warm-up -> (open loop: the arrival process starts)
-> warm traffic -> WINDOW -> drain grace -> checks. The window opens and
closes on tick boundaries, and every rate is taken over the time between
those two boundaries.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmark.common import load_module, percentile
from benchmark import weights

SPANS = ("tick", "submit")


class Streams:
    """What the clients see: per request the due and submit times and every
    delivery (time, new tokens, gap since the stream's previous one)."""

    def __init__(self):
        self.req = {}            # rid -> {"due", "submit", "prompt", "new"}
        self.seen = {}           # rid -> tokens delivered so far
        self.first = {}          # rid -> time of the first delivery
        self.last = {}           # rid -> time of the latest delivery
        self.deliveries = []     # (t, n_new, gap_s or None)
        self.terminals = {}      # rid -> [t, "completed" | reason, ...]

    def sink(self, rid, tokens, done, failure):
        now = time.perf_counter()
        n = (len(tokens) if tokens is not None else 0) - self.seen.get(rid, 0)
        if n > 0:
            prev = self.last.get(rid)
            self.deliveries.append(
                (now, n, None if prev is None else now - prev))
            if prev is None:
                self.first[rid] = now
            self.last[rid] = now
            self.seen[rid] = len(tokens)
        if done:
            self.terminals.setdefault(rid, []).append(
                (now, failure or "completed"))


def build_engine(model, cfg: dict):
    from paddle_tpu.serving import ContinuousBatchingEngine
    dep = cfg["deployment"]
    args = dict(paged=True, num_slots=dep["num_slots"],
                max_len=dep["max_len"], num_blocks=dep["num_blocks"])
    # tunables stay at the program's defaults unless the configuration
    # pins one under "overrides" (with the refusal that forced it)
    args.update({k: v["value"] for k, v in cfg.get("overrides", {}).items()})
    return ContinuousBatchingEngine(model, **args)


def kernel_vs_reference(engine, seed: int, heads: int):
    """The s=1 Pallas read on the engine's OWN arena and live block tables
    against ``paged_gather`` + dense attention, on the device
    (chip_smoke.py's probe, copied). Returns (max |diff| over live
    decoding slots, how many)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    state, cache = engine._state, engine._cache
    k_arena, v_arena = cache[0], cache[1]            # layer 0
    table, pos, live = state["table"], state["pos"], state["live"]
    live_np = np.asarray(live)
    b, d = int(table.shape[0]), int(k_arena.shape[-1])
    q = jax.random.normal(jax.random.PRNGKey(seed), (b, heads, d),
                          jnp.float32).astype(k_arena.dtype)
    lengths = jnp.maximum(pos, 1).astype(jnp.int32)
    scale = 1.0 / math.sqrt(d)
    got = jax.jit(lambda *a: pa.paged_attention_decode(*a, scale=scale))(
        q, k_arena, v_arena, table, lengths)
    ref = jax.jit(lambda *a: pa.paged_attention_reference(
        *a, scale=scale))(q[:, None], k_arena, v_arena, table, lengths)
    diff = np.abs(np.asarray(got, np.float32)
                  - np.asarray(ref[:, 0], np.float32))
    return float(diff[live_np].max()), int(live_np.sum())


def run(ctx) -> dict:
    import jax
    from jax.profiler import TraceAnnotation
    from paddle_tpu.observability import ObservabilityConfig
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving import RequestFailure, Scheduler, Server

    cfg, cell, say = ctx.config, ctx.cell, ctx.say
    clock = time.perf_counter

    # -- build ------------------------------------------------------------
    t = clock()
    model = weights.build_lazy(weights.llama_config(cfg), ctx.seed)
    jax.block_until_ready([p._value for p in model.parameters()])
    ctx.split["weights_s"] = clock() - t
    t = clock()
    ref_checks = ctx.reference(model) if ctx.reference is not None else {}
    ctx.split["reference_s"] = clock() - t
    t = clock()
    engine = build_engine(model, cfg)
    srv = Server(engine, Scheduler(), observability=ObservabilityConfig(
        trace_requests=bool(ctx.trace)))
    streams = Streams()
    srv.stream_sink = streams.sink
    ctx.split["engine_s"] = clock() - t
    say(f"engine: {type(engine).__name__}; slots {engine.num_slots}, max_len "
        f"{engine.max_len}, decode_block {engine.decode_block}, kv block "
        f"{engine.kv_block_size}, prefill chunk {engine.prefill_chunk_len}, "
        f"arena blocks {engine.num_kv_blocks}, kv_int8 {engine.kv_int8}, "
        f"prefill_token_budget {srv.scheduler.prefill_token_budget}, "
        f"params {model.num_params() / 1e6:.1f}M")

    gen = load_module("traffic", cell["generator"] + ".py")
    traffic = gen.Traffic(cell["traffic"], ctx.seed, cfg["vocab_size"])
    say(f"traffic pool: {gen.describe(cell['traffic'])}")
    backlog = traffic.backlog_depth
    nxt = [0]                       # index of the next request to submit
    origin = [None]                 # when the arrival process started

    def submit(i, now, cap=None):
        r = traffic.request(i)
        new = r["max_new_tokens"] if cap is None \
            else min(r["max_new_tokens"], cap)
        offset = None if origin[0] is None else traffic.due(i)
        due = now if offset is None else origin[0] + offset
        rid = srv.submit(r["prompt"], max_new_tokens=new)
        streams.req[rid] = {"due": due, "submit": clock(),
                            "prompt": r["prompt"], "new": new}

    def submit_due(now):
        with TraceAnnotation("submit"):
            if backlog is not None:
                while srv.scheduler.pending() < backlog:
                    submit(nxt[0], now)
                    nxt[0] += 1
            elif origin[0] is not None:
                while origin[0] + traffic.due(nxt[0]) <= now:
                    submit(nxt[0], now)
                    nxt[0] += 1

    kv_live = []                    # live KV tokens, sampled every tick

    def tick():
        """Submit what is due, then one server tick, or sleep to the next
        due time when idle. Returns the clock after it."""
        now = clock()
        submit_due(now)
        if srv.scheduler.pending() or engine.has_live():
            with TraceAnnotation("tick"):
                srv.run_until_idle(max_ticks=1)
            kv_live.append(sum(
                len(run.request.prompt) + len(run.tokens)
                for slot, run in engine.live_runs()
                if slot not in engine._prefill_slots))
        else:
            nxt_due = origin[0] + traffic.due(nxt[0])
            time.sleep(max(0.0, min(nxt_due - clock(), 0.05)))
        return clock()

    # -- compile warm-up: every program this cell's traffic uses ------------
    t = clock()
    probe = None
    if backlog is None:             # open loop: a few requests, to idle
        warm_n = int(cell.get("compile_warm_requests", 4))
        for i in range(warm_n):     # short, drawn from far down the stream
            submit(10 ** 6 + i, clock(), cap=4 * engine.decode_block)
        while srv.scheduler.pending() or engine.has_live():
            tick()
            if probe is None and len(kv_live) and int(np.asarray(
                    engine._state["live"]).sum()) >= min(3, warm_n):
                probe = kernel_vs_reference(
                    engine, 0, cfg["num_attention_heads"])
    else:                           # backlog: the first wave fills the slots
        tick()
        probe = kernel_vs_reference(engine, 0, cfg["num_attention_heads"])
    route_ok = ctx.rehearse or pa._kernel_ok(engine._cache[0])
    ctx.split["compile_warm_s"] = clock() - t

    # -- warm traffic, then the window ----------------------------------
    t = clock()
    origin[0] = clock()
    warm_until = origin[0] + float(cell["warm_s"])
    now = clock()
    while now < warm_until:
        now = tick()
    ctx.split["warm_traffic_s"] = clock() - t

    def counters():
        return {"t": clock(), "ticks": len(srv.tick_seconds),
                "backlog": srv.scheduler.pending()
                + len(engine.live_runs()),
                "deliveries": len(streams.deliveries),
                "decode_tokens": engine.decode_tokens,
                "slot_steps": engine.slot_steps, "steps": engine.steps,
                "prefilled_tokens": engine.prefilled_tokens,
                "prefill_chunks": engine.prefill_chunks,
                "kv_live": len(kv_live), "meter": ctx.meter.snapshot()}

    c0 = counters()
    ctx.window_opens(c0["t"])
    t_end = c0["t"] + ctx.seconds
    trace_from = t_end - float(cell["trace_s"]) if ctx.trace else None
    while now < t_end:
        if trace_from is not None and now >= trace_from:
            ctx.start_trace()
            trace_from = None
        now = tick()
    c1 = counters()
    if ctx.trace:
        ctx.stop_trace(SPANS)
    window = (c0["t"], c1["t"])
    elapsed = c1["t"] - c0["t"]

    # -- drain grace: requests due in the window get their first token ------
    in_window = [rid for rid, r in streams.req.items()
                 if window[0] <= r["due"] < window[1]]
    grace_until = c1["t"] + float(cell.get("drain_s", 0))
    origin[0] = backlog = None      # the generator stops
    while clock() < grace_until and any(
            rid not in streams.first and rid not in streams.terminals
            for rid in in_window):
        srv.run_until_idle(max_ticks=1)
    t_drained = clock()

    # -- what the window held -----------------------------------------------
    deliv = streams.deliveries[c0["deliveries"]:c1["deliveries"]]
    out_tokens = sum(n for _, n, _ in deliv)
    gaps_ms = [g * 1e3 for _, _, g in deliv if g is not None]
    ticks_s = srv.tick_seconds[c0["ticks"]:c1["ticks"]]
    e2e = {"out_tokens_per_s": out_tokens / elapsed,
           "gap_ms_p95": percentile(gaps_ms, 95)}
    failures = {rid: v for rid, v in srv.results.items()
                if isinstance(v, RequestFailure)}
    if traffic.backlog_depth is None:
        ttft_ms, late_ms, no_first = [], [], 0
        for rid in in_window:
            r = streams.req[rid]
            first = streams.first.get(rid)
            if first is None:       # failed, shed or still waiting: worst
                no_first += 1
                first = t_drained
            ttft_ms.append((first - r["due"]) * 1e3)
            late_ms.append((r["submit"] - r["due"]) * 1e3)
        e2e["ttft_ms_p90"] = percentile(ttft_ms, 90)
        attempted, failed = len(in_window), no_first
        say(f"ttft: n {len(ttft_ms)}, p50 {percentile(ttft_ms, 50)} ms, "
            f"p90 {e2e['ttft_ms_p90']} ms, without a first token {no_first}")
    else:
        late_ms = []
        ended = [rid for rid, ts in streams.terminals.items()
                 if window[0] <= ts[0][0] < window[1]]
        attempted = len(ended)
        failed = sum(1 for rid in ended if rid in failures)
    say(f"window: {elapsed:.3f} s, {len(ticks_s)} ticks, {out_tokens} output "
        f"tokens in {len(deliv)} deliveries; gap n {len(gaps_ms)}, p50 "
        f"{percentile(gaps_ms, 50)} ms, p95 {e2e['gap_ms_p95']} ms; prefill "
        f"{c1['prefilled_tokens'] - c0['prefilled_tokens']} tokens in "
        f"{c1['prefill_chunks'] - c0['prefill_chunks']} chunks; decode steps "
        f"{c1['steps'] - c0['steps']}; submitted {len(streams.req)} requests; "
        f"queued + live at the window's start {c0['backlog']}, end "
        f"{c1['backlog']}; mean live KV tokens "
        f"{np.mean(kv_live[c0['kv_live']:c1['kv_live']] or [0]):.0f}")

    # -- correct -------------------------------------------------------------
    checks = {}
    done = {rid: v for rid, v in srv.results.items() if rid not in failures}
    checks["no request failed"] = not failures
    checks["each ended request has exactly one terminal"] = all(
        len(ts) == 1 for ts in streams.terminals.values()) and \
        set(streams.terminals) == set(srv.results)
    vocab = cfg["vocab_size"]
    checks["completed streams: asked length, prompt intact, ids in vocabulary"] \
        = bool(done) and all(
            len(v) == len(streams.req[rid]["prompt"]) + streams.req[rid]["new"]
            and np.array_equal(v[:len(streams.req[rid]["prompt"])],
                               streams.req[rid]["prompt"])
            and 0 <= int(v.min()) and int(v.max()) < vocab
            and streams.seen.get(rid) == streams.req[rid]["new"]
            for rid, v in done.items())
    checks["decode and prefill programs compiled once"] = \
        engine.decode_compile_count() == 1 \
        and engine.prefill_compile_count() == 1
    compiles = c1["meter"]["compiles"] - c0["meter"]["compiles"]
    checks[f"no compilation inside the window ({compiles})"] = compiles == 0
    err, n_live = probe or (math.nan, 0)
    checks[f"Pallas s=1 read vs paged_gather + dense attention on the "
           f"engine's arena: max |diff| {err:.3e} <= 2e-2 over {n_live} "
           "live slots"] = err <= 2e-2
    checks["the s=1 read routes to the Pallas kernel"] = bool(route_ok)
    if not ctx.rehearse:
        t = clock()
        be = engine.backend
        text = be._block_jit.lower(be._pv, be._bv, engine._cache,
                                   engine._state).as_text()
        checks[f"the decode program holds the Pallas call "
               f"({text.count('tpu_custom_call')} sites; lowered in "
               f"{clock() - t:.1f} s)"] = "tpu_custom_call" in text
    checks.update(ref_checks)

    queue_wait_ms = []
    if ctx.trace:
        for rid in in_window if traffic.backlog_depth is None else ():
            tr = srv.tracer.traces.get(rid)
            queue_wait_ms += [s["dur"] / 1e3 for s in (tr.spans if tr else ())
                              if s["name"] == "queue_wait"]
    kv = kv_live[c0["kv_live"]:c1["kv_live"]]
    return {
        "e2e": e2e, "attempted": attempted, "failed": failed,
        "checks": checks,
        "window": {
            "elapsed_s": elapsed, "tick_s": ticks_s, "gen_late_ms": late_ms,
            "queue_wait_ms": queue_wait_ms,
            "slot_occupancy": (c1["decode_tokens"] - c0["decode_tokens"])
            / max(1, c1["slot_steps"] - c0["slot_steps"]),
            "decode_block": engine.decode_block,
            "kv_live_tokens_mean": float(np.mean(kv)) if kv else 0.0,
            "decode_module": "jit_block_fn", "prefill_module": "jit_chunk_fn",
        },
    }
