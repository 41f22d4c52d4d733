#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that paddle_tpu starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of Llama-2-7B (hidden 4096, 32 heads x 128, ff 11008, vocab
32000; ``models.llama.llama_7b_config``), bf16, random weights from
``--seed``. Depth is the only cut and is printed.

  serve  ``Server`` over ``ContinuousBatchingEngine(model, paged=True)``:
         a dozen ragged requests (greedy + two seeded-sampled, some arriving
         mid-stream so admission, chunked prefill and decode interleave).
  train  ``TrainStep`` with AdamW in the bf16 params+moments setting, seq
         2048 batches fed through ``paddle.io.DataLoader`` with workers.

Both phases run in ONE process, one after the other; the first is freed
before the second. With no argument the script needs one TPU chip and fails
without one. ``--chips 4`` runs ONLY the sharded paths (tensor-parallel
paged serving on mp=4, one dp=2 x mp=2 train step) against their one-chip
twins in the same process. ``--tiny`` is the CPU rehearsal of the control
flow at toy widths (Pallas in interpret mode); it never reports ``"ok"``.

Last line of stdout on success, and nothing like it otherwise:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
import traceback

# The only device this script knows. An unknown kind is an error, never a
# default (Google Cloud "TPU v5e": 16 GB HBM per chip).
KNOWN_DEVICES = {"TPU v5 lite": {"name": "v5e", "hbm_bytes": 16 * 2 ** 30}}


@dataclasses.dataclass(frozen=True)
class Sizes:
    widths: dict            # LlamaConfig width fields — never cut
    serve_layers: int       # depth cut, serving on one chip
    tp_serve_layers: int    # depth cut, serving with --chips 4 (device 0
    #                         holds the one-chip twin AND its TP shard)
    train_layers: int       # depth cut, training
    slots: int
    max_len: int
    prompt_lo: int
    prompt_hi: int
    new_tokens: int
    prefill_chunk: int
    train_seq: int
    train_batch: int


FULL = Sizes(
    widths=dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                num_attention_heads=32, num_key_value_heads=32),
    serve_layers=12, tp_serve_layers=8, train_layers=4, slots=8,
    max_len=2048, prompt_lo=64,
    prompt_hi=1024, new_tokens=64, prefill_chunk=128, train_seq=2048,
    train_batch=2)
TINY = Sizes(
    widths=dict(vocab_size=512, hidden_size=128, intermediate_size=384,
                num_attention_heads=4, num_key_value_heads=4),
    serve_layers=2, tp_serve_layers=2, train_layers=2, slots=8,
    max_len=256, prompt_lo=8,
    prompt_hi=96, new_tokens=32, prefill_chunk=32, train_seq=128,
    train_batch=2)

KV_BLOCK = 16           # paged arena block (the engine's documented default)
DECODE_BLOCK = 8        # tokens per compiled decode dispatch
TRAIN_STEPS = 5
FULL_LAYERS = 32        # llama_7b_config depth the cut is taken from


def say(msg: str):
    print(msg, flush=True)


def check(cond, what: str):
    if not cond:
        raise AssertionError(what)
    say(f"  ok: {what}")


# ---------------------------------------------------------------------------
# compile accounting: seconds the backend spent compiling, cache hits
# ---------------------------------------------------------------------------

class CompileMeter:
    """Sums jax's own compile-duration events so compile seconds are
    reported apart from run seconds, and counts persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (time.perf_counter(), self.compile_s, self.compiles,
                self.cache_hits, self.cache_misses)

    def since(self, mark) -> dict:
        t0, c0, n0, h0, m0 = mark
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        return {"wall_s": round(wall, 2), "compile_s": round(comp, 2),
                "run_s": round(wall - comp, 2),
                "programs_compiled": self.compiles - n0,
                "cache_hits": self.cache_hits - h0,
                "cache_misses": self.cache_misses - m0}


# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------

def build_model(sizes: Sizes, layers: int, seed: int, dtype="bfloat16",
                **cfg_kw):
    """A bf16 Llama at ``sizes.widths`` and ``layers`` deep, random weights
    from ``seed``."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg_kw.setdefault("tensor_parallel", False)
    cfg = LlamaConfig(**sizes.widths, num_hidden_layers=layers,
                      max_position_embeddings=sizes.max_len,
                      dtype=dtype, **cfg_kw)
    paddle.seed(seed)
    paddle.set_default_dtype(dtype)
    t0 = time.perf_counter()
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        paddle.set_default_dtype("float32")
    say(f"  model: {layers} layers, {model.num_params() / 1e6:.1f}M "
        f"parameters, built in {time.perf_counter() - t0:.1f} s (weights are "
        "sampled on the host: nn.initializer._draw)")
    return model


def make_requests(sizes: Sizes, seed: int):
    """The smoke's traffic, from the seed: 12 prompts of prompt_lo..hi
    tokens (the longest spans several prefill chunks), 64 new tokens each,
    ten greedy and two seeded-sampled, plus a second copy of one greedy and
    one sampled request. ``arrival`` says how each reaches the server: an
    ``arrival_step`` tick (0 = queued before the first tick), or None =
    submitted live, after the server has been running."""
    import numpy as np
    rs = np.random.RandomState(seed)
    lens = rs.randint(sizes.prompt_lo, sizes.prompt_hi + 1, size=12)
    lens[0], lens[1] = sizes.prompt_hi, sizes.prompt_lo   # both extremes
    reqs = []
    for i, n in enumerate(lens):
        kw = {"max_new_tokens": sizes.new_tokens}
        if i in (3, 7):                       # the two sampled requests
            kw.update(temperature=0.8, top_k=50, seed=1000 + i)
        arrival = 0 if i < 6 else (i - 4 if i < 10 else None)
        reqs.append({"prompt": rs.randint(
            0, sizes.widths["vocab_size"], (int(n),)).astype(np.int32),
            "kw": kw, "arrival": arrival, "twin": None})
    for src in (2, 3):      # served twice: one greedy, one sampled
        reqs.append({"prompt": reqs[src]["prompt"], "kw": reqs[src]["kw"],
                     "arrival": None, "twin": src})
    return reqs


def hbm(dev) -> dict:
    return dev.memory_stats() or {}


def gib(n) -> str:
    return f"{n / 2 ** 30:.2f} GiB"


RELEASED_BELOW = 2 ** 30    # bytes a freed phase may leave on a device


def release(dev, label: str):
    """Drop every compiled program and dead buffer; the next phase must
    start from an (almost) empty device."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    used = hbm(dev).get("bytes_in_use")
    if used is None:
        say(f"  {label}: memory_stats() unavailable on this backend")
        return
    say(f"  {label}: {gib(used)} still in use after release")
    check(used < RELEASED_BELOW, f"{label}: device memory released "
          f"(< {gib(RELEASED_BELOW)})")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def build_engine(model, sizes: Sizes, tp=False):
    from paddle_tpu.serving import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(
        model, num_slots=sizes.slots, max_len=sizes.max_len,
        decode_block=DECODE_BLOCK, paged=True, block_size=KV_BLOCK,
        prefill_chunk=sizes.prefill_chunk, tp=tp)
    say(f"  engine: {type(engine).__name__} over "
        f"{type(engine.backend).__name__}; slots {engine.num_slots}, "
        f"max_len {engine.max_len}, decode_block {engine.decode_block}, "
        f"kv block {engine.kv_block_size} (source: explicit argument), "
        f"prefill chunk {engine.prefill_chunk_len} (source: explicit "
        f"argument), {engine.num_kv_blocks} arena blocks, kv_int8 "
        f"{engine.kv_int8}, weight quant {engine.backend.quant_cfg}, "
        f"tp degree {engine.tp_degree()}")
    return engine


def run_server(engine, reqs, sizes: Sizes, probe=None):
    """Serve ``reqs``; returns (server, rids, probed). ``probe(engine)``
    runs once mid-stream, between two ticks, while slots are decoding;
    ``probed`` is what it returned (None if that moment never came)."""
    import numpy as np
    from paddle_tpu.observability import ObservabilityConfig
    from paddle_tpu.serving import Scheduler, Server
    srv = Server(
        engine,
        Scheduler(prefill_token_budget=4 * sizes.prefill_chunk),
        observability=ObservabilityConfig(trace_requests=True))
    rids = [None if r["arrival"] is None else
            srv.submit(r["prompt"], arrival_step=r["arrival"], **r["kw"])
            for r in reqs]
    probed = None
    while probe is not None and (srv.scheduler.pending()
                                 or engine.has_live()):
        srv.run_until_idle(max_ticks=1)
        if int(np.asarray(engine._state["live"]).sum()) >= 3:
            probed = probe(engine)
            break
    for i, r in enumerate(reqs):
        if r["arrival"] is None:
            rids[i] = srv.submit(r["prompt"], **r["kw"])
    srv.run_until_idle(max_ticks=20000)
    return srv, rids, probed


def kernel_vs_reference(engine, seed: int, heads: int, device=None):
    """The s=1 Pallas read on the engine's OWN arena and live block tables
    against ``paged_gather`` + dense attention, on the device. Returns
    (max |diff| over live slots, live slot count)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import paged_attention as pa
    state, cache = engine._state, engine._cache
    k_arena, v_arena = cache[0], cache[1]            # layer 0
    table, pos, live = state["table"], state["pos"], state["live"]
    if device is not None:       # sharded engine: bring one copy together
        k_arena, v_arena, table, pos, live = jax.device_put(
            (k_arena, v_arena, table, pos, live), device)
    else:
        check(pa._kernel_ok(k_arena), "the s=1 read routes to the Pallas "
              f"kernel ({k_arena.dtype} arena, dispatch gate open)")
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
    check(np.isfinite(diff[live_np]).all(), "kernel output finite on "
          "live slots")
    return float(diff[live_np].max()), int(live_np.sum())


def decode_program_text(engine) -> str:
    """HLO text of the compiled decode-block program the engine runs
    (re-lowered from its own jit on its own arguments; with the persistent
    cache on this is a cache read, not a second compile)."""
    be = engine.backend
    return be._block_jit.lower(be._pv, be._bv, engine._cache,
                               engine._state).compile().as_text()


def check_streams(srv, rids, reqs, sizes: Sizes):
    import numpy as np
    from paddle_tpu.serving import RequestFailure
    res = srv.results
    check(all(r is not None for r in rids) and len(set(rids)) == len(reqs),
          f"all {len(reqs)} requests were submitted under distinct ids")
    bad = {rid: res.get(rid) for rid in rids
           if rid not in res or isinstance(res[rid], RequestFailure)}
    check(not bad, f"every request completed (failures: {bad})")
    for rid, r in zip(rids, reqs):
        want = len(r["prompt"]) + sizes.new_tokens
        if len(res[rid]) != want or \
                not np.array_equal(res[rid][:len(r["prompt"])], r["prompt"]):
            raise AssertionError(f"request {rid}: got {len(res[rid])} "
                                 f"tokens, asked {want}")
    check(True, f"every request returned prompt + {sizes.new_tokens} new "
          "tokens")
    terms = srv.tracer.terminal_states()
    check(all(terms.get(rid) == ["completed"] for rid in rids),
          "every request ended in exactly one terminal ('completed')")
    vocab = sizes.widths["vocab_size"]
    check(all(0 <= int(res[rid].min()) and int(res[rid].max()) < vocab
              for rid in rids), "every token id is inside the vocabulary")
    for i, r in enumerate(reqs):
        if r["twin"] is not None:
            kind = "sampled" if "temperature" in r["kw"] else "greedy"
            check(np.array_equal(res[rids[i]], res[rids[r["twin"]]]),
                  f"the {kind} request served twice gave the same tokens")


def serve_phase(args, sizes: Sizes, meter, dev):
    import numpy as np
    say(f"== serve: paged continuous batching, {sizes.serve_layers} of "
        f"{FULL_LAYERS} layers (depth cut), widths {sizes.widths}")
    mark = meter.mark()
    model = build_model(sizes, sizes.serve_layers, args.seed)
    engine = build_engine(model, sizes)
    reqs = make_requests(sizes, args.seed)
    srv, rids, probed = run_server(
        engine, reqs, sizes, lambda eng: kernel_vs_reference(
            eng, args.seed, sizes.widths["num_attention_heads"]))
    check_streams(srv, rids, reqs, sizes)
    check(engine.decode_compile_count() == 1,
          "decode_compile_count() == 1")
    check(engine.prefill_compile_count() == 1,
          "prefill_compile_count() == 1")
    engine.manager.assert_consistent()
    check(engine.free_slot_count() == engine.num_slots,
          "every slot is free and the block arena is consistent")
    check(engine.prefill_chunks > len(reqs),
          f"prefill ran in chunks ({engine.prefill_chunks} chunk "
          f"dispatches for {len(reqs)} requests) interleaved with "
          f"{engine.steps} decode steps")
    err, live = probed or (math.nan, 0)
    check(err <= 2e-2,
          f"Pallas s=1 read vs paged_gather+dense attention on the "
          f"engine's arena: max |diff| {err:.3e} <= 2e-2 over {live} live "
          "slots")
    stats = srv.stats()
    timing = meter.since(mark)
    # informational: generate() on one short request (random weights make
    # logit margins tiny, so agreement is reported, never required)
    short = min(range(12), key=lambda i: len(reqs[i]["prompt"]))
    import paddle_tpu as paddle
    gmark = meter.mark()
    out = model.generate(paddle.to_tensor(reqs[short]["prompt"][None]),
                         max_new_tokens=sizes.new_tokens)
    gen = np.asarray(out.numpy())[0]
    agree = float(np.mean(gen[-sizes.new_tokens:]
                          == srv.results[rids[short]][-sizes.new_tokens:]))
    say(f"  info: token agreement with model.generate() on request "
        f"{rids[short]} ({len(reqs[short]['prompt'])}-token prompt): "
        f"{agree:.3f} ({meter.since(gmark)})")
    if not args.tiny:
        text = decode_program_text(engine)
        check("tpu_custom_call" in text,
              "the compiled decode program contains the Pallas paged-"
              f"attention call ({text.count('tpu_custom_call')} "
              "tpu_custom_call sites)")
    say(f"  serve: {stats['requests_completed']} requests, "
        f"{stats['tokens_emitted']} tokens, {stats['decode_steps']} decode "
        f"steps, slot occupancy {stats['slot_occupancy']}, prefix hit rate "
        f"{stats.get('prefix_cache_hit_rate')}, ttft p50 "
        f"{stats['ttft_p50_s']} s, max tick {stats['max_tick_s']} s "
        "(smoke wall clock, first ticks include compilation; not a "
        "benchmark)")
    say(f"  serve timing: {timing}")
    peak = hbm(dev).get("peak_bytes_in_use")
    if peak is not None:
        say(f"  serve peak HBM: {gib(peak)} (memory_stats peak_bytes_in_use)")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_token_set(sizes: Sizes, seed: int, n: int):
    import numpy as np
    rs = np.random.RandomState(seed + 1)
    return rs.randint(0, sizes.widths["vocab_size"],
                      (n, sizes.train_seq + 1)).astype(np.int32)


def build_trainer(model):
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    # bf16 params AND bf16 Adam moments (multi_precision off): what
    # fits the largest one-chip config in 16 GB
    opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                          parameters=model.parameters(),
                          multi_precision=False)

    def loss_fn(m, batch):
        ids, labels = batch
        loss, _ = m(ids, labels)
        return loss

    return TrainStep(model, loss_fn, opt)


def train_phase(args, sizes: Sizes, meter, dev):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import flash_attention as fa
    say(f"== train: TrainStep + AdamW (bf16 params and moments), "
        f"{sizes.train_layers} of {FULL_LAYERS} layers (depth cut), batch "
        f"{sizes.train_batch} x seq {sizes.train_seq}")
    mark = meter.mark()
    model = build_model(sizes, sizes.train_layers, args.seed,
                        recompute=True, scan_layers=True)
    step = build_trainer(model)
    tokens = make_token_set(sizes, args.seed, 2 * sizes.train_batch)

    class FixedTokens(paddle.io.Dataset):
        def __len__(self):
            return len(tokens)

        def __getitem__(self, i):
            return tokens[i, :-1], tokens[i, 1:]

    # forked workers started from a parent that holds the chip: they read
    # numpy rows and never touch jax (io.DataLoader collates in the parent)
    loader = paddle.io.DataLoader(FixedTokens(), batch_size=sizes.train_batch,
                                  shuffle=False, num_workers=2, timeout=120)
    check(loader._shm_usable(), "DataLoader uses forked workers over the "
          "native shared-memory ring")
    losses = []
    while len(losses) < TRAIN_STEPS:
        for batch in loader:
            losses.append(float(step(tuple(batch)).item()))
            if len(losses) == TRAIN_STEPS:
                break
    say(f"  losses: {[round(x, 4) for x in losses]} "
        f"(steps 1 and {TRAIN_STEPS} see the same batch)")
    target = math.log(sizes.widths["vocab_size"])
    check(all(math.isfinite(x) for x in losses), "all losses finite")
    check(abs(losses[0] - target) <= 0.5,
          f"first loss {losses[0]:.3f} within 0.5 of ln(vocab) = "
          f"{target:.3f}")
    check(losses[-1] < losses[0], "last loss below first")
    route = fa.sdpa_last_dispatch()
    say(f"  sdpa route: {route}; blocks {fa.last_block_choice()}")
    if not args.tiny:
        check(route in ("jax_flash", "splash", "fused_flash"),
              f"training attention ran a Pallas route ({route}), not 'xla'")
    timing = meter.since(mark)
    say(f"  train timing: {timing}")
    peak = hbm(dev).get("peak_bytes_in_use")
    if peak is not None:
        say(f"  peak HBM after train: {gib(peak)} (process peak, "
            "memory_stats peak_bytes_in_use)")


# ---------------------------------------------------------------------------
# four chips: the sharded paths against their one-chip twins
# ---------------------------------------------------------------------------

def spread(arr, n: int, what: str):
    """``arr`` lives as one shard on each of ``n`` devices."""
    devs = {s.device for s in arr.addressable_shards}
    shapes = {tuple(s.data.shape) for s in arr.addressable_shards}
    check(len(devs) == n, f"{what}: {tuple(arr.shape)} spread over "
          f"{len(devs)} devices as shards {sorted(shapes)}")
    return shapes


def serve_tp_phase(args, sizes: Sizes, meter, devices):
    import numpy as np
    from paddle_tpu.distributed.mesh import (build_device_mesh,
                                             set_current_mesh)
    from paddle_tpu.serving import TPConfig
    say(f"== serve on 4 chips: tp=TPConfig() over mp=4 against the one-chip "
        f"engine, {sizes.tp_serve_layers} of {FULL_LAYERS} layers")
    mark = meter.mark()
    mesh = build_device_mesh({"mp": 4}, devices)
    set_current_mesh(mesh)
    say(f"  mesh {dict(mesh.shape)}; device order "
        f"{[d.id for d in mesh.devices.flat]}")
    model = build_model(sizes, sizes.tp_serve_layers, args.seed,
                        tensor_parallel=True)
    reqs = make_requests(sizes, args.seed)
    eng4 = build_engine(model, sizes, tp=TPConfig())
    check(eng4.tp_degree() == 4, "engine reports tp degree 4")
    heads = sizes.widths["num_key_value_heads"]
    shapes = spread(eng4._cache[0], 4, "KV arena leaf")
    check(all(s[2] == heads // 4 for s in shapes),
          f"each device holds {heads // 4} of {heads} kv heads")
    names = [n for n, _ in model.named_parameters()]
    qi = next(i for i, n in enumerate(names) if n.endswith("q_proj.weight"))
    spread(eng4.backend._pv[qi], 4, f"column-sharded {names[qi]}")
    per_dev = [hbm(d).get("bytes_in_use") for d in devices]
    if all(v is not None for v in per_dev):
        say(f"  bytes in use per device: {[gib(v) for v in per_dev]} "
            "(device 0 also holds the unsharded model it was cut from)")
        check(min(per_dev[1:]) > 0.2 * max(per_dev[1:]) and
              min(per_dev[1:]) > 0,
              "devices 1-3 each hold their shard of weights and KV")
    srv4, rids4, probed = run_server(
        eng4, reqs, sizes, lambda eng: kernel_vs_reference(
            eng, args.seed, sizes.widths["num_attention_heads"],
            device=devices[0]))
    check_streams(srv4, rids4, reqs, sizes)
    check(eng4.decode_compile_count() == 1
          and eng4.prefill_compile_count() == 1,
          "sharded decode and prefill compile counts are 1")
    err, live = probed or (math.nan, 0)
    check(err <= 2e-2,
          f"Pallas s=1 read vs reference on the sharded arena (gathered to "
          f"one device): max |diff| {err:.3e} <= 2e-2 over {live} live "
          "slots")
    if not args.tiny:
        text = decode_program_text(eng4)
        check("tpu_custom_call" in text and "all-gather" in text,
              "the sharded decode program holds the Pallas paged-attention "
              f"call ({text.count('tpu_custom_call')} sites) and its "
              "all-gathers")
    t4 = meter.since(mark)
    mark = meter.mark()
    set_current_mesh(None)
    eng1 = build_engine(model, sizes, tp=False)
    srv1, rids1, _ = run_server(eng1, reqs, sizes)
    check_streams(srv1, rids1, reqs, sizes)
    same_len = all(len(srv4.results[a]) == len(srv1.results[b])
                   for a, b in zip(rids4, rids1))
    check(same_len, "equal requests gave equal lengths on 4 chips and 1")
    agree = float(np.mean([np.mean(
        srv4.results[a][-sizes.new_tokens:]
        == srv1.results[b][-sizes.new_tokens:])
        for a, b in zip(rids4, rids1)]))
    say(f"  info: token agreement 4 chips vs 1 chip: {agree:.3f} (exact "
        "mode is bit-identical on the CPU lane; random bf16 weights on the "
        "chip leave tiny margins)")
    say(f"  timing 4 chips {t4}; 1 chip {meter.since(mark)}")
    say(f"  stats 4 chips: {srv4.stats()['tokens_emitted']} tokens in "
        f"{srv4.stats()['wall_s']} s; 1 chip: "
        f"{srv1.stats()['tokens_emitted']} tokens in "
        f"{srv1.stats()['wall_s']} s (smoke wall clock incl. compilation)")


def train_hybrid_phase(args, sizes: Sizes, meter, devices):
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.distributed.mesh import (HybridCommunicateGroup,
                                             set_current_mesh)
    from paddle_tpu.distributed.sharding_utils import (place_model,
                                                       shard_batch)
    say(f"== train on 4 chips: one TrainStep on dp=2 x mp=2 against the "
        f"one-chip loss on the same batch, {sizes.train_layers} of "
        f"{FULL_LAYERS} layers")
    tokens = make_token_set(sizes, args.seed, sizes.train_batch)
    ids, labels = tokens[:, :-1], tokens[:, 1:]
    kw = dict(tensor_parallel=True, recompute=True, scan_layers=True)
    if args.tiny:   # XLA:CPU aborts in AllReducePromotion on the bf16 program
        kw["dtype"] = "float32"
    mark = meter.mark()
    set_current_mesh(None)
    model1 = build_model(sizes, sizes.train_layers, args.seed, **kw)
    loss1 = float(build_trainer(model1)(
        (paddle.to_tensor(ids), paddle.to_tensor(labels))).item())
    say(f"  one chip: loss {loss1:.4f}, sdpa route "
        f"{fa.sdpa_last_dispatch()} ({meter.since(mark)})")
    del model1
    release(devices[0], "one-chip trainer")
    mark = meter.mark()
    hcg = HybridCommunicateGroup(dp_degree=2, mp_degree=2, devices=devices)
    mesh = hcg.jax_mesh
    say(f"  mesh {dict(mesh.shape)}; device order "
        f"{[d.id for d in mesh.devices.flat]}")
    model4 = build_model(sizes, sizes.train_layers, args.seed, **kw)
    place_model(model4, mesh)
    gate = next(p for n, p in model4.named_parameters()
                if "gate_proj" in n)
    shapes = spread(gate._value, 4, "column-sharded gate_proj")
    check(all(s[-1] == sizes.widths["intermediate_size"] // 2
              for s in shapes), "gate_proj is split 2-way over mp and "
          "replicated over dp")
    step4 = build_trainer(model4)
    batch = (shard_batch(mesh, paddle.to_tensor(ids), P("dp", None)),
             shard_batch(mesh, paddle.to_tensor(labels), P("dp", None)))
    loss4 = float(step4(batch).item())
    say(f"  dp=2 x mp=2: loss {loss4:.4f}, sdpa route "
        f"{fa.sdpa_last_dispatch()} — jax cannot partition a Mosaic kernel "
        "under GSPMD, so the mesh trainer takes the jnp routes "
        f"(ops/pallas/fused.pallas_gate) ({meter.since(mark)})")
    check(fa.sdpa_last_dispatch() == "xla",
          "the mesh train step reports the route it took ('xla')")
    check(math.isfinite(loss4) and abs(loss4 - loss1) <= 1e-2,
          f"|loss(4 chips) - loss(1 chip)| = {abs(loss4 - loss1):.2e} "
          "<= 1e-2")
    check(spread(gate._value, 4, "gate_proj after the update") == shapes,
          "the update kept gate_proj split over mp")
    per_dev = [hbm(d).get("peak_bytes_in_use") for d in devices]
    if all(v is not None for v in per_dev):
        say(f"  peak HBM per device: {[gib(v) for v in per_dev]}")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths and their one-chip "
                         "twins (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy widths, Pallas in interpret "
                         "mode; never reports ok")
    args = ap.parse_args(argv)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if not args.tiny:
        if dev.platform != "tpu":
            print(f"chip_smoke: no TPU — jax found {device}; this script "
                  "runs on the chip only (--tiny is the CPU rehearsal)",
                  file=sys.stderr)
            return 2
        if dev.device_kind not in KNOWN_DEVICES:
            print(f"chip_smoke: unknown device kind {dev.device_kind!r}; "
                  f"known: {sorted(KNOWN_DEVICES)}", file=sys.stderr)
            return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, jax found {len(devices)}", file=sys.stderr)
        return 2

    # pin block-size lookups to a table that never exists: whatever sits
    # under the user's home must not steer the smoke
    here = os.path.dirname(os.path.abspath(__file__))
    os.environ["PT_TUNE_TABLE"] = os.path.join(here, ".tune_table_unused")
    from paddle_tpu.core import native_available
    from paddle_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    sizes = TINY if args.tiny else FULL
    if args.tiny:
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.ops.pallas import fused
        fused._FORCE_INTERPRET = fa._FORCE_INTERPRET = True
    say(f"chip_smoke: device {device}"
        + (f" = {KNOWN_DEVICES[dev.device_kind]['name']}"
           if dev.device_kind in KNOWN_DEVICES else " (CPU REHEARSAL)")
        + f"; jax {jax.__version__}; seed {args.seed}; chips {args.chips}")
    say(f"  compile cache: {cache_dir} "
        + ("(from JAX_COMPILATION_CACHE_DIR)"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "(fixed directory inside the checkout)"))
    say(f"  native library (libptcore, built from ptcore.cc on first use) "
        f"loaded: {native_available()}")
    say(f"  depth cut: serve "
        f"{sizes.tp_serve_layers if args.chips == 4 else sizes.serve_layers}"
        f"/{FULL_LAYERS} layers, "
        f"train {sizes.train_layers}/{FULL_LAYERS} layers; widths are "
        "Llama-2-7B's" + (" — NOT in this --tiny rehearsal" if args.tiny
                          else ""))

    meter = CompileMeter()
    t0 = meter.mark()
    if args.chips == 4:
        four = devices[:4]
        phases = [
            ("serve-tp", lambda: serve_tp_phase(args, sizes, meter, four)),
            ("free", lambda: [release(d, f"device {d.id} after serve-tp")
                              for d in four]),
            ("train-hybrid", lambda: train_hybrid_phase(args, sizes, meter,
                                                        four)),
        ]
    else:
        phases = [
            ("serve", lambda: serve_phase(args, sizes, meter, dev)),
            ("free", lambda: release(dev, "after serve")),
            ("train", lambda: train_phase(args, sizes, meter, dev)),
        ]
    failed = []
    for name, fn in phases:
        try:
            fn()
        except Exception:           # reported below; the exit code says so
            failed.append(name)
            say(f"PHASE FAILED: {name}\n{traceback.format_exc()}")
        gc.collect()
    total = meter.since(t0)
    say(f"total: {total}")
    peak = hbm(dev).get("peak_bytes_in_use")
    if peak is not None:
        total_hbm = KNOWN_DEVICES[dev.device_kind]["hbm_bytes"]
        say(f"peak HBM on device 0: {gib(peak)} of {gib(total_hbm)}")
    if failed:
        say(f"chip_smoke: FAILED phases: {failed}")
        return 1
    if args.tiny:
        print(json.dumps({"rehearsal_ok": True, "device": device}),
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
