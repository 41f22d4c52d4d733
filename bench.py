"""Headline benchmark: Llama pretrain step throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

vs_baseline = achieved MFU / 0.45 (the BASELINE.json north-star MFU target).

With no argument the parent — which NEVER initializes a jax backend itself,
so the chip belongs to one process at a time — runs the TPU child under a
hard deadline, then the CPU-lane stage children strictly in turn, and
prints the merged line. Without a TPU the child fails and so does the
parent: there is no CPU fallback and no number under a device metric's
name that a chip did not produce. Every ``--child-*`` stage stays callable
on its own.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# env knobs through the utils/flags helpers (the PR 4/5 migration
# pattern — uniform empty-value leniency). Importing the package does
# NOT initialize a jax backend (verified: xla_bridge._backends stays
# empty), so the parent's never-init contract holds.
from paddle_tpu.utils.flags import env_float, env_int, env_str

# peak bf16 FLOP/s keyed by jax's device_kind (Google Cloud "TPU v5e":
# 197 TFLOP/s). A device that is not in the table is an error, not a default.
PEAK_FLOPS = {"TPU v5 lite": 197e12}

# the ~1B config's scan_layers compile + 3-batch ladder needs ~10-15 min
# end to end; per-stage BENCH_JSON emission preserves earlier stages if
# the child dies
TPU_DEADLINE_S = env_float("BENCH_TPU_DEADLINE_S", 1100)
COMMS_DEADLINE_S = env_float("BENCH_COMMS_DEADLINE_S", 240)
PASSES_DEADLINE_S = env_float("BENCH_PASSES_DEADLINE_S", 240)
SERVING_SPEC_DEADLINE_S = env_float("BENCH_SERVING_SPEC_DEADLINE_S", 240)
SERVING_TP_DEADLINE_S = env_float("BENCH_SERVING_TP_DEADLINE_S", 300)
SERVING_QUANT_DEADLINE_S = env_float("BENCH_SERVING_QUANT_DEADLINE_S",
                                     300)
SERVING_MEGA_DEADLINE_S = env_float("BENCH_SERVING_MEGA_DEADLINE_S", 300)
SERVING_FRONTDOOR_DEADLINE_S = env_float(
    "BENCH_SERVING_FRONTDOOR_DEADLINE_S", 300)
SERVING_FAILOVER_DEADLINE_S = env_float(
    "BENCH_SERVING_FAILOVER_DEADLINE_S", 300)
SERVING_DISAGG_DEADLINE_S = env_float(
    "BENCH_SERVING_DISAGG_DEADLINE_S", 300)
SERVING_PREFIXCACHE_DEADLINE_S = env_float(
    "BENCH_SERVING_PREFIXCACHE_DEADLINE_S", 300)
SERVING_AUTOSCALE_DEADLINE_S = env_float(
    "BENCH_SERVING_AUTOSCALE_DEADLINE_S", 300)
SERVING_RECOVERY_DEADLINE_S = env_float(
    "BENCH_SERVING_RECOVERY_DEADLINE_S", 300)
AUTOTUNE_DEADLINE_S = env_float("BENCH_AUTOTUNE_DEADLINE_S", 300)

def _bench_train(model_cfg, batch, seq, steps, warmup, peak,
                 multi_precision=True, hbm_limit=None):
    """Measure one-chip training throughput for one config. Runs inside the
    child process (backend already chosen). ``hbm_limit``: AOT-compile
    first and SKIP execution (raise with the numbers) when XLA's memory
    estimate exceeds it — an OOM config then costs one compile, not a
    crashed child (VERDICT r2 missing #3)."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(0)
    if getattr(model_cfg, "dtype", "float32") == "bfloat16":
        # pure-bf16 build: params AND Adam moments in bf16
        # (2 bytes x 3 per param) — the memory budget that fits ~1B on
        # one 16 GB v5e chip; no AMP wrapper needed. finally: a failed
        # build (e.g. OOM) must not leak the bf16 default into later
        # stages of this child
        paddle.set_default_dtype("bfloat16")
        try:
            model = LlamaForCausalLM(model_cfg)
        finally:
            paddle.set_default_dtype("float32")
        opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                              parameters=model.parameters(),
                              multi_precision=False)
    else:
        model = LlamaForCausalLM(model_cfg)
        opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                              parameters=model.parameters(),
                              multi_precision=multi_precision)
        model, opt = amp.decorate(model, opt, level="O2",
                                  dtype="bfloat16")

    def loss_fn(m, b):
        ids, labels = b
        loss, _ = m(ids, labels)
        return loss

    step = TrainStep(model, loss_fn, opt)
    ids = np.random.randint(0, model_cfg.vocab_size,
                            (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    batch_t = (paddle.to_tensor(ids), paddle.to_tensor(labels))

    if hbm_limit is not None:
        compiled = step.lower(batch_t).compile()
        ma = compiled.memory_analysis()
        est = (getattr(ma, "temp_size_in_bytes", 0)
               + getattr(ma, "argument_size_in_bytes", 0)
               + getattr(ma, "output_size_in_bytes", 0)
               - getattr(ma, "alias_size_in_bytes", 0))
        if est <= 0:
            # an inert guard must not masquerade as a passed check —
            # the caller decides whether to run un-prechecked
            raise RuntimeError(
                "AOT memory precheck unavailable on this backend "
                "(memory_analysis lacks size fields); refusing the "
                "un-prechecked run at this batch size")
        if est > hbm_limit:
            raise RuntimeError(
                f"AOT memory precheck: {est / 1e9:.2f} GB estimated > "
                f"{hbm_limit / 1e9:.2f} GB limit; skipping execution")

    for _ in range(warmup):
        loss = step(batch_t)
    float(loss.item())  # sync

    # the timed window runs as ONE lax.scan dispatch: per-step host
    # round-trips showed up as 9.3% device IDLE in PROFILE_r03.json;
    # scan removes them entirely
    try:
        loss = step.run_steps(batch_t, steps)   # compile the scan prog
        float(loss.item())
        t0 = time.perf_counter()
        loss = step.run_steps(batch_t, steps)
        final = float(loss.item())  # sync
        dt = time.perf_counter() - t0
    except Exception:
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(batch_t)
        final = float(loss.item())  # sync
        dt = time.perf_counter() - t0

    tok_per_s = batch * seq * steps / dt
    mfu = tok_per_s * model.flops_per_token(seq) / peak
    return {"tokens_per_sec": round(tok_per_s, 1),
            "mfu": round(mfu, 4),
            "model_params": int(model.num_params()),
            "batch": batch, "seq": seq,
            "final_loss": round(final, 4),
            "step_ms": round(dt / steps * 1000, 2)}


def _bench_decode(model_cfg, batch, prompt, new_tokens):
    """KV-cache autoregressive decode throughput (jitted decode step)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(model_cfg)
    ids = paddle.to_tensor(np.random.randint(
        0, model_cfg.vocab_size, (batch, prompt)).astype(np.int32))
    # warmup with IDENTICAL shapes (same cache length) so the timed run
    # reuses the compiled prefill + decode step
    model.generate(ids, max_new_tokens=new_tokens)
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=new_tokens)
    assert out.shape[1] == prompt + new_tokens
    dt = time.perf_counter() - t0
    return {"decode_tokens_per_sec": round(batch * new_tokens / dt, 1),
            "decode_batch": batch, "decode_prompt": prompt,
            "decode_new_tokens": new_tokens}


def _bench_continuous_decode(model_cfg, num_slots=4, decode_block=8,
                             long_new=96, short_new=8):
    """Continuous-batching vs static-batch decode on a mixed-length
    staggered request stream — the serving headline. Static batching
    rides every row until the slowest request finishes; the slot pool
    retires/refills rows as they complete, so aggregate useful tokens/s
    is strictly higher on ragged traffic. Returns both numbers plus the
    ratio so the trajectory is tracked every round."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import ContinuousBatchingEngine, Server

    paddle.seed(0)
    model = LlamaForCausalLM(model_cfg)
    rs = np.random.RandomState(0)
    # arrival order interleaves one long-budget request per slot group:
    # the static baseline's every group then rides to 96 tokens while
    # three short rows sit finished (the continuous engine refills them)
    lens = [16, 4, 8, 4, 16, 4, 8, 4]
    news = [long_new, short_new, short_new, short_new] * 2
    bucket = 16
    max_len = bucket + max(news)
    prompts = [rs.randint(0, model_cfg.vocab_size, (l,)).astype(np.int32)
               for l in lens]
    useful = sum(news)

    engine = ContinuousBatchingEngine(
        model, num_slots=num_slots, max_len=max_len,
        decode_block=decode_block, prompt_buckets=(bucket,))

    def engine_pass():
        engine.reset()
        srv = Server(engine)
        for p, mn in zip(prompts, news):
            srv.submit(p, max_new_tokens=mn)
        srv.run_until_idle()
        return srv

    engine_pass()                         # compile warmup
    t0 = time.perf_counter()
    srv = engine_pass()
    dt_engine = time.perf_counter() - t0

    def static_pass():
        for g in range(0, len(prompts), num_slots):
            chunk = prompts[g:g + num_slots]
            mns = news[g:g + num_slots]
            lmax = max(len(p) for p in chunk)
            ids = np.zeros((len(chunk), lmax), np.int32)
            am = np.zeros((len(chunk), lmax), np.int32)
            for i, p in enumerate(chunk):
                ids[i, lmax - len(p):] = p
                am[i, lmax - len(p):] = 1
            out = model.generate(paddle.to_tensor(ids),
                                 max_new_tokens=max(mns),
                                 attention_mask=paddle.to_tensor(am))
            np.asarray(out.numpy())       # sync

    static_pass()                         # compile warmup
    t0 = time.perf_counter()
    static_pass()
    dt_static = time.perf_counter() - t0

    stats = srv.stats()
    return {
        "decode_tokens_per_sec": round(useful / dt_engine, 1),
        "decode_static_tokens_per_sec": round(useful / dt_static, 1),
        "decode_speedup_vs_static": round(dt_static / dt_engine, 3),
        "decode_mode": "continuous_batching",
        "decode_requests": len(prompts),
        "decode_slots": num_slots,
        "decode_slot_occupancy": stats["slot_occupancy"],
        "decode_compile_count": stats["decode_compile_count"],
        # time-to-first-token percentiles over the timed stream — the
        # user-facing latency half of the serving headline (tokens/s
        # alone hides admission queueing + prefill stalls)
        "decode_ttft_p50_ms": round(stats["ttft_p50_s"] * 1000, 2),
        "decode_ttft_p95_ms": round(stats["ttft_p95_s"] * 1000, 2),
    }


def _bench_paged_serving(model_cfg, num_slots=4, block_size=16,
                         decode_block=8, prefix_len=96, tail_len=8,
                         requests=6, max_new=16):
    """Paged-KV serving A/B on a shared-prefix workload: every request
    repeats one system prompt with a distinct tail (the prefix cache's
    target case). Measures (a) prefix-cache hit rate + per-slot KV HBM
    vs the dense engine, and (b) chunked-vs-whole prefill interference:
    max per-tick latency with a per-tick prefill token budget (chunks
    interleave with decode) against unbudgeted whole-prompt prefill —
    chunking bounds the decode-latency spike a long prompt causes."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)

    paddle.seed(0)
    model = LlamaForCausalLM(model_cfg)
    rs = np.random.RandomState(0)
    prefix = rs.randint(0, model_cfg.vocab_size,
                        (prefix_len,)).astype(np.int32)
    prompts = [np.concatenate([prefix, rs.randint(
        0, model_cfg.vocab_size, (tail_len,)).astype(np.int32)])
        for _ in range(requests)]
    max_len = block_size * (
        -(-(prefix_len + tail_len + max_new) // block_size))
    chunk = block_size
    # size the arena for the workload, not the worst case: the shared
    # prefix blocks exist ONCE, each slot only adds its tail + decode
    # blocks (+1 trash, +2 slack) — this is where the HBM-per-slot
    # reduction vs the dense (num_slots * max_len) layout comes from;
    # a transient shortage just re-queues the request
    per_req = -(-(prefix_len + tail_len + max_new - 1) // block_size)
    shared_blocks = prefix_len // block_size
    num_blocks = 1 + per_req + (num_slots - 1) * (
        per_req - shared_blocks) + 2

    engine = ContinuousBatchingEngine(
        model, num_slots=num_slots, max_len=max_len,
        decode_block=decode_block, paged=True, block_size=block_size,
        num_blocks=num_blocks, prefill_chunk=chunk)

    def run(budget):
        engine.reset()
        srv = Server(engine, Scheduler(prefill_token_budget=budget))
        for i, p in enumerate(prompts):
            # staggered arrivals: later prompts prefill WHILE earlier
            # requests decode — the interference case
            srv.submit(p, max_new_tokens=max_new, arrival_step=3 * i)
        srv.run_until_idle()
        return srv

    run(chunk)                              # compile warmup
    srv_chunked = run(chunk)
    st_chunked = srv_chunked.stats()
    srv_whole = run(None)
    st_whole = srv_whole.stats()

    dense_bytes = (2 * model_cfg.num_hidden_layers * max_len
                   * model_cfg.num_key_value_heads
                   * (model_cfg.hidden_size
                      // model_cfg.num_attention_heads) * 4)

    out = {
        "serving_paged_prefix_hit_rate":
            st_chunked["prefix_cache_hit_rate"],
        "serving_paged_kv_bytes_per_slot":
            st_chunked["kv_bytes_per_slot"],
        "serving_dense_kv_bytes_per_slot": dense_bytes,
        "serving_paged_tokens_per_sec": st_chunked["tokens_per_sec"],
        "serving_paged_max_tick_ms_chunked":
            round(st_chunked["max_tick_s"] * 1000, 2),
        "serving_paged_max_tick_ms_whole":
            round(st_whole["max_tick_s"] * 1000, 2),
        "serving_paged_ttft_p95_ms_chunked":
            round(st_chunked["ttft_p95_s"] * 1000, 2),
        "serving_paged_ttft_p95_ms_whole":
            round(st_whole["ttft_p95_s"] * 1000, 2),
        "serving_paged_compile_counts": [
            st_chunked["decode_compile_count"],
            engine.prefill_compile_count()],
    }

    # int8 KV point: measured dequant error of a served stream must sit
    # under the runtime-queryable bound (the EQuARX contract applied to
    # the cache)
    engine8 = ContinuousBatchingEngine(
        model, num_slots=num_slots, max_len=max_len,
        decode_block=decode_block, paged=True, block_size=block_size,
        num_blocks=num_blocks,       # same arena size as the fp32 A/B
        prefill_chunk=chunk, kv_int8=True)
    srv8 = Server(engine8, Scheduler(prefill_token_budget=chunk))
    for p in prompts[:2]:
        srv8.submit(p, max_new_tokens=max_new)
    srv8.run_until_idle()
    out["serving_paged_kv_int8_bytes_per_slot"] = \
        engine8.backend.kv_bytes_per_slot()
    out["serving_paged_kv_int8_error_bound"] = \
        round(engine8.kv_error_bound(), 6)
    return out


def _bench_resilience(model_cfg, num_slots=4, decode_block=8,
                      requests=10, max_new=24, fault_rate=0.01):
    """Resilience A/B: the same request stream clean vs with a
    ``fault_rate`` injected step-failure probability (the
    ``serving.step_block`` site, seeded — the schedule is identical
    every round). Reports the throughput + p95 latency cost of riding
    the retry/backoff path and the resilience counters, so a policy
    regression (e.g. retries stopping masking transient faults, or the
    breaker tripping on background noise) shows up as a number."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    ResilienceConfig, Server)
    from paddle_tpu.utils import faults

    paddle.seed(0)
    from paddle_tpu.models.llama import LlamaForCausalLM
    model = LlamaForCausalLM(model_cfg)
    rs = np.random.RandomState(0)
    lens = [4 + (i % 3) * 6 for i in range(requests)]
    prompts = [rs.randint(0, model_cfg.vocab_size, (l,)).astype(np.int32)
               for l in lens]
    engine = ContinuousBatchingEngine(
        model, num_slots=num_slots, max_len=16 + max_new,
        decode_block=decode_block, prompt_buckets=(16,))
    res_cfg = ResilienceConfig(retry_attempts=3, retry_backoff_s=0.002,
                               breaker_threshold=32)

    def run():
        engine.reset()
        srv = Server(engine, resilience=res_cfg)
        for i, p in enumerate(prompts):
            srv.submit(p, max_new_tokens=max_new, arrival_step=i)
        srv.run_until_idle()
        return srv

    run()                                   # compile warmup
    t0 = time.perf_counter()
    srv_clean = run()
    dt_clean = time.perf_counter() - t0
    st_clean = srv_clean.stats()

    faults.configure(f"serving.step_block:p={fault_rate}", seed=0)
    try:
        t0 = time.perf_counter()
        srv_faulty = run()
        dt_faulty = time.perf_counter() - t0
    finally:
        faults.clear()
    st_faulty = srv_faulty.stats()
    useful = requests * max_new
    return {
        "serving_resilience_tokens_per_sec_clean":
            round(useful / dt_clean, 1),
        "serving_resilience_tokens_per_sec_faulty":
            round(useful / dt_faulty, 1),
        "serving_resilience_p95_latency_ms_clean":
            round(st_clean["latency_p95_s"] * 1000, 2),
        "serving_resilience_p95_latency_ms_faulty":
            round(st_faulty["latency_p95_s"] * 1000, 2),
        "serving_resilience_fault_rate": fault_rate,
        "serving_resilience_step_failures": st_faulty["step_failures"],
        "serving_resilience_retries": st_faulty["retries"],
        "serving_resilience_requests_failed":
            st_faulty["requests_failed"],
        "serving_resilience_completed_faulty":
            st_faulty["requests_completed"],
        # the clean pass pins the inertness contract in the bench too
        "serving_resilience_clean_counters_zero":
            st_clean["step_failures"] == 0 == st_clean["retries"],
    }


def _child_tpu():
    """Runs on the TPU and fails without one. Benches a 0.2B config and the
    largest Llama that fits one chip in bf16, reports the Pallas dispatch
    route, prints one JSON dict; exits non-zero if any stage failed."""
    import jax
    from paddle_tpu.utils.compile_cache import configure_compile_cache
    # persistent compile cache: a repeat run skips the multi-minute
    # big-config compile
    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU (jax found {dev.platform} "
                         f"{dev.device_kind!r}); bench.py measures on the "
                         "chip only")
    if dev.device_kind not in PEAK_FLOPS:
        raise SystemExit(f"bench: no peak on record for device kind "
                         f"{dev.device_kind!r} (known: {sorted(PEAK_FLOPS)})")
    gen = dev.device_kind
    peak = PEAK_FLOPS[gen]

    from paddle_tpu.models.llama import LlamaConfig

    def _isolated(fn, label):
        """One config must not take down the others' results (a v5e HBM
        OOM on the big config previously killed the whole child)."""
        try:
            return fn(), None
        except Exception as e:
            msg = f"{type(e).__name__}: {e}"
            return None, f"{label}: {msg[:600]}"

    t_child0 = time.perf_counter()
    stage_s = {}

    def _staged(fn, label):
        """_isolated + wall-clock accounting per stage, so a deadline
        kill is attributable (r3: the window vanished into stages with
        no on-record timing)."""
        t0 = time.perf_counter()
        out, err = _isolated(fn, label)
        stage_s[label] = round(time.perf_counter() - t0, 1)
        return out, err

    def _emit(small, big, decode, errors):
        """One BENCH_JSON line from whatever has finished so far; the
        parent keeps the LAST line, so emitting after every stage means a
        deadline kill mid-child can no longer lose the headline."""
        from paddle_tpu.ops.pallas import flash_attention as fa
        head = big or small
        if head is None:
            return
        stage_s["child_total"] = round(time.perf_counter() - t_child0, 1)
        print("BENCH_JSON " + json.dumps({
            "metric": "llama_pretrain_tokens_per_sec_per_chip",
            "value": head["tokens_per_sec"],
            "unit": "tokens/s",
            "vs_baseline": round(head["mfu"] / 0.45, 4),
            "mfu": head["mfu"],
            "chip": gen,
            "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "sdpa_dispatch": fa.sdpa_last_dispatch(),
            "config_small": small,
            "config_big": big,
            "stage_s": dict(stage_s),
            **({"config_errors": errors} if errors else {}),
            **(decode or {}),
            **{k: head[k] for k in ("model_params", "batch", "seq",
                                    "final_loss", "step_ms")},
        }), flush=True)

    errors = []
    cfg_small = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_hidden_layers=16, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=1024,
        tensor_parallel=False)
    # batch 32 measured best on v5e: 24.4k tok/s, 22.65% MFU
    # (sweep: b8 20.8%, b16 22.2%, b32 22.65%; seq 2048 regresses)
    small, err = _staged(lambda: _bench_train(
        cfg_small, batch=32, seq=1024, steps=10, warmup=3, peak=peak),
        "small")
    if err:
        errors.append(err)
    _emit(small, None, None, errors)
    # ~0.95B params; bf16 optimizer states (multi_precision off) +
    # per-layer remat + fused head CE (default-on). Every batch size
    # is AOT-memory-prechecked (15.2/16 GB v5e budget) so an
    # over-budget config costs one compile, never an OOM crash.
    def big_cfg(gran):
        # scan_layers inside: the XLA program holds ONE layer body, so
        # the big config compiles in seconds, not minutes
        from _bench_common import headline_big_config
        return headline_big_config(gran)
    big = None
    # full-remat b8 first: the known-good 48.97%-MFU headline shape
    # — lock it in before experiments. Smallest batch runs even if
    # the backend can't report memory stats (r02 behavior).
    for gran, bb in (("full", 8), ("full", 4), ("full", 2)):
        limit = 15.2e9 if bb > 2 else None
        big, err = _staged(
            lambda g=gran, b=bb, lm=limit: _bench_train(
                big_cfg(g), batch=b, seq=2048, steps=8, warmup=2,
                peak=peak, multi_precision=False, hbm_limit=lm),
            f"big-{gran}-b{bb}")
        if err:
            errors.append(err)
        if big is not None:
            big["remat"] = gran
            break
    _emit(small, big, None, errors)
    # stages leak HBM into their successors (r5: big-splash and decode
    # both hit runtime RESOURCE_EXHAUSTED with three stages' buffers
    # resident): free executables + their held buffers between the
    # remaining stages.
    import gc

    def _release_hbm():
        gc.collect()
        try:
            jax.clear_caches()   # compiled programs pin donated bufs
        except Exception:
            pass
        gc.collect()
    _release_hbm()
    # upside experiment: selective remat executes ~16% fewer FLOPs
    # per step (CPU AOT: 6.80e12 vs 8.09e12) = higher MFU at equal
    # step time, but holds more live activations — b8 estimates
    # 42 GB (never fits v5e), so try b4 behind the precheck; one
    # failed compile is the max cost, and the full-remat headline
    # above is already on the record
    if big is not None:
        sel, err = _staged(lambda: _bench_train(
            big_cfg("selective"), batch=4, seq=2048, steps=8,
            warmup=2, peak=peak, multi_precision=False,
            hbm_limit=15.2e9), "big-selective-b4")
        if err:
            errors.append(err)
        if sel is not None and sel["mfu"] > big["mfu"]:
            sel["remat"] = "selective"
            big = sel
    _emit(small, big, None, errors)
    # sdpa kernel A/B on the headline shape: PROFILE_r03 charges the
    # equal-heads jax_flash route 20.5% of self-time plus a 5.7%
    # HBM-bound broadcast_in_dim in its bwd; splash (block-sparse
    # CausalMask, skips fully-masked tiles) may beat it — measure,
    # keep the winner, and record both so the choice is on-artifact
    if big is not None:
        _release_hbm()
        os.environ["PT_SDPA_PREFER"] = "splash"
        try:
            # 14.5 GB, tighter than the 15.2 run limit: splash-bwd's
            # true footprint EXCEEDS the AOT estimate (r5: est <=15.2
            # passed, runtime RESOURCE_EXHAUSTED), so an underestimated
            # config must be refused, not risked.
            lim = 14.5e9 if big["batch"] > 2 else None
            sp, err = _staged(lambda: _bench_train(
                big_cfg(big.get("remat", "full")), batch=big["batch"],
                seq=2048, steps=8, warmup=2, peak=peak,
                multi_precision=False, hbm_limit=lim), "big-splash")
        finally:
            os.environ.pop("PT_SDPA_PREFER", None)
        if err:
            errors.append(err)
        if sp is not None:
            # attribute the A/B to the block config that produced
            # it (tuned/env/default + effective sizes) — the
            # autotune-era contract for sdpa numbers
            from paddle_tpu.ops.pallas import flash_attention as _fa
            sp["sdpa_block_choice"] = _fa.last_block_choice()
            big["sdpa_ab"] = {"jax_flash": big["mfu"],
                              "splash": sp["mfu"]}
            if sp["mfu"] > big["mfu"]:
                sp["remat"] = big.get("remat")
                sp["sdpa_ab"] = big["sdpa_ab"]
                sp["sdpa"] = "splash"
                big = sp
    _emit(small, big, None, errors)
    # decode runs LAST: it is the least informative stage for the
    # MFU contract, and r3 showed it can eat the deadline window
    # the ~1B headline config needed
    _release_hbm()
    decode, err = _staged(lambda: _bench_decode(
        cfg_small, batch=8, prompt=128, new_tokens=128), "decode")
    if err:
        errors.append(err)
    decode = decode or {}
    # the continuous-batching engine owns the decode_tokens_per_sec
    # headline; the old fixed-batch decode point moves to its own
    # key. A failed engine stage must still leave the headline key
    # present (null), not silently drop the round's decode record.
    if "decode_tokens_per_sec" in decode:
        decode["decode_fixed_batch_tokens_per_sec"] = \
            decode.pop("decode_tokens_per_sec")
    _release_hbm()
    serve, err = _staged(lambda: _bench_continuous_decode(
        cfg_small, num_slots=8), "decode-continuous")
    if err:
        errors.append(err)
    decode.update(serve if serve is not None
                  else {"decode_tokens_per_sec": None})
    _release_hbm()
    paged, err = _staged(lambda: _bench_paged_serving(cfg_small),
                         "serving-paged")
    if err:
        errors.append(err)
    decode.update(paged if paged is not None
                  else {"serving_paged_prefix_hit_rate": None})
    _release_hbm()
    resil, err = _staged(lambda: _bench_resilience(cfg_small),
                         "serving-resilience")
    if err:
        errors.append(err)
    decode.update(resil if resil is not None
                  else {"serving_resilience_tokens_per_sec_faulty":
                        None})
    _release_hbm()
    # tensor-parallel decode over the window's REAL chips: the
    # microbench itself records a skip when the window owns one
    # chip (the usual case) — the key stays on the record either way
    from paddle_tpu.serving.microbench import run_serving_tp_bench
    tp, err = _staged(run_serving_tp_bench, "serving-tp")
    if err:
        errors.append(err)
    decode.update(tp if tp is not None
                  else {"serving_tp_bit_identical": None})
    _release_hbm()
    # speculative decode on the REAL chip: where the (S, k+1)
    # verify forward re-reads weights once instead of k+1 times per
    # emitted token — the 2-3x decode headline target lives here
    from paddle_tpu.serving.microbench import run_serving_spec_bench
    sp_dec, err = _staged(run_serving_spec_bench, "serving-spec")
    if err:
        errors.append(err)
    decode.update(sp_dec if sp_dec is not None
                  else {"serving_spec_speedup": None})
    _release_hbm()
    # fused decode-layer megakernel on the REAL chip: the Pallas
    # decode-layer kernel dispatches here (kernel_calls > 0), so
    # the tokens/s delta is the HBM-round-trip win, not overhead
    from paddle_tpu.serving.microbench import \
        run_serving_megakernel_bench
    mega, err = _staged(run_serving_megakernel_bench,
                        "serving-megakernel")
    if err:
        errors.append(err)
    decode.update(mega if mega is not None
                  else {"serving_megakernel_bit_identical": None})
    _release_hbm()
    # multi-tenant front door on the REAL chip: WFQ shares,
    # preemption + bit-identical resume, per-priority TTFT
    from paddle_tpu.serving.microbench import \
        run_serving_frontdoor_bench
    fd, err = _staged(run_serving_frontdoor_bench,
                      "serving-frontdoor")
    if err:
        errors.append(err)
    decode.update(fd if fd is not None
                  else {"serving_frontdoor_bit_identical": None})
    _release_hbm()
    # disaggregated prefill/decode fleet on the REAL chip: handoff
    # wire bytes, fleet-wide prefix hit rate, disagg-vs-unified
    # TTFT/tokens/s (the hardware-pool split claim lives here)
    from paddle_tpu.serving.microbench import \
        run_serving_disagg_bench
    dis, err = _staged(run_serving_disagg_bench, "serving-disagg")
    if err:
        errors.append(err)
    decode.update(dis if dis is not None
                  else {"serving_disagg_bit_identical": None})
    _release_hbm()
    # fleet failure domains on the REAL chip: kill-one-decode-
    # worker A/B over the socket transport (redrive latency +
    # goodput under worker loss are the chip claims)
    from paddle_tpu.serving.microbench import \
        run_serving_failover_bench
    fo, err = _staged(run_serving_failover_bench,
                      "serving-failover")
    if err:
        errors.append(err)
    decode.update(fo if fo is not None
                  else {"serving_failover_bit_identical": None})
    _release_hbm()
    # fleet-wide KV prefix cache on the REAL chip: cold vs warm-
    # local vs warm-remote TTFT ladder + bytes-moved-vs-flops-
    # saved (the fetch-beats-prefill claim is a chip claim too)
    from paddle_tpu.serving.microbench import \
        run_serving_prefixcache_bench
    pfx, err = _staged(run_serving_prefixcache_bench,
                       "serving-prefixcache")
    if err:
        errors.append(err)
    decode.update(pfx if pfx is not None
                  else {"serving_prefixcache_bit_identical": None})
    _release_hbm()
    # block-size autotune sweep on the REAL chip (flash/splash
    # blocks + the CPU-honest knobs, persisted per device kind)
    from paddle_tpu.ops.pallas.autotune import run_autotune
    tune, err = _staged(run_autotune, "autotune")
    if err:
        errors.append(err)
    decode.update(tune if tune is not None
                  else {"autotune_entries": None})
    _emit(small, big, decode, errors)
    if errors:
        raise SystemExit("bench: stages failed: " + "; ".join(errors))


def _run_child(mode: str, deadline: float):
    """Run this script in child mode; returns parsed JSON dict or None.
    The child emits BENCH_JSON after every completed stage — the LAST
    line wins, and a deadline kill still salvages the partial result."""
    env = dict(os.environ)
    if mode != "--child-tpu":       # every other stage is CPU-lane
        env["JAX_PLATFORMS"] = "cpu"
    if mode in ("--child-comms", "--child-serving-tp"):
        # simulated 2x4 mesh on the CPU lane
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    stdout, stderr, rc = "", "", "killed"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), mode], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        stdout, stderr = proc.communicate(timeout=deadline)
        rc = proc.returncode
    except BaseException as e:
        # deadline or ANY other escape (KeyboardInterrupt to the parent,
        # ...): never leave a child behind holding the chip
        proc.kill()
        stdout, stderr = proc.communicate()
        if not isinstance(e, subprocess.TimeoutExpired):
            raise
    stdout, stderr = stdout or "", stderr or ""
    result = None
    for line in stdout.splitlines():
        if line.startswith("BENCH_JSON "):
            try:
                result = json.loads(line[len("BENCH_JSON "):])
            except json.JSONDecodeError:
                pass  # SIGKILL mid-flush truncated this line; keep the
                      # last complete one
    if result is not None:
        if rc == "killed":
            result["partial"] = "deadline killed the child mid-stage"
        elif rc != 0:
            # child failed after emitting a stage result — keep the
            # salvage but say so
            result["partial"] = f"child crashed rc={rc} after this stage"
            result["crash_tail"] = (stdout + stderr)[-500:]
        return result, None
    if rc == "killed":
        return None, "deadline exceeded (backend init or compile hang)"
    tail = (stdout + stderr)[-2000:]
    return None, f"rc={rc}: {tail}"


def _child_comms():
    """comms stage: the hierarchical/quantized collective microbench
    (distributed/collectives/) over 8 simulated CPU devices. The round
    owns one chip, so there is no real multi-chip ICI to time — the
    stage pins wire-format bytes, algorithmic bandwidth and the
    quantized-vs-fp32 error contract every round, and becomes the comm
    headline the day a multi-chip window exists."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.distributed.collectives import run_comms_bench
    out = run_comms_bench(
        size_mb=env_float("BENCH_COMMS_MB", 2))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _attach_stage(result, key, mode, deadline_s):
    """Merge an auxiliary child stage into the headline JSON (own child,
    run after the one before it has exited, so a hung stage can never
    cost the training headline)."""
    out, err = _run_child(mode, deadline_s)
    result[key] = out if out is not None else {"error": (err or "")[:300]}
    return result


def _child_passes():
    """passes stage: the jaxpr fusion-pass pipeline microbench
    (passes/microbench.py) on the CPU backend. Pins eqn-count
    reduction, compile-time delta and step-time A/B of the
    cascaded-reduction fusion every round — non-null like the comms
    stage; the on-chip HBM win rides the same flag (PT_FUSION_PASSES)
    when a TPU window exists."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.passes.microbench import run_passes_bench
    out = run_passes_bench(
        rows=env_int("BENCH_PASSES_ROWS", 256),
        vocab=env_int("BENCH_PASSES_VOCAB", 2048))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_spec():
    """serving-spec stage: the draft-verify engine (serving/spec.py)
    A/B'd against the plain slot-pool engine on a repetitive-
    continuation workload (serving/microbench.py) — pins spec-vs-
    baseline decode tokens/s (CPU-lane gate: >= 1.3x), bit-identity,
    acceptance rate and mean accepted tokens/step every round. The
    2-3x decode target rides the same SpecConfig on the TPU child."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import run_serving_spec_bench
    out = run_serving_spec_bench(
        requests=env_int("BENCH_SERVING_SPEC_REQUESTS", 8),
        max_new=env_int("BENCH_SERVING_SPEC_MAX_NEW", 64),
        k=env_int("BENCH_SERVING_SPEC_K", 8))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_quant():
    """serving-quant stage: the bandwidth-true quantized paged engine
    (int8 KV arena + weight-only int8 decode weights, dequant inside
    the read/gemm) A/B'd against the fp32 paged engine
    (serving/microbench.py) — pins quant-vs-fp32 decode tokens/s,
    bytes-read/step from the metrics registry (~3.5x fewer), both
    error bounds and the compile-count pin every round. On the CPU
    lane the tokens/s delta is an overhead record; the HBM-bandwidth
    win rides the same QuantConfig on the TPU child."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import run_serving_quant_bench
    out = run_serving_quant_bench(
        requests=env_int("BENCH_SERVING_QUANT_REQUESTS", 8),
        max_new=env_int("BENCH_SERVING_QUANT_MAX_NEW", 48),
        weights=env_str("BENCH_SERVING_QUANT_WEIGHTS", "int8"))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_megakernel():
    """serving-megakernel stage: the decode-layer fusion pass + fused
    decode-layer call (passes/fusion_decode.py +
    ops/pallas/decode_layer.py) A/B'd against the plain paged+int8-KV
    engine (serving/microbench.py) — pins fused-vs-unfused bit-identity,
    tokens/s, the no-hidden-state-transient jaxpr walk, the per-layer
    rewrite count and the compile-count pin every round. On the CPU
    lane the fused body is the captured unfused jaxpr (structure pin);
    the VMEM-residency win rides the same flag on the TPU child, where
    the Pallas megakernel dispatches."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import run_serving_megakernel_bench
    out = run_serving_megakernel_bench(
        requests=env_int("BENCH_SERVING_MEGA_REQUESTS", 8),
        max_new=env_int("BENCH_SERVING_MEGA_MAX_NEW", 32))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_frontdoor():
    """serving-frontdoor stage: the multi-tenant traffic layer
    (serving/frontend.py) on the paged engine — pins measured
    per-tenant throughput shares vs the configured WFQ weights (gate:
    within 10%) on a saturated 3-tenant workload, priority preemption
    (count, the evicted request still completing bit-identical to an
    uninterrupted run), TTFT p50/p95 split by priority with a
    preemption-on/off A/B, and the decode/prefill compile-count pin
    every round. All fields non-null on the CPU lane; the TPU child
    stages the same workload."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import run_serving_frontdoor_bench
    out = run_serving_frontdoor_bench(
        requests_per_tenant=env_int("BENCH_SERVING_FRONTDOOR_REQUESTS",
                                    18),
        max_new=env_int("BENCH_SERVING_FRONTDOOR_MAX_NEW", 8))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_disagg():
    """serving-disagg stage: the prefill/decode fleet
    (serving/fleet.py + handoff.py) on a shared-system-prompt workload
    — pins cross-worker bit-identity vs a unified Server, handoff KV
    payload bytes at wire size with the fp32-vs-int8 ratio (~3.6x),
    fleet-wide prefix hit rate with an affinity-on/off A/B (gate:
    affinity >= the single-replica rate), disagg-vs-unified TTFT p50
    and decode tokens/s, and the compile-count pins (ONE decode block
    per decode worker, ONE chunk program per prefill worker). All
    fields non-null on the CPU lane; the TPU child stages the same
    fleet."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import run_serving_disagg_bench
    out = run_serving_disagg_bench(
        requests_per_group=env_int("BENCH_SERVING_DISAGG_REQUESTS", 6),
        max_new=env_int("BENCH_SERVING_DISAGG_MAX_NEW", 8))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_failover():
    """serving-failover stage: the fleet failure-domain layer
    (serving/transport.py + fleet.py) — kill-one-decode-worker A/B on
    the REAL localhost-TCP SocketTransport with ~1% wire faults armed.
    Pins recovered-stream bit-identity (greedy + seeded-sampled),
    redrive latency p50/p95, goodput with/without the mid-run kill,
    and the handoff retry / (rid, seq)-dedup / transport
    resend-reconnect-CRC counters from the metrics registry. All
    fields non-null on the CPU lane; the TPU child stages the same
    fleet."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import run_serving_failover_bench
    out = run_serving_failover_bench(
        requests=env_int("BENCH_SERVING_FAILOVER_REQUESTS", 6),
        max_new=env_int("BENCH_SERVING_FAILOVER_MAX_NEW", 24))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_prefixcache():
    """serving-prefixcache stage: the fleet-wide KV prefix cache
    (serving/prefix_cache.py + the fleet directory/fetch wiring) —
    cold vs warm-local vs warm-remote TTFT on a shared-system-prompt
    ladder, bytes moved over the wire vs prefill flops saved, and the
    fetch/failure/duplicate/eviction counters from the metrics
    registry. Gates: the warm-REMOTE stream is bit-identical to the
    cold locally-prefilled one, warm-remote TTFT strictly beats cold
    (a fetch must cost less than the prefill it replaces), and decode
    + prefill compile counts stay 1 — the fetch adopts through the
    existing scatter program. All fields non-null on the CPU lane; the
    TPU child stages the same fleet."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import \
        run_serving_prefixcache_bench
    out = run_serving_prefixcache_bench(
        max_new=env_int("BENCH_SERVING_PREFIXCACHE_MAX_NEW", 8),
        sys_len=env_int("BENCH_SERVING_PREFIXCACHE_SYS_LEN", 192))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_autoscale():
    """serving-autoscale stage: SLO-driven autoscaling
    (serving/loadgen.py + autoscaler.py) — ONE seeded kill-and-burst
    trace replayed against an autoscaled fleet vs static-peak vs
    static-min. Pins bit-identity across scale events (completed
    streams match static-peak token-for-token, greedy rows match
    generate()), the decode-compile count staying 1 through scale-ins,
    the control loop converging (scale up on the burst, repair the
    kill, drain back to the min size), and SLO attainment vs
    worker-ticks — the capacity autoscaling saves. All fields non-null
    on the CPU lane; the TPU child stages the same fleet."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import run_serving_autoscale_bench
    out = run_serving_autoscale_bench(
        seed=env_int("BENCH_SERVING_AUTOSCALE_SEED", 0),
        horizon=env_int("BENCH_SERVING_AUTOSCALE_HORIZON", 36),
        max_new=env_int("BENCH_SERVING_AUTOSCALE_MAX_NEW", 10))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_recovery():
    """serving-recovery stage: the durable fleet control plane
    (serving/durability.py + fleet.py) — ONE seeded workload run
    clean, then run again with a checkpoint mid-traffic and a
    whole-fleet crash two ticks later, recovered via Fleet.recover.
    Pins bit-identity through the crash (every completed row matches
    the clean arm token-for-token, greedy AND seeded-sampled),
    recovery wall time, journal records replayed, streams redriven,
    decode compiles staying 1 on the recovered arenas, zero leaks.
    All fields non-null on the CPU lane; the TPU child stages the
    same fleet."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import run_serving_recovery_bench
    out = run_serving_recovery_bench(
        seed=env_int("BENCH_SERVING_RECOVERY_SEED", 0),
        requests=env_int("BENCH_SERVING_RECOVERY_REQUESTS", 6),
        max_new=env_int("BENCH_SERVING_RECOVERY_MAX_NEW", 10))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_autotune():
    """autotune stage: the Pallas block-size sweep harness
    (ops/pallas/autotune.py) — sweeps every knob that is honest on this
    backend (xent vocab-chunk + paged arena block size on any lane;
    flash/splash blocks only where the kernels dispatch), persists the
    provenance-stamped table, and PROVES a kernel reads it at trace
    time (the xent chunk cap re-derived through the production lookup).
    Also records the effective flash block-choice attribution so sdpa
    A/Bs are attributable to a config, not a guess."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas.autotune import run_autotune
    out = run_autotune(
        rows=env_int("BENCH_AUTOTUNE_ROWS", 256),
        vocab=env_int("BENCH_AUTOTUNE_VOCAB", 8192))
    out["autotune_flash_block_choice"] = fa.last_block_choice()
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _child_serving_tp():
    """serving-tp stage: the slot-pool decode block sharded over a
    simulated 2x4 CPU mesh (serving/microbench.py) — pins exact-mode
    bit-identity, 1-chip vs sharded tokens/s, collective bytes/calls
    per decode step from the metrics registry, and the int8-hop error
    bound every round. The real multi-chip decode win rides the same
    TPConfig when a multi-chip window exists."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.serving.microbench import run_serving_tp_bench
    out = run_serving_tp_bench(
        requests=env_int("BENCH_SERVING_TP_REQUESTS", 6),
        max_new=env_int("BENCH_SERVING_TP_MAX_NEW", 16))
    print("BENCH_JSON " + json.dumps(out), flush=True)


def _provenance():
    """Stamp for every bench artifact: which software stack and source
    rev produced it — so a committed BENCH_*.json is attributable (the
    r0x files predate this stamp; absence of the stamp marks them
    stale). Versions come from package metadata (the parent never
    initializes a jax backend); device kind/count ride the child
    results, where the backend actually lives."""
    import importlib.metadata as md
    def _v(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return None
    try:
        rev = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10).stdout.strip()
    except (OSError, subprocess.TimeoutExpired):
        rev = None
    return {"jax_version": _v("jax"), "jaxlib_version": _v("jaxlib"),
            "git_rev": rev or None,
            "bench_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime())}


def _emit_final(result):
    """The parent's ONE final JSON line, provenance-stamped."""
    result.update(_provenance())
    print(json.dumps(result))


# the CPU-lane stages the parent attaches after the TPU child, in order:
# (result key, child mode, child entry point, deadline)
CPU_STAGES = (
    ("comms", "--child-comms", _child_comms, COMMS_DEADLINE_S),
    ("passes", "--child-passes", _child_passes, PASSES_DEADLINE_S),
    ("serving-tp", "--child-serving-tp", _child_serving_tp, SERVING_TP_DEADLINE_S),
    ("serving-spec", "--child-serving-spec", _child_serving_spec, SERVING_SPEC_DEADLINE_S),
    ("serving-quant", "--child-serving-quant", _child_serving_quant, SERVING_QUANT_DEADLINE_S),
    ("serving-megakernel", "--child-serving-megakernel", _child_serving_megakernel, SERVING_MEGA_DEADLINE_S),
    ("serving-frontdoor", "--child-serving-frontdoor", _child_serving_frontdoor, SERVING_FRONTDOOR_DEADLINE_S),
    ("serving-disagg", "--child-serving-disagg", _child_serving_disagg, SERVING_DISAGG_DEADLINE_S),
    ("serving-failover", "--child-serving-failover", _child_serving_failover, SERVING_FAILOVER_DEADLINE_S),
    ("serving-prefixcache", "--child-serving-prefixcache", _child_serving_prefixcache, SERVING_PREFIXCACHE_DEADLINE_S),
    ("serving-autoscale", "--child-serving-autoscale", _child_serving_autoscale, SERVING_AUTOSCALE_DEADLINE_S),
    ("serving-recovery", "--child-serving-recovery", _child_serving_recovery, SERVING_RECOVERY_DEADLINE_S),
    ("autotune", "--child-autotune", _child_autotune, AUTOTUNE_DEADLINE_S),
)


def main() -> int:
    mode = sys.argv[1] if len(sys.argv) > 1 else None
    children = {"--child-tpu": _child_tpu}
    children.update({m: fn for _k, m, fn, _d in CPU_STAGES})
    if mode in children:
        children[mode]()
        return 0
    if mode is not None:
        print(f"bench: unknown mode {mode!r}; known: {sorted(children)}",
              file=sys.stderr)
        return 2
    # the parent stays off jax: one process on the chip at a time, the
    # children strictly in turn
    result, err = _run_child("--child-tpu", TPU_DEADLINE_S)
    if result is None:
        print(f"bench: the TPU child produced no result: {err}",
              file=sys.stderr)
        return 1
    for key, child_mode, _fn, deadline in CPU_STAGES:
        result = _attach_stage(result, key, child_mode, deadline)
    _emit_final(result)
    # a salvaged partial result is printed, never passed off as a clean run
    return 1 if "partial" in result else 0


if __name__ == "__main__":
    sys.exit(main())
