"""Fleet soaks the ``tools/*.sh`` scripts drive on the CPU lane: seeded
traffic through a paged prefill/decode fleet with workers killed
(``tools/chaos.sh``), sized by the autoscaler
(``tools/autoscale_soak.sh``), or crashed and recovered from the
durable journal (``tools/recovery_soak.sh``). Each asserts what the
recovery code promises (bit-identity to ``generate()``, zero block
leaks, one decode compile per engine) and returns the counters the
script prints. Device speed is the benchmark's to measure
(``benchmark/run.py``), not theirs.
"""
from __future__ import annotations

import time

import numpy as np

__all__ = ["run_fleet_kill_soak", "run_serving_autoscale_bench",
           "run_serving_recovery_bench"]


def run_fleet_kill_soak(seed: int = 0, kills: int = 2,
                        requests: int = 12, max_new: int = 16,
                        wire_fault_p: float = 0.01) -> dict:
    """Seeded worker-kill chaos soak (tools/chaos.sh): K decode-worker
    kills at seeded ticks over one traffic run on the socket
    transport with wire faults armed; after each kill a fresh decode
    worker scales in (``add_decode_worker``) so capacity survives the
    schedule. Asserts every request completed-or-explicitly-failed,
    completed greedy rows bit-identical to generate(), and zero block
    leaks on every surviving arena (prefill AND decode)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    DecodeWorker, Fleet, PrefillWorker,
                                    PrefillPagedEngine, RequestFailure,
                                    SocketTransport)
    from paddle_tpu.utils import faults

    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(seed)
    kw = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)
    prompts = [rs.randint(0, cfg.vocab_size,
                          (int(rs.randint(4, 14)),)).astype(np.int32)
               for _ in range(requests)]
    news = [max_new - int(rs.randint(0, 8)) for _ in range(requests)]
    kill_ticks = sorted(int(t) for t in rs.randint(2, 14, size=kills))

    t = SocketTransport("fleet", retry_backoff_s=0.001)
    fleet = Fleet(
        [PrefillWorker(PrefillPagedEngine(model, **kw))
         for _ in range(2)],
        [DecodeWorker(ContinuousBatchingEngine(model, **kw))
         for _ in range(2)],
        transport=t, lease_misses=2, spill_depth=100)
    rids = [fleet.submit(p, max_new_tokens=mn, arrival_step=i % 4)
            for i, (p, mn) in enumerate(zip(prompts, news))]
    spec = (f"transport.partial_write:p={wire_fault_p};"
            f"transport.corrupt:p={wire_fault_p};"
            f"transport.disconnect:p={wire_fault_p}")
    killed = 0
    next_name = len(fleet.decode)
    with faults.injected(spec, seed=seed):
        ticks = 0
        while fleet.busy() and ticks < 3000:
            fleet.tick()
            ticks += 1
            if killed < kills and ticks >= kill_ticks[killed]:
                victims = [i for i, d in enumerate(fleet.decode)
                           if not d.killed]
                vi = victims[int(rs.randint(0, len(victims)))]
                fleet.kill_decode_worker(vi)
                killed += 1
                fleet.add_decode_worker(DecodeWorker(
                    ContinuousBatchingEngine(model, **kw),
                    name=f"decode{next_name}"))
                next_name += 1
        res = fleet.results
    completed = failed = 0
    for rid, p, mn in zip(rids, prompts, news):
        assert rid in res, f"request {rid} vanished"
        v = res[rid]
        if isinstance(v, RequestFailure):
            assert v.reason in ("timeout", "poisoned", "circuit_open",
                                "shed", "handoff", "worker_lost"), \
                f"{rid}: unexpected reason {v.reason}"
            failed += 1
        else:
            ref = model.generate(paddle.to_tensor(p[None, :]),
                                 max_new_tokens=mn).numpy()[0]
            assert np.array_equal(v, ref), \
                f"completed stream {rid} not bit-identical"
            completed += 1
    # zero leaks on every surviving arena, both specialties
    for w in fleet.prefill:
        if fleet._alive(w.name) and hasattr(w.engine, "manager"):
            assert not w.engine.manager._ref
            w.engine.manager.assert_consistent()
    for d in fleet.decode:
        if fleet._alive(d.name) and hasattr(d.engine, "manager"):
            assert not d.engine.manager._ref
            d.engine.manager.assert_consistent()
    st = fleet.stats()
    t.close()
    return {
        "soak_seed": seed, "soak_kills": killed,
        "soak_requests": requests, "soak_completed": completed,
        "soak_failed": failed, "soak_redrives": st["redrives"],
        "soak_workers_lost": st["workers_lost"],
        "soak_duplicate_adopts": st["duplicate_adopts"],
        "soak_transport": st["transport"], "soak_ticks": st["ticks"],
        "soak_leaks": 0,
    }


def run_serving_autoscale_bench(seed: int = 0, horizon: int = 36,
                                max_new: int = 10) -> dict:
    """SLO-driven autoscaling stage (serving/loadgen.py +
    serving/autoscaler.py): ONE seeded kill-and-burst trace — steady
    traffic, a burst episode, a decode-worker kill inside the burst,
    recovery — replayed against three fleets: AUTOSCALED (starts at
    the min size, control loop armed), STATIC-PEAK (pinned at the
    autoscaler's max), STATIC-MIN (pinned at the min, no repair).

    What the stage pins every round:

    - **identical traffic**: all three arms replay the same
      materialized trace (same prompts, ticks, sampling seeds) and the
      same kill tick — the A/B/C is about fleet sizing only;
    - **bit-identity across scale events**: every request completed in
      both the autoscaled and static-peak arms must match
      token-for-token, and completed greedy rows must equal
      ``generate()`` — scaling up mid-burst, draining after it, and
      redriving through the kill never touch token streams;
    - **SLO attainment vs worker-ticks**: fraction of completed
      requests with TTFT under the target, against the capacity spent
      (sum over ticks of live decode workers) — the autoscaled arm
      should track static-peak's attainment at fewer worker-ticks;
    - **the loop converging**: the autoscaled fleet scales up on the
      burst (and repairs the kill immediately — below-min bypasses
      hysteresis), then drains back to the min size after the burst
      clears; peak and end sizes are reported;
    - the compile-count pin: every decode engine — including the ones
      scaled in mid-run — compiles its decode block exactly once.
    """
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    from paddle_tpu.observability import metrics as om
    from paddle_tpu.serving import (Autoscaler, AutoscalerConfig,
                                    ContinuousBatchingEngine,
                                    DecodeWorker, Fleet, PrefillWorker,
                                    PrefillPagedEngine, RequestFailure,
                                    TraceConfig, generate_trace, replay)

    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    kw = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)
    scfg = AutoscalerConfig(min_decode=2, max_decode=4,
                            interval_ticks=2, queue_high=2,
                            pressure_high=0.92, ttft_slo_s=0.5,
                            breach_intervals=2, clear_intervals=4,
                            up_cooldown=2, down_cooldown=3)

    trace = generate_trace(TraceConfig(
        seed=seed, horizon=horizon, base_rate=0.2, bursts=1,
        burst_mult=6.0, burst_len=(8, 12), prompt_alpha=1.5,
        prompt_lo=4, prompt_hi=12, output_alpha=1.2, output_lo=4,
        output_hi=max_new, vocab_size=cfg.vocab_size,
        shared_fraction=0.3, shared_len=8, sampled_fraction=0.25))
    b0, b1 = trace.burst_windows[0]
    kill_tick = (b0 + b1) // 2
    # every arm runs the SAME total tick window (trace + recovery
    # tail): worker-ticks then mean "capacity reserved over the
    # window", the quantity autoscaling actually saves, and the tail
    # gives the control loop room to drain back to the min size
    total_ticks = horizon + 60

    def drive(n_decode, autoscale):
        fleet = Fleet(
            [PrefillWorker(PrefillPagedEngine(model, **kw))
             for _ in range(2)],
            [DecodeWorker(ContinuousBatchingEngine(model, **kw))
             for _ in range(n_decode)],
            lease_misses=2, spill_depth=100)
        scaler = Autoscaler(
            fleet,
            lambda: ContinuousBatchingEngine(model, **kw),
            config=scfg) if autoscale else None
        state = {"killed": False, "worker_ticks": 0,
                 "peak": n_decode, "clock": 0}

        def submit(r):
            return fleet.submit(
                r.prompt, max_new_tokens=r.max_new_tokens,
                temperature=r.temperature, top_k=r.top_k, seed=r.seed,
                arrival_step=r.arrival_step, tenant=r.tenant,
                priority=r.priority)

        def on_tick(clock):
            state["clock"] = clock
            if not state["killed"] and clock >= kill_tick:
                live = [i for i, d in enumerate(fleet.decode)
                        if not d.killed]
                if len(live) > 1:
                    fleet.kill_decode_worker(live[-1])
                    state["killed"] = True
            n_live = len(fleet._live_decode())
            state["worker_ticks"] += n_live
            state["peak"] = max(state["peak"], n_live)
            if scaler is not None:
                scaler.on_tick(clock)

        t0 = time.perf_counter()
        ids = replay(trace, submit, fleet.tick, fleet.busy,
                     max_ticks=3000, on_tick=on_tick)
        while state["clock"] < total_ticks:
            fleet.tick()
            on_tick(state["clock"] + 1)
        dt = time.perf_counter() - t0
        # zero block leaks on every surviving arena — including the
        # workers the autoscaler scaled in and the ones it drained
        for w in list(fleet.prefill) + list(fleet.decode):
            if fleet._alive(w.name) and hasattr(w.engine, "manager"):
                assert not w.engine.manager._ref, \
                    f"block leak on {w.name}"
                w.engine.manager.assert_consistent()
        res = fleet.results
        ttft = {}
        for w in list(fleet.prefill) + list(fleet.decode):
            ttft.update(w.server.ttft)
        rows, completed_tokens = {}, 0
        for tid, rid in ids.items():
            v = res.get(rid)
            if v is not None and not isinstance(v, RequestFailure):
                rows[tid] = np.asarray(v)
                completed_tokens += int(np.asarray(v).size)
        attain = [1 for tid, rid in ids.items()
                  if tid in rows and rid in ttft
                  and ttft[rid] <= scfg.ttft_slo_s]
        return {
            "fleet": fleet, "scaler": scaler, "ids": ids,
            "rows": rows, "dt": dt,
            "completed": len(rows), "failed": len(ids) - len(rows),
            "tokens": completed_tokens,
            "worker_ticks": state["worker_ticks"],
            "peak": state["peak"],
            "end_live": len(fleet._live_decode()),
            "attainment": len(attain) / max(len(rows), 1),
            "ticks": fleet.stats()["ticks"],
        }

    # warm-up: compiles land here so the arms compare steady states
    drive(scfg.min_decode, autoscale=False)
    om.reset()
    om.enable(True)
    try:
        auto = drive(scfg.min_decode, autoscale=True)
        peak = drive(scfg.max_decode, autoscale=False)
        mini = drive(scfg.min_decode, autoscale=False)
    finally:
        om.enable(False)

    both = sorted(set(auto["rows"]) & set(peak["rows"]))
    identical = all(np.array_equal(auto["rows"][t], peak["rows"][t])
                    for t in both)
    greedy_ok = True
    for t in both[:8]:
        r = trace.requests[t]
        if r.temperature > 0.0:
            continue
        ref = model.generate(paddle.to_tensor(r.prompt[None, :]),
                             max_new_tokens=r.max_new_tokens
                             ).numpy()[0]
        greedy_ok = greedy_ok and np.array_equal(auto["rows"][t], ref)
    compiles = max(
        (d.engine.decode_compile_count()
         for d in auto["fleet"].decode), default=1)
    sc = auto["scaler"].stats()
    return {
        "serving_autoscale_requests": len(trace),
        "serving_autoscale_burst_window": [int(b0), int(b1)],
        "serving_autoscale_kill_tick": int(kill_tick),
        "serving_autoscale_bit_identical_vs_peak": bool(identical),
        "serving_autoscale_greedy_matches_generate": bool(greedy_ok),
        "serving_autoscale_decode_compiles": int(compiles),
        "serving_autoscale_scale_ups": sc["scale_ups"],
        "serving_autoscale_scale_downs": sc["scale_downs"],
        "serving_autoscale_removals": sc["removals"],
        "serving_autoscale_peak_size": auto["peak"],
        "serving_autoscale_end_size": auto["end_live"],
        "serving_autoscale_returned_to_min": bool(
            auto["end_live"] == scfg.min_decode),
        "serving_autoscale_completed": auto["completed"],
        "serving_autoscale_failed": auto["failed"],
        "serving_autoscale_attainment": round(auto["attainment"], 4),
        "serving_autoscale_attainment_static_peak": round(
            peak["attainment"], 4),
        "serving_autoscale_attainment_static_min": round(
            mini["attainment"], 4),
        "serving_autoscale_worker_ticks": auto["worker_ticks"],
        "serving_autoscale_worker_ticks_static_peak":
            peak["worker_ticks"],
        "serving_autoscale_worker_ticks_static_min":
            mini["worker_ticks"],
        "serving_autoscale_worker_tick_ratio_vs_peak": round(
            auto["worker_ticks"] / max(peak["worker_ticks"], 1), 3),
        "serving_autoscale_goodput_per_worker_tick": round(
            auto["tokens"] / max(auto["worker_ticks"], 1), 3),
        "serving_autoscale_goodput_per_worker_tick_static_peak": round(
            peak["tokens"] / max(peak["worker_ticks"], 1), 3),
        "serving_autoscale_goodput_per_worker_tick_static_min": round(
            mini["tokens"] / max(mini["worker_ticks"], 1), 3),
        "serving_autoscale_tokens_per_sec": round(
            auto["tokens"] / auto["dt"], 1) if auto["dt"] else 0.0,
        "serving_autoscale_leaks": 0,
    }


def run_serving_recovery_bench(seed: int = 0, requests: int = 6,
                               max_new: int = 10) -> dict:
    """Durable-control-plane stage (serving/durability.py +
    fleet.py): ONE seeded workload run twice — a CLEAN arm straight
    to idle, and a CRASHED arm that checkpoints mid-traffic, submits
    more, is killed two ticks later with streams in every state, and
    comes back via ``Fleet.recover``.

    What the stage pins every round:

    - **bit-identity through the crash**: every row the crashed arm
      completes must equal the clean arm's token-for-token (greedy
      AND seeded-sampled) — the whole point of journaled rng keys +
      redrive;
    - **recovery cost**: wall time of ``Fleet.recover`` itself
      (manifest load + journal replay + worker restore + redrive
      dispatch), the journal records replayed, and the streams
      redriven;
    - the compile-count pin: recovery reuses the restored arenas —
      decode compiles stay 1 per engine, no new programs on the
      steady path;
    - zero block leaks on every recovered arena.
    """
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    DecodeWorker, Fleet,
                                    PrefillPagedEngine, PrefillWorker,
                                    RequestFailure)

    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    kw = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)
    rs = np.random.RandomState(seed)
    lens = rs.randint(5, 18, size=requests)
    prompts = [rs.randint(0, cfg.vocab_size, (int(L),)).astype(np.int32)
               for L in lens]
    sample_kw = [{} if i % 3 else
                 {"temperature": 0.9, "top_k": 40, "seed": 11 + i}
                 for i in range(requests)]

    pf = [PrefillPagedEngine(model, **kw) for _ in range(2)]
    dc = [ContinuousBatchingEngine(model, **kw)
          for _ in range(2)]
    by_name = {f"prefill{i}": e for i, e in enumerate(pf)}
    by_name.update({f"decode{i}": e for i, e in enumerate(dc)})

    def submit_all(fleet):
        """First half before the mid-run boundary, second half after —
        the caller decides what the boundary is (checkpoint or just
        ticks). Returns {rid: prompt index}."""
        rid_of = {}
        for i in range(requests // 2):
            rid_of[fleet.submit(prompts[i], max_new_tokens=max_new,
                                **sample_kw[i])] = i
        return rid_of

    def submit_rest(fleet, rid_of):
        for i in range(requests // 2, requests):
            rid_of[fleet.submit(prompts[i], max_new_tokens=max_new,
                                **sample_kw[i])] = i
        return rid_of

    def rows_of(fleet, rid_of):
        res = fleet.results
        out = {}
        for rid, i in rid_of.items():
            v = res.get(rid)
            if v is not None and not isinstance(v, RequestFailure):
                out[i] = np.asarray(v)
        return out

    # -- clean arm (also the warm-up: compiles land here) --
    for e in list(by_name.values()):
        e.reset()
    clean_fleet = Fleet([PrefillWorker(e) for e in pf],
                        [DecodeWorker(e) for e in dc])
    rid_of = submit_all(clean_fleet)
    for _ in range(4):
        clean_fleet.tick()
    submit_rest(clean_fleet, rid_of)
    t0 = time.perf_counter()
    clean_fleet.run_until_idle(max_ticks=600)
    clean_dt = time.perf_counter() - t0
    clean_rows = rows_of(clean_fleet, rid_of)
    del clean_fleet

    # -- crashed arm --
    d = tempfile.mkdtemp(prefix="pt-recovery-bench-")
    try:
        for e in list(by_name.values()):
            e.reset()
        fleet = Fleet([PrefillWorker(e) for e in pf],
                      [DecodeWorker(e) for e in dc], durability=d)
        rid_of2 = submit_all(fleet)
        for _ in range(4):
            fleet.tick()
        t0 = time.perf_counter()
        fleet.checkpoint()
        ckpt_dt = time.perf_counter() - t0
        submit_rest(fleet, rid_of2)
        for _ in range(2):
            fleet.tick()
        journal_appends = fleet._journal.appends
        del fleet                       # CRASH: only the dir survives
        for e in list(by_name.values()):
            e.reset()
        t0 = time.perf_counter()
        fleet2 = Fleet.recover(
            d, engine_factory=lambda role, name: by_name[name])
        recover_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        fleet2.run_until_idle(max_ticks=600)
        drain_dt = time.perf_counter() - t0
        crashed_rows = rows_of(fleet2, rid_of2)
        lr = dict(fleet2.last_recovery)
        leaks = 0
        for w in list(fleet2.prefill) + list(fleet2.decode):
            if hasattr(w.engine, "manager"):
                leaks += len(w.engine.manager._ref)
        compiles = max((dw.engine.decode_compile_count()
                        for dw in fleet2.decode), default=1)
        del fleet2
    finally:
        shutil.rmtree(d, ignore_errors=True)

    identical = (sorted(clean_rows) == sorted(crashed_rows)
                 and all(np.array_equal(clean_rows[i], crashed_rows[i])
                         for i in clean_rows))
    return {
        "serving_recovery_requests": int(requests),
        "serving_recovery_bit_identical": bool(identical),
        "serving_recovery_completed": len(crashed_rows),
        "serving_recovery_journal_appends": int(journal_appends),
        "serving_recovery_journal_replayed": int(lr["replayed"]),
        "serving_recovery_redriven": int(lr["redriven"]),
        "serving_recovery_torn_tail": bool(lr["torn_tail"]),
        "serving_recovery_checkpoint_wall_s": round(ckpt_dt, 4),
        "serving_recovery_recover_wall_s": round(recover_dt, 4),
        "serving_recovery_drain_wall_s": round(drain_dt, 4),
        "serving_recovery_clean_wall_s": round(clean_dt, 4),
        "serving_recovery_decode_compiles": int(compiles),
        "serving_recovery_leaks": int(leaks),
    }
