"""SLO-driven autoscaling (serving/loadgen.py + autoscaler.py): the
deterministic trace generator (byte-identical replay, JSON round-trip,
per-component stream independence), the rolling-window histogram
quantile the control loop reads, cost-aware prefix eviction
(least-reused-first with LRU tiebreak), the pure decision kernel pinned
against synthetic metric streams (hysteresis through flap, cooldown
against thrash, min/max bounds, below-min repair bypassing both), and
the headline kill-and-burst integration pin: the fleet scales up on the
burst, repairs a mid-burst worker kill, drains back to the min size,
every stream ends terminal, and completed streams stay BIT-IDENTICAL
to a static-fleet run (greedy + seeded-sampled, paged and
paged+kv_int8) with decode compile counts still 1."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability.metrics import Histogram
from paddle_tpu.serving import (Autoscaler, AutoscalerConfig,
                                BlockManager, ContinuousBatchingEngine,
                                DecisionKernel, DecodeWorker, Fleet,
                                Observation, PrefillPagedEngine,
                                PrefillWorker, RequestFailure, Trace,
                                TraceConfig, generate_trace, replay)
from paddle_tpu.utils import faults

FAIL_REASONS = ("timeout", "poisoned", "circuit_open", "shed",
                "handoff", "worker_lost")


@pytest.fixture(scope="module")
def setup():
    """One model + the paged engine pools for the whole file: 2
    prefill, 2 base decode, 2 spare decode for the warm scale-up
    factory — and the kv_int8 set (1 prefill, 2+2 decode). reset()
    frees slots/blocks, never the compiled programs."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    kw = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)
    pf = [PrefillPagedEngine(model, **kw) for _ in range(2)]
    dc = [ContinuousBatchingEngine(model, paged=True, **kw)
          for _ in range(4)]
    pf8 = [PrefillPagedEngine(model, kv_int8=True, **kw)]
    dc8 = [ContinuousBatchingEngine(model, paged=True, kv_int8=True,
                                    **kw) for _ in range(4)]
    return model, cfg, pf, dc, pf8, dc8


@pytest.fixture(autouse=True)
def _disarmed():
    faults.clear()
    yield
    faults.clear()


# NOTE on the persistent jax compile cache: this module builds many
# near-identical paged backends (warm spares for the scale-up
# factory). Under the tier-1 flags (-p no:xdist -p no:randomly) the
# cache stays ON deliberately — identical programs deserialize from
# the on-disk cache instead of recompiling, which keeps in-process
# native-heap churn low (the test_resilience._no_compile_cache
# docstring records that the cache/plugin corruption needs the xdist/
# randomly plugins loaded; under tier-1 flags cache-on is green).


def _ref(model, prompt, max_new, **kw):
    return model.generate(paddle.to_tensor(prompt[None, :]),
                          max_new_tokens=max_new, **kw).numpy()[0]


def _reset(*engines):
    for e in engines:
        e.reset()


# ---------------------------------------------------------------------------
# loadgen: deterministic trace generation
# ---------------------------------------------------------------------------
class TestLoadgen:
    CFG = dict(seed=7, horizon=50, base_rate=0.4, bursts=1,
               burst_mult=6.0, burst_len=(10, 14), diurnal_period=30,
               diurnal_amplitude=0.4, prompt_lo=4, prompt_hi=20,
               output_lo=4, output_hi=16, vocab_size=256,
               shared_fraction=0.4, shared_len=8,
               sampled_fraction=0.3,
               tenants={"a": 1.0, "b": 2.0},
               priority_weights={0: 3.0, 5: 1.0})

    def test_byte_identical_replay(self):
        a = generate_trace(TraceConfig(**self.CFG))
        b = generate_trace(TraceConfig(**self.CFG))
        assert a.to_json() == b.to_json()

    def test_json_round_trip(self):
        a = generate_trace(TraceConfig(**self.CFG))
        b = Trace.from_json(a.to_json())
        assert b.to_json() == a.to_json()
        assert len(b) == len(a)
        for x, y in zip(a.requests, b.requests):
            assert np.array_equal(x.prompt, y.prompt)
            assert (x.arrival_step, x.max_new_tokens, x.temperature,
                    x.top_k, x.seed, x.tenant, x.priority) \
                == (y.arrival_step, y.max_new_tokens, y.temperature,
                    y.top_k, y.seed, y.tenant, y.priority)

    def test_schedule_properties(self):
        t = generate_trace(TraceConfig(**self.CFG))
        assert len(t) > 0
        for r in t.requests:
            assert 0 <= r.arrival_step < t.config.horizon
            assert 4 <= r.prompt.size <= 20
            assert 4 <= r.max_new_tokens <= 16
            assert r.tenant in ("a", "b")
            assert r.priority in (0, 5)
            if r.temperature > 0:
                assert r.top_k == t.config.top_k
        assert any(r.temperature > 0 for r in t.requests)
        assert any(r.temperature == 0 for r in t.requests)
        # trace-local ids are the list indices (replay maps them)
        assert [r.request_id for r in t.requests] \
            == list(range(len(t)))

    def test_burst_elevates_arrival_rate(self):
        t = generate_trace(TraceConfig(
            seed=3, horizon=60, base_rate=0.2, bursts=1,
            burst_mult=8.0, burst_len=(12, 16)))
        (b0, b1), = t.burst_windows
        per_tick = np.zeros(60)
        for r in t.requests:
            per_tick[r.arrival_step] += 1
        inside = per_tick[b0:b1].mean()
        outside = np.concatenate(
            [per_tick[:b0], per_tick[b1:]]).mean()
        assert inside > outside * 2

    def test_shared_fraction_reuses_prefixes(self):
        t = generate_trace(TraceConfig(
            seed=1, horizon=60, base_rate=0.5, shared_fraction=0.6,
            shared_len=8, prompt_lo=10, prompt_hi=16))
        heads = {}
        for r in t.requests:
            h = tuple(int(x) for x in r.prompt[:8])
            heads[h] = heads.get(h, 0) + 1
        assert max(heads.values()) > 1
        assert t.stats()["shared_prefix"] > 1

    def test_component_stream_independence(self):
        """Changing the sampled fraction must not shift arrival ticks
        or prompt lengths — each stochastic component owns its rng
        stream (the faults.py discipline)."""
        base = dict(self.CFG)
        a = generate_trace(TraceConfig(**base))
        base["sampled_fraction"] = 0.0
        b = generate_trace(TraceConfig(**base))
        assert [r.arrival_step for r in a.requests] \
            == [r.arrival_step for r in b.requests]
        assert [int(r.prompt.size) for r in a.requests] \
            == [int(r.prompt.size) for r in b.requests]
        assert [r.tenant for r in a.requests] \
            == [r.tenant for r in b.requests]

    def test_replay_open_loop_driver(self):
        t = generate_trace(TraceConfig(seed=2, horizon=10,
                                       base_rate=0.5))
        submitted, ticks = [], [0]

        def submit(r):
            submitted.append(r.request_id)
            return 1000 + r.request_id

        def tick():
            ticks[0] += 1

        ids = replay(t, submit, tick, lambda: False)
        assert sorted(ids) == sorted(r.request_id for r in t.requests)
        assert all(ids[k] == 1000 + k for k in ids)
        assert ticks[0] == t.config.horizon


# ---------------------------------------------------------------------------
# satellite: rolling-window histogram quantiles
# ---------------------------------------------------------------------------
class TestRecentQuantile:
    def _hist(self, **kw):
        return Histogram("t_recent_q", buckets=(0.1, 1.0), **kw)

    def test_window_semantics(self):
        om.enable(True)
        try:
            h = self._hist()
            for v in range(1, 11):
                h.observe(float(v))
            assert h.recent_quantile(0.0) == 1.0
            assert h.recent_quantile(1.0) == 10.0
            # window keeps the LAST n observations: [7, 8, 9, 10]
            assert h.recent_quantile(0.0, window=4) == 7.0
            assert h.recent_quantile(0.5, window=4) == 8.0
            assert h.recent_quantile(1.0, window=4) == 10.0
            # window larger than retained samples → everything
            assert h.recent_quantile(0.0, window=99) == 1.0
            assert h.recent_count() == 10
        finally:
            om.enable(False)

    def test_ring_is_bounded(self):
        om.enable(True)
        try:
            h = self._hist(recent_cap=4)
            for v in range(1, 7):
                h.observe(float(v))
            assert h.recent_count() == 4
            assert h.recent_quantile(0.0) == 3.0   # 1, 2 aged out
            assert h.count() == 6                  # cumulative intact
        finally:
            om.enable(False)

    def test_per_label_rings(self):
        om.enable(True)
        try:
            h = Histogram("t_recent_q_lbl", labels=("w",),
                          buckets=(1.0,))
            h.observe(1.0, w="a")
            h.observe(9.0, w="b")
            assert h.recent_quantile(1.0, w="a") == 1.0
            assert h.recent_quantile(1.0, w="b") == 9.0
        finally:
            om.enable(False)

    def test_disabled_is_zero_cost_and_none(self):
        om.enable(False)
        h = self._hist()
        h.observe(5.0)
        assert h.recent_count() == 0
        assert h.recent_quantile(0.5) is None

    def test_validation_and_clear(self):
        om.enable(True)
        try:
            h = self._hist()
            h.observe(1.0)
            with pytest.raises(ValueError):
                h.recent_quantile(1.5)
            with pytest.raises(ValueError):
                h.recent_quantile(0.5, window=0)
            h.clear()
            assert h.recent_quantile(0.5) is None
            assert h.recent_count() == 0
        finally:
            om.enable(False)


# ---------------------------------------------------------------------------
# satellite: cost-aware prefix eviction
# ---------------------------------------------------------------------------
class TestCostAwareEviction:
    def _park(self, m, tokens):
        """Allocate + register + release one block → parked in the
        LRU cache, matchable."""
        ids = m.allocate(1)
        m.register_prefix(tokens, ids)
        m.release(ids)
        return ids[0]

    def test_reused_prefix_outlives_cold_chain(self):
        """A shared system prompt with observed prefix-index hits must
        outlive a NEWER cold chain — the reuse tally outranks LRU
        age."""
        m = BlockManager(num_blocks=6, block_size=4)
        pa = np.arange(5, dtype=np.int32)           # the hot prefix
        pb = np.arange(100, 105, dtype=np.int32)    # the cold chain
        a = self._park(m, pa)
        got = m.match_prefix(pa)                    # one observed hit
        assert got == [a]
        m.release(got)
        b = self._park(m, pb)
        # old LRU order would evict a first had it not been
        # resurrected; with the re-park, a and b are both cached and b
        # is the younger — pure LRU evicts a, cost-aware evicts b
        assert m.evict_cached(1) == 1
        assert m.match_prefix(pb) == []             # cold chain gone
        hot = m.match_prefix(pa)                    # hot prefix lives
        assert hot == [a]
        m.release(hot)
        m.assert_consistent()

    def test_zero_hits_degrades_to_lru(self):
        """With no observed reuse anywhere the ordering is exactly the
        old LRU: oldest parked block evicts first."""
        m = BlockManager(num_blocks=6, block_size=4)
        a = self._park(m, np.arange(5, dtype=np.int32))
        b = self._park(m, np.arange(50, 55, dtype=np.int32))
        assert m.evict_cached(1) == 1
        assert m.match_prefix(np.arange(5, dtype=np.int32)) == []
        keep = m.match_prefix(np.arange(50, 55, dtype=np.int32))
        assert keep == [b]
        m.release(keep)
        m.assert_consistent()

    def test_allocate_evicts_least_reused(self):
        """The allocate-path eviction (pool pressure) uses the same
        victim policy as the explicit watermark tier."""
        m = BlockManager(num_blocks=4, block_size=4)   # 3 usable
        pa = np.arange(5, dtype=np.int32)
        pb = np.arange(100, 105, dtype=np.int32)
        a = self._park(m, pa)
        got = m.match_prefix(pa)
        m.release(got)
        self._park(m, pb)
        # free list is down to 1; asking for 2 must evict — the cold
        # chain goes, the hot prefix survives
        out = m.allocate(2)
        assert out is not None and len(out) == 2
        assert m.evictions == 1
        assert m.match_prefix(pb) == []
        hot = m.match_prefix(pa)
        assert hot == [a]
        m.release(hot)
        m.release(out)
        m.assert_consistent()

    def test_hits_never_leak_stale_entries(self):
        m = BlockManager(num_blocks=6, block_size=4)
        pa = np.arange(5, dtype=np.int32)
        a = self._park(m, pa)
        got = m.match_prefix(pa)
        m.release(got)
        assert m._hits.get(a) == 1
        assert m.evict_cached(1) == 1
        assert a not in m._hits          # tally died with the block
        m.assert_consistent()


# ---------------------------------------------------------------------------
# the decision kernel, in isolation (synthetic metric streams)
# ---------------------------------------------------------------------------
def _kcfg(**kw):
    base = dict(ttft_slo_s=0.25, window=8, queue_high=4,
                pressure_high=0.9, breach_intervals=2,
                clear_intervals=2, up_cooldown=2, down_cooldown=2,
                min_decode=1, max_decode=3)
    base.update(kw)
    return AutoscalerConfig(**base)


def _obs(ttft=None, queue=0, pressure=0.0, size=2, draining=0,
         dead=0):
    return Observation(ttft_p95_s=ttft, queue_depth=queue,
                       block_pressure=pressure, fleet_size=size,
                       draining=draining, dead=dead)


class TestDecisionKernel:
    def test_breach_needs_hysteresis(self):
        k = DecisionKernel(_kcfg())
        seq = [k.decide(_obs(ttft=0.5)).action for _ in range(2)]
        assert seq == ["hold", "up"]   # one noisy sample never scales

    def test_flap_never_acts(self):
        k = DecisionKernel(_kcfg())
        seq = [k.decide(_obs(ttft=0.5 if i % 2 == 0 else 0.01))
               .action for i in range(8)]
        assert seq == ["hold"] * 8

    def test_up_cooldown_suppresses_thrash(self):
        k = DecisionKernel(_kcfg())
        seq = [k.decide(_obs(queue=9)).action for _ in range(8)]
        assert seq == ["hold", "up", "hold", "hold", "up",
                       "hold", "hold", "up"]

    def test_down_cooldown_suppresses_thrash(self):
        k = DecisionKernel(_kcfg())
        seq = [k.decide(_obs(size=3)).action for _ in range(8)]
        assert seq == ["hold", "down", "hold", "hold", "down",
                       "hold", "hold", "down"]

    def test_up_arms_down_cooldown(self):
        """Fresh capacity is never immediately drained: the up also
        arms the down-cooldown, delaying the first down past the
        clear hysteresis alone."""
        k = DecisionKernel(_kcfg(clear_intervals=2, down_cooldown=2))
        assert k.decide(_obs(ttft=0.5)).action == "hold"
        assert k.decide(_obs(ttft=0.5)).action == "up"
        seq = [k.decide(_obs(ttft=0.01, size=3)).action
               for _ in range(4)]
        # hysteresis alone would allow a down at seq[1]; the armed
        # down-cooldown pushes it to seq[2]
        assert seq == ["hold", "hold", "down", "hold"]

    def test_max_bound_never_crossed(self):
        k = DecisionKernel(_kcfg(max_decode=2))
        out = [k.decide(_obs(queue=9, size=2)) for _ in range(6)]
        assert all(d.action != "up" for d in out)
        assert any(d.reason == "at_max" for d in out)

    def test_min_bound_never_crossed(self):
        k = DecisionKernel(_kcfg(min_decode=2))
        out = [k.decide(_obs(ttft=0.01, size=2)) for _ in range(6)]
        assert all(d.action != "down" for d in out)
        assert any(d.reason == "at_min" for d in out)

    def test_draining_workers_do_not_count_as_capacity(self):
        # 3 live but 2 already draining → routable 1 == min: no down
        k = DecisionKernel(_kcfg(min_decode=1))
        out = [k.decide(_obs(ttft=0.01, size=3, draining=2))
               for _ in range(4)]
        assert all(d.action != "down" for d in out)

    def test_lease_death_bypasses_cooldown(self):
        """A worker lost mid-cooldown is topology damage, not a noisy
        signal: repair fires immediately, cooldown or not."""
        k = DecisionKernel(_kcfg(min_decode=2, max_decode=4,
                                 up_cooldown=5))
        assert k.decide(_obs(queue=9, size=2)).action == "hold"
        assert k.decide(_obs(queue=9, size=2)).action == "up"
        assert k.up_cold == 5                       # cooling down
        d = k.decide(_obs(queue=9, size=1, dead=1))  # lease death
        assert (d.action, d.reason) == ("up", "below_min")

    def test_missing_ttft_is_not_a_breach(self):
        k = DecisionKernel(_kcfg())
        seq = [k.decide(_obs(ttft=None, size=2)).action
               for _ in range(3)]
        assert "up" not in seq
        # but the other signals stay actionable without TTFT data
        k2 = DecisionKernel(_kcfg())
        seq2 = [k2.decide(_obs(ttft=None, queue=9)).action
                for _ in range(2)]
        assert seq2 == ["hold", "up"]


# ---------------------------------------------------------------------------
# the autoscaler against a live fleet
# ---------------------------------------------------------------------------
def _mk_fleet(pf_engines, dc_engines, **kw):
    return Fleet([PrefillWorker(e) for e in pf_engines],
                 [DecodeWorker(e) for e in dc_engines],
                 spill_depth=100, **kw)


def _spare_factory(spares):
    pool = list(spares)

    def factory():
        e = pool.pop(0)
        e.reset()
        return e
    return factory


class TestAutoscalerOnFleet:
    def test_dry_run_acts_on_nothing(self, setup):
        model, cfg, pf, dc, pf8, dc8 = setup
        _reset(*(pf[:2] + dc[:2]))
        fleet = _mk_fleet(pf[:2], dc[:2])
        sc = Autoscaler(fleet, _spare_factory(dc[2:]),
                        config=AutoscalerConfig(
                            queue_high=-1, breach_intervals=1,
                            min_decode=1, max_decode=4,
                            up_cooldown=0, dry_run=True))
        for _ in range(3):
            d = sc.step()
            assert d.action == "up" and not d.acted
        assert len(fleet.decode) == 2            # fleet untouched
        assert sc.scale_ups == 0
        ev = [e for e in fleet.flight.events()
              if e["kind"] == "autoscale"]
        assert len(ev) == 3 and all(e["dry_run"] for e in ev)

    def test_scale_action_retries_under_faults(self, setup):
        """A transiently-failing scale action (the fleet.scale site)
        retries under the PR 5 policy and still lands."""
        model, cfg, pf, dc, pf8, dc8 = setup
        _reset(*(pf[:2] + dc[:3]))
        fleet = _mk_fleet(pf[:2], dc[:2])
        sc = Autoscaler(fleet, _spare_factory(dc[2:3]),
                        config=AutoscalerConfig(
                            queue_high=-1, breach_intervals=1,
                            min_decode=1, max_decode=3,
                            up_cooldown=0))
        with faults.injected("fleet.scale:at=1"):
            d = sc.step()
        assert d.action == "up" and d.acted
        assert len(fleet.decode) == 3
        assert fleet.decode[-1].name == "scale0"
        assert sc.retries >= 1

    def test_exhausted_retries_drop_the_action(self, setup):
        model, cfg, pf, dc, pf8, dc8 = setup
        _reset(*(pf[:2] + dc[:3]))
        fleet = _mk_fleet(pf[:2], dc[:2])
        sc = Autoscaler(fleet, _spare_factory(dc[2:3]),
                        config=AutoscalerConfig(
                            queue_high=-1, breach_intervals=1,
                            min_decode=1, max_decode=3,
                            up_cooldown=0))
        with faults.injected("fleet.scale:every=1"):
            d = sc.step()
        assert d.action == "up" and not d.acted
        assert len(fleet.decode) == 2            # dropped, not wedged
        assert any(e["kind"] == "autoscale_action_failed"
                   for e in fleet.flight.events())

    def test_decision_metrics_exported(self, setup):
        model, cfg, pf, dc, pf8, dc8 = setup
        _reset(*(pf[:2] + dc[:2]))
        fleet = _mk_fleet(pf[:2], dc[:2])
        sc = Autoscaler(fleet, _spare_factory([]),
                        config=AutoscalerConfig(dry_run=True))
        om.reset()
        om.enable(True)
        try:
            sc.step()
            sc.step()
            dec = om.REGISTRY.get("pt_autoscaler_decisions_total")
            size = om.REGISTRY.get("pt_autoscaler_fleet_size")
            assert dec.value(action="hold") == 2
            assert size.value() == 2
        finally:
            om.enable(False)
            om.reset()


# ---------------------------------------------------------------------------
# the headline pin: kill-and-burst, autoscaled vs static, bit-identical
# ---------------------------------------------------------------------------
class TestAutoscaleKillBurst:
    TRACE = dict(horizon=20, base_rate=0.25, bursts=1,
                 burst_mult=5.0, burst_len=(6, 9), prompt_lo=4,
                 prompt_hi=12, output_lo=4, output_hi=8,
                 shared_fraction=0.25, shared_len=8,
                 sampled_fraction=0.3)

    def _drive(self, trace, pf_engines, dc_engines, factory,
               autoscale, kill_ticks):
        _reset(*(list(pf_engines) + list(dc_engines)))
        fleet = _mk_fleet(pf_engines, dc_engines, lease_misses=2)
        scfg = AutoscalerConfig(
            min_decode=2, max_decode=4, interval_ticks=2,
            queue_high=1, ttft_slo_s=10.0, breach_intervals=2,
            clear_intervals=3, up_cooldown=2, down_cooldown=2)
        scaler = Autoscaler(fleet, factory,
                            config=scfg) if autoscale else None
        state = {"killed": 0, "clock": 0}
        kills = list(kill_ticks or ())

        def submit(r):
            return fleet.submit(
                r.prompt, max_new_tokens=r.max_new_tokens,
                temperature=r.temperature, top_k=r.top_k,
                seed=r.seed, arrival_step=r.arrival_step,
                tenant=r.tenant, priority=r.priority)

        def on_tick(clock):
            state["clock"] = clock
            if (state["killed"] < len(kills)
                    and clock >= kills[state["killed"]]):
                live = [i for i, d in enumerate(fleet.decode)
                        if not d.killed]
                if len(live) > 1:
                    fleet.kill_decode_worker(live[-1])
                    state["killed"] += 1
            if scaler is not None:
                scaler.on_tick(clock)

        ids = replay(trace, submit, fleet.tick, fleet.busy,
                     max_ticks=2000, on_tick=on_tick)
        total = trace.config.horizon + 40
        while state["clock"] < total:
            fleet.tick()
            on_tick(state["clock"] + 1)
        res = fleet.results
        rows = {}
        for tid, rid in ids.items():
            assert rid in res, f"request {rid} vanished"
            v = res[rid]
            if isinstance(v, RequestFailure):
                assert v.reason in FAIL_REASONS
            else:
                rows[tid] = np.asarray(v)
        # zero leaks on every surviving arena
        for w in list(fleet.prefill) + list(fleet.decode):
            if fleet._alive(w.name) and hasattr(w.engine, "manager"):
                assert not w.engine.manager._ref
                w.engine.manager.assert_consistent()
        return fleet, scaler, rows

    def _run_variant(self, model, cfg, pf_engines, dc_engines,
                     spares, mk_engine, seed, **trace_kw):
        trace = generate_trace(TraceConfig(
            seed=seed, vocab_size=cfg.vocab_size,
            **{**self.TRACE, **trace_kw}))
        b0, b1 = trace.burst_windows[0]
        # kill 1: mid-burst, while the autoscaler is scaling — the
        # lost streams redrive under load.  kill 2: after the drain
        # has the fleet back at min size, so routable capacity
        # provably drops below min and the repair path must fire.
        kill_ticks = [(b0 + b1) // 2, trace.config.horizon + 15]
        pool = list(spares)
        for e in pool:
            e.reset()

        def factory():
            # warm spares first (pre-compiled, reset between runs);
            # a fresh engine past the pool still compiles exactly once
            return pool.pop(0) if pool else mk_engine()

        # static reference arm: same trace, no kill, no scaling
        _, _, ref_rows = self._drive(trace, pf_engines, dc_engines,
                                     factory, False, None)
        fleet, scaler, rows = self._drive(
            trace, pf_engines, dc_engines, factory, True, kill_ticks)

        # the loop converged: up on the burst, the kill repaired
        # (below_min bypass), drained back to the min afterwards
        assert scaler.scale_ups >= 1
        assert any(d.reason == "below_min" for d in scaler.decisions)
        assert scaler.peak_size > 2
        assert len(fleet._live_decode()) == 2
        assert scaler.scale_downs >= 1 and scaler.removals >= 1

        # bit-identity through every scale event, greedy AND
        # seeded-sampled: completed streams match the static run
        both = set(rows) & set(ref_rows)
        assert len(both) >= len(trace) * 0.8
        for t in both:
            assert np.array_equal(rows[t], ref_rows[t]), \
                f"stream {t} diverged across scale events"
        sampled = [t for t in both
                   if trace.requests[t].temperature > 0]
        assert sampled, "trace produced no sampled requests"
        greedy = [t for t in both
                  if trace.requests[t].temperature == 0]
        for t in greedy[:3]:
            r = trace.requests[t]
            assert np.array_equal(
                rows[t], _ref(model, r.prompt, r.max_new_tokens))

        # compile counts: nothing EVER recompiles across scale events
        # (a scaled-in repair worker that never served stays at 0)
        for d in fleet.decode:
            assert d.engine.decode_compile_count() <= 1
        assert any(d.engine.decode_compile_count() == 1
                   for d in fleet.decode)
        for w in fleet.prefill:
            assert w.engine.prefill_compile_count() == 1

    KW = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)

    def test_paged(self, setup):
        model, cfg, pf, dc, pf8, dc8 = setup
        self._run_variant(
            model, cfg, pf[:2], dc[:2], dc[2:],
            lambda: ContinuousBatchingEngine(model, paged=True,
                                             **self.KW), seed=0)

    def test_paged_kv_int8(self, setup):
        model, cfg, pf, dc, pf8, dc8 = setup
        # seed=1's base trace is too light to ever breach queue_high;
        # thicken the arrival process so the burst forces a scale-up
        self._run_variant(
            model, cfg, pf8, dc8[:2], dc8[2:],
            lambda: ContinuousBatchingEngine(model, paged=True,
                                             kv_int8=True, **self.KW),
            seed=1, base_rate=0.5, burst_mult=6.0)


def test_autoscale_soak_entry_point_runs_and_reports(run_soak):
    """``tools/autoscale_soak.sh`` at its smallest: one burst with a kill
    inside it over three fleets, and every key the script asserts on."""
    out = run_soak("run_serving_autoscale_bench", horizon=12, max_new=4)
    assert out["serving_autoscale_completed"] \
        + out["serving_autoscale_failed"] \
        == out["serving_autoscale_requests"] > 0
    assert out["serving_autoscale_bit_identical_vs_peak"]
    assert out["serving_autoscale_greedy_matches_generate"]
    assert out["serving_autoscale_returned_to_min"]
    assert out["serving_autoscale_decode_compiles"] == 1
    assert out["serving_autoscale_leaks"] == 0
    assert out["serving_autoscale_scale_ups"] >= 1
    assert {"serving_autoscale_scale_downs", "serving_autoscale_peak_size",
            "serving_autoscale_end_size"} <= set(out)
