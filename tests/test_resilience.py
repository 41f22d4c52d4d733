"""Serving resilience subsystem (serving/resilience.py +
utils/faults.py): deterministic fault injection, deadlines +
cancellation, load shedding, retry/backoff, circuit breaker, the
NaN-logit quarantine, crash-safe snapshot/restore (whole-prompt and
mid-chunked-prefill kill points, bit-identical resume), the
arena-validation livelock regression, and a
chaos suite driving seeded randomized fault schedules against the
accounting invariants (every request completed or explicitly failed,
zero slot leaks, BlockManager.assert_consistent clean)."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (BlockManager, ContinuousBatchingEngine,
                                RequestFailure, ResilienceConfig,
                                Scheduler, Server)
from paddle_tpu.utils import faults


@pytest.fixture(scope="module")
def setup():
    """One model + two engines for the whole file: one at the default
    geometry (16-token blocks, a whole prompt in one chunk) and one at
    8-token blocks and chunks (reset() frees slots/blocks, never the
    compiled programs)."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    eng16 = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                     decode_block=4)
    paged = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                     decode_block=4, paged=True,
                                     block_size=8, prefill_chunk=8)
    return model, cfg, eng16, paged


@pytest.fixture(autouse=True)
def _disarmed():
    """Every test starts and ends with the fault registry disarmed —
    a leaked schedule must never bleed into the next test."""
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def _no_compile_cache():
    """Disable jax's persistent compilation cache for tests that build
    a SECOND paged step backend in one process. Under the tier-1
    invocation (-p no:xdist -p no:randomly) everything is green with
    the cache on; with those pytest plugins loaded, this jaxlib build
    corrupts the native heap when the paged scan programs round-trip
    through the on-disk cache next to a fresh identical compile (glibc
    'double free or corruption' at exit, garbage numerics before it).
    The same scenario as a plain script passes cold and warm, and
    restoring into the SAME engine is bit-identical with the cache on
    — so this is a cache/plugin environment bug, not engine state;
    the fixture just keeps the suite green under default plugins."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _ref(model, prompt, max_new, **kw):
    return model.generate(paddle.to_tensor(prompt[None, :]),
                          max_new_tokens=max_new, **kw).numpy()[0]


def _prompts(cfg, seed, lens):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
            for L in lens]


class TestFaultRegistry:
    def test_spec_parsing_and_firing_modes(self):
        faults.configure("a:at=2;b:every=3,times=1;c:p=0.0")
        fired_a = [faults.should_fire("a") for _ in range(4)]
        assert fired_a == [False, True, False, False]
        fired_b = [faults.should_fire("b") for _ in range(9)]
        assert fired_b == [False, False, True] + [False] * 6  # times=1
        assert not any(faults.should_fire("c") for _ in range(20))
        assert not faults.should_fire("unknown_site")
        st = faults.site_stats()
        assert st["a"] == {"calls": 4, "fires": 1}
        assert st["b"]["fires"] == 1

    def test_probability_is_seed_deterministic(self):
        def draw(seed):
            faults.configure("s:p=0.3", seed=seed)
            return [faults.should_fire("s") for _ in range(50)]
        a, b, c = draw(7), draw(7), draw(8)
        assert a == b
        assert a != c
        assert any(a) and not all(a)

    def test_disarmed_is_the_default_and_clear_works(self):
        assert not faults.active()
        faults.configure("x:at=1")
        assert faults.active()
        faults.clear()
        assert not faults.active() and not faults.should_fire("x")

    def test_bad_specs_raise(self):
        with pytest.raises(ValueError, match="site:key=val"):
            faults.configure("nokeys")
        with pytest.raises(ValueError, match="unknown fault spec key"):
            faults.configure("s:bogus=1")

    def test_injected_context_manager_disarms(self):
        with faults.injected("s:at=1"):
            assert faults.should_fire("s")
        assert not faults.active()

    def test_fault_point_raises_injected_fault(self):
        faults.configure("s:at=1")
        with pytest.raises(faults.InjectedFault, match="site 's'"):
            faults.fault_point("s")


class TestFlagsSatellite:
    def test_env_bool_env_float(self, monkeypatch):
        from paddle_tpu.utils.flags import env_bool, env_float, env_flag
        assert env_flag is env_bool        # canonical alias
        monkeypatch.setenv("PT_X_BOOL", "off")
        assert env_bool("PT_X_BOOL", True) is False
        monkeypatch.setenv("PT_X_F", "2.5")
        assert env_float("PT_X_F", 1.0) == 2.5
        monkeypatch.setenv("PT_X_F", "  ")   # lenient empty
        assert env_float("PT_X_F", 1.0) == 1.0
        monkeypatch.setenv("PT_X_F", "nope")
        with pytest.raises(ValueError):
            env_float("PT_X_F", 1.0)

    def test_resilience_config_from_env(self, monkeypatch):
        monkeypatch.setenv("PT_SERVING_DEADLINE_TICKS", "9")
        monkeypatch.setenv("PT_SERVING_RETRIES", "5")
        monkeypatch.setenv("PT_SERVING_NAN_SENTINEL", "0")
        cfg = ResilienceConfig.from_env()
        assert cfg.deadline_ticks == 9
        assert cfg.retry_attempts == 5
        assert cfg.nan_sentinel is False
        assert cfg.deadline_s is None        # unset stays None


class TestInertWhenDisabled:
    def test_disarmed_streams_bit_identical_compile_counts_pinned(
            self, setup):
        """The acceptance pin: with the fault layer imported but
        disarmed, both engines' greedy streams stay bit-identical to
        generate() and the decode/chunk compile counts stay 1 — the
        resilience wiring costs nothing on the clean path."""
        model, cfg, eng16, paged = setup
        prompts = _prompts(cfg, 0, (5, 9, 12, 5, 9))
        news = [6, 4, 7, 5, 6]
        for engine in (eng16, paged):
            engine.reset()
            srv = Server(engine)
            rids = [srv.submit(p, max_new_tokens=mn)
                    for p, mn in zip(prompts, news)]
            res = srv.run_until_idle()
            for rid, p, mn in zip(rids, prompts, news):
                np.testing.assert_array_equal(
                    res[rid], _ref(model, p, mn, temperature=0.0))
            assert engine.decode_compile_count() == 1
            st = srv.stats()
            assert st["step_failures"] == 0 and st["retries"] == 0
            assert st["requests_failed"] == 0 and not st["breaker_open"]
        assert paged.prefill_compile_count() == 1


class TestRetryAndBreaker:
    def test_step_and_harvest_faults_retried_bit_identical(self, setup):
        """Transient step + harvest faults are absorbed by the retry
        path with ZERO effect on outputs: the harvest fault parks the
        dispatched block so a retry never re-steps (no token decoded
        twice or dropped)."""
        model, cfg, eng16, _ = setup
        eng16.reset()
        prompts = _prompts(cfg, 1, (5, 9, 12))
        srv = Server(eng16)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        with faults.injected(
                "serving.step_block:every=3;serving.harvest:at=2"):
            res = srv.run_until_idle()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 6, temperature=0.0))
        st = srv.stats()
        assert st["retries"] > 0 and st["step_failures"] > 0
        assert st["requests_failed"] == 0
        assert eng16.decode_compile_count() == 1

    def test_tick_fault_skips_without_losing_requests(self, setup):
        model, cfg, eng16, _ = setup
        eng16.reset()
        p = _prompts(cfg, 2, (5,))[0]
        srv = Server(eng16)
        rid = srv.submit(p, max_new_tokens=5)
        with faults.injected("server.tick:at=1"):
            res = srv.run_until_idle()
        np.testing.assert_array_equal(
            res[rid], _ref(model, p, 5, temperature=0.0))
        assert srv.stats()["tick_faults"] == 1

    def test_breaker_opens_and_drains_everything(self, setup, tmp_path):
        model, cfg, eng16, _ = setup
        eng16.reset()
        prompts = _prompts(cfg, 3, (5, 9, 6))
        srv = Server(eng16, resilience=ResilienceConfig(
            retry_attempts=1, retry_backoff_s=0.001,
            breaker_threshold=3))
        rids = [srv.submit(p, max_new_tokens=8) for p in prompts]
        with faults.injected("serving.step_block:p=1.0"):
            res = srv.run_until_idle()
        for rid in rids:
            assert isinstance(res[rid], RequestFailure)
            assert res[rid].reason == "circuit_open"
        st = srv.stats()
        assert st["breaker_open"] and st["requests_failed"] == 3
        assert all(s is None for s in eng16._slots)   # no slot leak
        # the OPEN circuit survives snapshot/restore — a restored
        # server must not silently re-close the breaker and resume
        # dispatching to a device the policy quarantined
        path = str(tmp_path / "breaker.npz")
        srv.snapshot(path)
        eng16.reset()
        srv2 = Server.restore(path, eng16)
        st2 = srv2.stats()
        assert st2["breaker_open"]
        assert st2["requests_failed"] == 3
        assert st2["step_failures"] == st["step_failures"]

    def test_prefill_retry_respects_tick_budget(self, setup):
        """A mid-loop prefill fault must NOT re-arm the tick's full
        prefill token budget on retry: chunks dispatched before the
        fault count against it (the decode-interference bound)."""
        model, cfg, _, paged = setup
        paged.reset()
        rs = np.random.RandomState(14)
        long_p = rs.randint(0, cfg.vocab_size, (24,)).astype(np.int32)
        # budget 16 = two 8-token chunks per tick; the fault fires at
        # the SECOND chunk dispatch, after 8 tokens were already spent
        srv = Server(paged, Scheduler(prefill_token_budget=16),
                     resilience=ResilienceConfig(retry_attempts=3,
                                                 retry_backoff_s=0.001))
        rid = srv.submit(long_p, max_new_tokens=4)
        with faults.injected("serving.prefill_tick:at=2"):
            srv.run_until_idle(max_ticks=1)
        # un-fixed, the retry re-armed a fresh 16-token budget and the
        # whole 24-token prompt prefilled in one tick
        assert paged.prefilled_tokens <= 16
        res = srv.run_until_idle()
        np.testing.assert_array_equal(
            res[rid], _ref(model, long_p, 4, temperature=0.0))


class TestDeadlinesAndShedding:
    def test_inflight_deadline_cancels_and_frees(self, setup):
        model, cfg, eng16, _ = setup
        eng16.reset()
        prompts = _prompts(cfg, 4, (5, 9))
        srv = Server(eng16,
                     resilience=ResilienceConfig(deadline_ticks=2))
        r0 = srv.submit(prompts[0], max_new_tokens=40)   # will expire
        r1 = srv.submit(prompts[1], max_new_tokens=4)    # finishes first
        res = srv.run_until_idle()
        assert isinstance(res[r0], RequestFailure)
        assert res[r0].reason == "timeout"
        assert res[r0].tokens_emitted > 0      # partial work accounted
        np.testing.assert_array_equal(
            res[r1], _ref(model, prompts[1], 4, temperature=0.0))
        assert all(s is None for s in eng16._slots)
        assert srv.stats()["timeouts"] == 1

    def test_paged_deadline_releases_blocks_exactly(self, setup):
        model, cfg, _, paged = setup
        paged.reset()
        p = _prompts(cfg, 5, (12,))[0]
        free0 = paged.manager.available()
        srv = Server(paged,
                     resilience=ResilienceConfig(deadline_ticks=1))
        rid = srv.submit(p, max_new_tokens=40)
        res = srv.run_until_idle()
        assert isinstance(res[rid], RequestFailure)
        assert res[rid].reason == "timeout"
        assert paged.manager.available() == free0
        assert not paged.manager._ref
        paged.manager.assert_consistent()

    def test_queue_wait_timeout_and_per_request_deadline(self, setup):
        model, cfg, eng16, _ = setup
        eng16.reset()
        prompts = _prompts(cfg, 6, (5, 5, 5))
        srv = Server(eng16, resilience=ResilienceConfig(
            max_queue_wait_ticks=1))
        # two long-running requests occupy both slots; the third waits
        # in queue past the cap and times out without ever admitting
        r0 = srv.submit(prompts[0], max_new_tokens=20)
        r1 = srv.submit(prompts[1], max_new_tokens=20)
        r2 = srv.submit(prompts[2], max_new_tokens=4)
        res = srv.run_until_idle()
        assert isinstance(res[r2], RequestFailure)
        assert res[r2].reason == "timeout"
        for rid, mn in ((r0, 20), (r1, 20)):
            assert not isinstance(res[rid], RequestFailure)
        # per-request deadline overrides the (absent) config default
        eng16.reset()
        srv2 = Server(eng16)
        ra = srv2.submit(prompts[0], max_new_tokens=40, deadline_ticks=2)
        res2 = srv2.run_until_idle()
        assert isinstance(res2[ra], RequestFailure)

    def test_load_shedding_at_queue_depth(self, setup):
        model, cfg, eng16, _ = setup
        eng16.reset()
        prompts = _prompts(cfg, 7, (5, 5, 5, 5))
        srv = Server(eng16, resilience=ResilienceConfig(
            max_queue_depth=2))
        rids = [srv.submit(p, max_new_tokens=4) for p in prompts[:2]]
        shed = [srv.submit(p, max_new_tokens=4) for p in prompts[2:]]
        for rid in shed:         # rejected synchronously, at the door
            assert isinstance(srv.results[rid], RequestFailure)
            assert srv.results[rid].reason == "shed"
        res = srv.run_until_idle()
        for rid, p in zip(rids, prompts[:2]):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 4, temperature=0.0))
        assert srv.stats()["shed_requests"] == 2


class TestNaNSentinel:
    @pytest.mark.parametrize("which", ["paged"])
    def test_poison_quarantines_only_that_slot(self, setup, which):
        """The blast-radius pin: a poisoned slot fails as 'poisoned';
        the OTHER slot's greedy stream stays bit-identical (the poison
        lands in a block only the victim owns)."""
        model, cfg, _, engine = setup
        engine.reset()
        prompts = _prompts(cfg, 8, (5, 9))
        news = [6, 6]
        srv = Server(engine)
        rids = [srv.submit(p, max_new_tokens=mn)
                for p, mn in zip(prompts, news)]
        with faults.injected("serving.poison:at=1"):
            res = srv.run_until_idle()
        failed = [r for r in rids if isinstance(res[r], RequestFailure)]
        assert len(failed) == 1 and res[failed[0]].reason == "poisoned"
        ok = [r for r in rids if r not in failed][0]
        i = rids.index(ok)
        np.testing.assert_array_equal(
            res[ok], _ref(model, prompts[i], news[i], temperature=0.0))
        assert all(s is None for s in engine._slots)
        engine.manager.assert_consistent()

    def test_sentinel_off_lets_the_stream_run(self, setup):
        """nan_sentinel=False: no quarantine — the poisoned slot runs
        its budget out and returns (garbage) tokens instead of a
        failure. Pins that the gate is the config, not the flags."""
        model, cfg, eng16, _ = setup
        eng16.reset()
        p = _prompts(cfg, 9, (5,))[0]
        srv = Server(eng16, resilience=ResilienceConfig(
            nan_sentinel=False))
        rid = srv.submit(p, max_new_tokens=5)
        with faults.injected("serving.poison:at=1"):
            res = srv.run_until_idle()
        assert not isinstance(res[rid], RequestFailure)
        assert res[rid].shape == (len(p) + 5,)


class TestSnapshotRestore:
    def _fresh_engine(self, cfg, paged):
        """A new model and engine (a process-restart simulation): the
        8-token geometry when ``paged``, else the default one."""
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        if paged:
            return ContinuousBatchingEngine(
                model, num_slots=2, max_len=64, decode_block=4,
                paged=True, block_size=8, prefill_chunk=8)
        return ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4)

    def test_kill_restore_dense_bit_identical(self, setup, tmp_path,
                                              _no_compile_cache):
        model, cfg, eng16, _ = setup
        prompts = _prompts(cfg, 10, (5, 9, 12, 5))
        news = [8, 4, 7, 5]

        def submit_all(srv):
            return [srv.submit(p, max_new_tokens=mn, arrival_step=i)
                    for i, (p, mn) in enumerate(zip(prompts, news))]

        eng16.reset()                       # uninterrupted reference
        srv_ref = Server(eng16)
        rids = submit_all(srv_ref)
        ref = srv_ref.run_until_idle()

        eng16.reset()                       # killed mid-stream
        srv_kill = Server(eng16)
        assert submit_all(srv_kill) == rids
        srv_kill.run_until_idle(max_ticks=3)
        assert eng16.has_live()             # genuinely mid-decode
        path = str(tmp_path / "eng16.npz")
        srv_kill.snapshot(path)

        engine2 = self._fresh_engine(cfg, paged=False)
        srv_new = Server.restore(path, engine2)
        res = srv_new.run_until_idle()
        for rid in rids:
            np.testing.assert_array_equal(res[rid], ref[rid])
        assert engine2.decode_compile_count() == 1

    def test_kill_restore_paged_bit_identical(self, setup, tmp_path,
                                              _no_compile_cache):
        """Kill point chosen while a long prompt is MID-CHUNKED-PREFILL
        and another request is mid-decode — the hardest state: block
        tables, prefix index, refcounts, and the pending prefill job
        all have to survive the round trip."""
        model, cfg, _, paged = setup
        rs = np.random.RandomState(11)
        short_p = rs.randint(0, cfg.vocab_size, (5,)).astype(np.int32)
        long_p = rs.randint(0, cfg.vocab_size, (21,)).astype(np.int32)

        def run(max_ticks=None, srv=None):
            if srv is None:
                srv = Server(paged, Scheduler(prefill_token_budget=8))
                srv.submit(short_p, max_new_tokens=8)
                srv.submit(long_p, max_new_tokens=6, arrival_step=1)
            return srv, srv.run_until_idle(max_ticks=max_ticks)

        paged.reset()
        _, ref = run()
        paged.reset()
        srv_kill, _ = run(max_ticks=2)
        assert paged._jobs                  # mid-prefill at the kill
        path = str(tmp_path / "paged.npz")
        srv_kill.snapshot(path)

        engine2 = self._fresh_engine(cfg, paged=True)
        srv_new = Server.restore(path, engine2,
                                 Scheduler(prefill_token_budget=8))
        res = srv_new.run_until_idle()
        for rid in ref:
            np.testing.assert_array_equal(res[rid], ref[rid])
        engine2.manager.assert_consistent()
        assert engine2.decode_compile_count() == 1
        assert engine2.prefill_compile_count() == 1

    def test_restore_rejects_mismatched_engine(self, setup, tmp_path):
        model, cfg, eng16, paged = setup
        paged.reset()
        path = str(tmp_path / "p.npz")
        paged.snapshot(path)
        eng16.reset()
        with pytest.raises(ValueError, match="mismatch|pool_specs"):
            eng16.restore(path)

    def test_snapshot_is_atomic_no_tmp_litter(self, setup, tmp_path):
        from paddle_tpu.distributed.checkpoint import atomic_savez
        model, cfg, eng16, _ = setup
        eng16.reset()
        path = str(tmp_path / "s.npz")
        eng16.snapshot(path)
        eng16.snapshot(path)                # overwrite goes via rename
        assert [f for f in os.listdir(tmp_path)] == ["s.npz"]

        def boom(f):
            raise IOError("disk full")
        from paddle_tpu.distributed.checkpoint import atomic_write
        with pytest.raises(IOError):
            atomic_write(str(tmp_path / "t.bin"), boom)
        assert sorted(os.listdir(tmp_path)) == ["s.npz"]  # no torn tmp


class TestLivelockRegression:
    def test_oversized_paged_request_rejected_at_submit(
            self, setup, _no_compile_cache):
        """The PR-5 livelock fix: a request whose prompt+decode block
        need exceeds the ENTIRE pool must be rejected at submit() with
        a clear error — under the old stale-attribute validation it
        passed the door and re-queued every tick forever. The manager
        (what allocate() actually draws from) is the source of truth,
        including when a caller swaps in a smaller one."""
        model, cfg, _, paged = setup
        # (a) tiny pool straight from the constructor
        small = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            block_size=8, prefill_chunk=8, num_blocks=3)
        srv = Server(small)
        with pytest.raises(ValueError, match="KV blocks"):
            srv.submit(np.ones((20,), np.int32), max_new_tokens=10)
        # a fitting request on the same tiny pool still completes
        p = _prompts(cfg, 12, (6,))[0]
        rid = srv.submit(p, max_new_tokens=3)
        res = srv.run_until_idle(max_ticks=50)
        np.testing.assert_array_equal(
            res[rid], _ref(model, p, 3, temperature=0.0))
        small.manager.assert_consistent()
        # (b) manager swapped without touching num_kv_blocks — the
        # exact desync that produced the livelock
        stale = ContinuousBatchingEngine(backend=paged.backend)
        stale.manager = BlockManager(3, stale.kv_block_size)
        stale.reset()
        with pytest.raises(ValueError, match="KV blocks"):
            Server(stale).submit(np.ones((20,), np.int32),
                                 max_new_tokens=10)


class TestChaos:
    """Randomized (seeded) fault schedules against the accounting
    invariants. Injected transient faults (step/harvest/prefill/
    allocate/tick) are SEMANTICALLY INVISIBLE — retries and re-queues
    absorb them — so completed greedy requests must STILL be
    bit-identical to generate(); poison and deadlines produce explicit
    failures. Always: every request ends in results, no slot leaks,
    arena accounting exact."""

    SPECS = {
        0: "serving.step_block:p=0.05;serving.allocate:p=0.3",
        1: "serving.harvest:p=0.05;serving.poison:at=3,times=1",
        2: "serving.prefill_tick:p=0.1;server.tick:p=0.1",
        3: ("serving.step_block:p=0.04;serving.harvest:p=0.04;"
            "serving.allocate:p=0.2;serving.poison:at=5,times=1"),
        4: ("server.tick:p=0.05;serving.step_block:p=0.05;"
            "serving.prefill_tick:p=0.05;serving.allocate:p=0.15"),
    }

    @pytest.mark.parametrize("seed", sorted(SPECS))
    def test_randomized_fault_schedules_hold_invariants(self, setup,
                                                        seed):
        model, cfg, _, paged = setup
        paged.reset()
        rs = np.random.RandomState(100 + seed)
        lens = rs.randint(4, 20, size=6)
        news = rs.randint(3, 8, size=6)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in lens]
        srv = Server(paged, Scheduler(prefill_token_budget=8),
                     resilience=ResilienceConfig(
                         retry_attempts=3, retry_backoff_s=0.001,
                         breaker_threshold=12, deadline_ticks=60,
                         seed=seed))
        rids = [srv.submit(p, max_new_tokens=int(mn), arrival_step=i)
                for i, (p, mn) in enumerate(zip(prompts, news))]
        with faults.injected(self.SPECS[seed], seed=seed):
            res = srv.run_until_idle(max_ticks=300)
        # termination: the loop drained (no livelock under faults)
        assert srv.scheduler.pending() == 0 and not paged.has_live()
        # completeness: every request ended, one way or the other
        for rid, p, mn in zip(rids, prompts, news):
            assert rid in res, f"request {rid} vanished"
            v = res[rid]
            if isinstance(v, RequestFailure):
                assert v.reason in ("timeout", "poisoned",
                                    "circuit_open", "shed")
            else:
                np.testing.assert_array_equal(
                    v, _ref(model, p, int(mn), temperature=0.0))
        # zero leaks: slots empty, no pending jobs, arena exact
        assert all(s is None for s in paged._slots)
        assert not paged._jobs and not paged._prefill_slots
        assert not paged.manager._ref
        paged.manager.assert_consistent()
        assert paged.decode_compile_count() == 1
        assert paged.prefill_compile_count() == 1

    def test_dense_chaos_schedule(self, setup):
        model, cfg, eng16, _ = setup
        eng16.reset()
        prompts = _prompts(cfg, 13, (5, 9, 12, 6))
        srv = Server(eng16, resilience=ResilienceConfig(
            retry_attempts=3, retry_backoff_s=0.001,
            breaker_threshold=12, deadline_ticks=60))
        rids = [srv.submit(p, max_new_tokens=5, arrival_step=i)
                for i, p in enumerate(prompts)]
        spec = ("serving.step_block:p=0.08;serving.harvest:p=0.05;"
                "server.tick:p=0.05;serving.poison:at=4,times=1")
        with faults.injected(spec, seed=42):
            res = srv.run_until_idle(max_ticks=300)
        assert srv.scheduler.pending() == 0 and not eng16.has_live()
        for rid, p in zip(rids, prompts):
            v = res[rid]
            if not isinstance(v, RequestFailure):
                np.testing.assert_array_equal(
                    v, _ref(model, p, 5, temperature=0.0))
        assert all(s is None for s in eng16._slots)
        assert eng16.decode_compile_count() == 1
