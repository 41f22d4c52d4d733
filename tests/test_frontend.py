"""Multi-tenant front door (serving/frontend.py): token-by-token
streaming out of the harvest path (bounded per-request queues,
iterator + callback APIs, greedy streams bit-identical to generate()),
per-tenant weighted-fair admission with quotas (FairScheduler deficit
ledger layered on the bisect-FIFO scheduler), and priority preemption —
a low-priority slot evicted mid-decode (paged blocks released at exact
refcounts, prefix index retained) and resumed later via chunked
re-prefill, bit-identical for greedy AND seeded-sampled traffic with
decode/prefill compile counts pinned at 1.
Plus a seeded chaos schedule (~1% step faults) pinning the fairness +
preemption invariants: exactly one terminal per request, zero
slot/block leaks, arena consistent, completed greedy rows still
bit-identical."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import ObservabilityConfig
from paddle_tpu.serving import (ContinuousBatchingEngine, FairScheduler,
                                Frontend, Request, RequestFailure,
                                ResilienceConfig, Scheduler, Server,
                                TenantConfig)
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
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def _no_compile_cache():
    """Same environment workaround as tests/test_resilience.py: this
    jaxlib build corrupts the native heap when a SECOND paged step
    backend compiles in one process through the persistent compile
    cache (glibc heap abort mid-GC) — disable the cache for the
    fresh-engine restore test."""
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


class TestFairScheduler:
    def _req(self, rid, tenant, mn=8, arrival=0, priority=0):
        return Request(request_id=rid, prompt=np.ones((4,), np.int32),
                       max_new_tokens=mn, arrival_step=arrival,
                       tenant=tenant, priority=priority)

    def test_weighted_shares_over_backlog(self):
        """Admissions one slot at a time over a 3-tenant backlog track
        the configured weights (the deficit-ledger invariant — cost
        debited per admission, smallest weighted usage wins)."""
        s = FairScheduler(tenants={"a": TenantConfig(weight=1.0),
                                   "b": TenantConfig(weight=2.0),
                                   "c": TenantConfig(weight=3.0)})
        rid = 0
        for i in range(40):
            for t in ("a", "b", "c"):
                s.submit(self._req(rid, t))
                rid += 1
        counts = {"a": 0, "b": 0, "c": 0}
        for _ in range(60):
            (r,) = s.pop_ready(0, free_slots=1, engine_idle=True)
            counts[r.tenant] += 1
        assert counts["a"] == 10 and counts["b"] == 20 \
            and counts["c"] == 30

    def test_priority_tier_beats_deficit(self):
        """A visible higher-priority request admits first even when its
        tenant is far over its fair share."""
        s = FairScheduler(tenants={"a": TenantConfig(weight=1.0),
                                   "b": TenantConfig(weight=100.0)})
        for i in range(4):
            s.submit(self._req(i, "a"))
        for _ in range(2):           # 'a' racks up weighted usage
            s.pop_ready(0, 1, True)
        s.submit(self._req(10, "b"))              # huge weight, prio 0
        s.submit(self._req(11, "a", priority=3))  # tiny weight, prio 3
        (r,) = s.pop_ready(0, 1, True)
        assert r.request_id == 11

    def test_fifo_within_tenant_and_gate(self):
        s = FairScheduler(max_wait_steps=5, min_admit=3)
        s.submit(self._req(0, "a", arrival=0))
        s.submit(self._req(1, "a", arrival=1))
        # base batching gate preserved: engine busy + short queue holds
        assert s.pop_ready(1, 4, engine_idle=False) == []
        s.submit(self._req(2, "a", arrival=2))
        out = s.pop_ready(3, 4, engine_idle=False)
        assert [r.request_id for r in out] == [0, 1, 2]   # FIFO

    def test_requeue_credits_ledger_no_double_charge(self):
        """A deferred request (popped, engine refused, requeued) and a
        preempted one (requeued carrying resume) must not be charged
        twice — the requeue credits back the undelivered cost."""
        from paddle_tpu.serving import ResumeState
        s = FairScheduler()
        for i, t in enumerate(("a", "a", "b", "b")):
            s.submit(self._req(i, t, mn=8))
        (r,) = s.pop_ready(0, 1, True)
        assert r.tenant == "a"
        s.requeue(r)                     # defer: nothing delivered
        (r2,) = s.pop_ready(0, 1, True)
        # uncredited, tenant a would sit at usage 8 and b would win
        assert r2 is r
        # preemption: 20-token request delivered 12 before eviction —
        # total charge across both admissions must equal 20, not 28
        s2 = FairScheduler()
        s2.submit(self._req(0, "a", mn=20))
        s2.pop_ready(0, 1, True)
        assert s2._usage["a"] == 20.0
        pre = self._req(0, "a", mn=20)
        pre.resume = ResumeState(tokens=list(range(12)),
                                 key=np.zeros(2, np.uint32))
        s2.requeue(pre)                  # credit the 8-token tail
        assert s2._usage["a"] == 12.0
        s2.pop_ready(0, 1, True)         # resume re-debits the tail
        assert s2._usage["a"] == 20.0

    def test_idle_tenant_banks_no_credit(self):
        """A tenant that idles while others keep submitting re-enters
        the ledger at the CONTINUING tenants' floor — it must not spend
        banked credit monopolizing admissions on return."""
        s = FairScheduler()
        rid = 0

        def sub(t, n):
            nonlocal rid
            for _ in range(n):
                s.submit(self._req(rid, t, mn=10))
                rid += 1

        sub("a", 4)
        sub("b", 4)
        for _ in range(8):               # both drain: usage 40 each
            s.pop_ready(0, 1, True)
        sub("b", 20)                     # a idles, b keeps going
        for _ in range(10):              # b's usage climbs to 140
            s.pop_ready(0, 1, True)
        sub("a", 6)                      # a returns
        order = [s.pop_ready(0, 1, True)[0].tenant for _ in range(6)]
        # unfixed, a's stale usage-40 entry wins all six in a row
        assert order == ["b", "a", "b", "a", "b", "a"]

    def test_pending_counts_track_queue(self):
        s = FairScheduler()
        for i, t in enumerate(("a", "a", "b")):
            s.submit(self._req(i, t))
        assert (s.tenant_pending("a"), s.tenant_pending("b")) == (2, 1)
        (r,) = s.pop_ready(0, 1, True)
        assert s.tenant_pending(r.tenant) == 1
        s.requeue(r)
        assert s.tenant_pending(r.tenant) == 2
        s.drop_where(lambda q: q.tenant == "b")
        assert s.tenant_pending("b") == 0
        assert s.pending() == 2

    def test_quota_and_weight_validation(self):
        s = FairScheduler(tenants={"a": TenantConfig(max_queued=2)})
        s.submit(self._req(0, "a"))
        assert not s.quota_exceeded("a")
        s.submit(self._req(1, "a"))
        assert s.quota_exceeded("a")
        assert not s.quota_exceeded("b")      # unconfigured: unbounded
        with pytest.raises(ValueError, match="must be > 0"):
            FairScheduler(tenants={"x": TenantConfig(weight=0.0)})

    def test_server_sheds_at_tenant_quota(self, setup):
        model, cfg, eng16, _ = setup
        eng16.reset()
        fe = Frontend(eng16,
                      tenants={"a": TenantConfig(max_queued=1)})
        p = _prompts(cfg, 0, (5,))[0]
        ok = fe.submit(p, tenant="a", max_new_tokens=3)
        shed = fe.submit(p, tenant="a", max_new_tokens=3)
        free = fe.submit(p, tenant="b", max_new_tokens=3)
        assert isinstance(fe.results[shed], RequestFailure)
        assert fe.results[shed].reason == "shed"
        res = fe.run_until_idle()
        assert not isinstance(res[ok], RequestFailure)
        assert not isinstance(res[free], RequestFailure)
        st = fe.stats()
        assert st["tenants"]["a"]["shed"] == 1
        assert st["tenants"]["b"]["shed"] == 0


class TestStreaming:
    @pytest.mark.parametrize("which", ["paged"])
    def test_iterator_greedy_bit_identical(self, setup, which):
        """The headline pin: tokens consumed token-by-token off the
        iterator equal the generate() tail exactly, while run_until_idle
        results keep the full padded-array contract — one compiled
        decode program throughout."""
        model, cfg, _, engine = setup
        engine.reset()
        fe = Frontend(engine)
        prompts = _prompts(cfg, 1, (5, 9, 12))
        news = [6, 4, 7]
        streams = [fe.submit(p, max_new_tokens=mn, stream=True)
                   for p, mn in zip(prompts, news)]
        for s, p, mn in zip(streams, prompts, news):
            got = s.read_all()
            r = _ref(model, p, mn, temperature=0.0)
            assert got == [int(t) for t in r[len(p):len(p) + len(got)]]
            assert s.done and s.failure is None and s.dropped == 0
        res = fe.results
        for s, p, mn in zip(streams, prompts, news):
            np.testing.assert_array_equal(
                res[s.request_id], _ref(model, p, mn, temperature=0.0))
        assert engine.decode_compile_count() == 1

    def test_callback_api_under_run_until_idle(self, setup):
        model, cfg, eng16, _ = setup
        eng16.reset()
        fe = Frontend(eng16)
        p = _prompts(cfg, 2, (5,))[0]
        got = []
        s = fe.submit(p, max_new_tokens=6, on_token=got.append)
        fe.run_until_idle()
        r = _ref(model, p, 6, temperature=0.0)
        assert got == [int(t) for t in r[len(p):len(p) + len(got)]]
        assert s.done and s.tokens_seen == len(got)

    def test_bounded_queue_drops_oldest_counts_all(self, setup):
        """A consumer that never drains: the queue stays bounded at
        capacity, the oldest tokens are evicted and counted, and
        tokens_seen still tallies the full stream."""
        model, cfg, eng16, _ = setup
        eng16.reset()
        fe = Frontend(eng16, stream_capacity=4)
        p = _prompts(cfg, 3, (5,))[0]
        s = fe.submit(p, max_new_tokens=12, stream=True)
        fe.run_until_idle()
        assert s.tokens_seen == 12
        assert s.dropped == 8
        r = _ref(model, p, 12, temperature=0.0)
        assert s.drain() == [int(t) for t in r[len(p) + 8:len(p) + 12]]

    def test_sampled_stream_matches_generate_seed(self, setup):
        model, cfg, eng16, _ = setup
        eng16.reset()
        fe = Frontend(eng16)
        p = _prompts(cfg, 4, (9,))[0]
        s = fe.submit(p, max_new_tokens=6, temperature=1.0, top_k=40,
                      seed=7, stream=True)
        got = s.read_all()
        r = _ref(model, p, 6, do_sample=True, temperature=1.0,
                 top_k=40, seed=7)
        assert got == [int(t) for t in r[len(p):len(p) + len(got)]]

    def test_shed_stream_terminates_immediately(self, setup):
        model, cfg, eng16, _ = setup
        eng16.reset()
        fe = Frontend(eng16,
                      resilience=ResilienceConfig(max_queue_depth=1))
        p = _prompts(cfg, 5, (5,))[0]
        fe.submit(p, max_new_tokens=4)
        s = fe.submit(p, max_new_tokens=4, stream=True)
        assert s.done and s.failure == "shed"
        assert s.read_all() == []
        fe.run_until_idle()


class TestPreemption:
    @pytest.mark.parametrize("which", ["paged"])
    def test_greedy_preempt_resume_bit_identical(self, setup, which):
        """The acceptance pin: low-priority requests evicted mid-decode
        by a high-priority arrival finish BIT-IDENTICAL to their
        uninterrupted generate() twins; the high-priority request got a
        slot while the pool was full; compile counts stay 1."""
        model, cfg, _, engine = setup
        engine.reset()
        prompts = _prompts(cfg, 6, (5, 9, 12))
        fe = Frontend(engine, preemption=True)
        low = [fe.submit(p, max_new_tokens=20, priority=0)
               for p in prompts[:2]]
        fe.pump()
        fe.pump()                       # both slots decoding
        hi = fe.submit(prompts[2], max_new_tokens=4, priority=5)
        res = fe.run_until_idle()
        st = fe.stats()
        assert st["preemptions"] >= 1 and st["resumes"] >= 1
        for rid, p, mn in zip(low + [hi], prompts, (20, 20, 4)):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, mn, temperature=0.0))
        assert engine.decode_compile_count() == 1
        assert all(s is None for s in engine._slots)
        assert engine.prefill_compile_count() == 1
        assert not engine.manager._ref
        engine.manager.assert_consistent()
        # the eviction retained the prompt's prefix-index entries, so
        # the resume's re-prefill was mostly cache hits
        assert engine.prefix_cache_hit_rate() > 0.0

    @pytest.mark.parametrize("which", ["paged"])
    def test_seeded_sampled_preempt_resume_bit_identical(self, setup,
                                                         which):
        """The rng key carried through ResumeState is the NEXT step's
        split input — a preempted seeded-sampled stream resumes on the
        exact key schedule generate(seed) uses."""
        model, cfg, _, engine = setup
        engine.reset()
        prompts = _prompts(cfg, 7, (5, 9, 12))
        fe = Frontend(engine, preemption=True)
        rs = fe.submit(prompts[0], max_new_tokens=20, priority=0,
                       temperature=0.9, top_k=40, seed=11)
        rg = fe.submit(prompts[1], max_new_tokens=20, priority=0)
        for _ in range(3):
            fe.pump()
        hi = fe.submit(prompts[2], max_new_tokens=4, priority=5)
        res = fe.run_until_idle()
        assert fe.stats()["preemptions"] >= 1
        np.testing.assert_array_equal(
            res[rs], _ref(model, prompts[0], 20, do_sample=True,
                          temperature=0.9, top_k=40, seed=11))
        np.testing.assert_array_equal(
            res[rg], _ref(model, prompts[1], 20, temperature=0.0))
        np.testing.assert_array_equal(
            res[hi], _ref(model, prompts[2], 4, temperature=0.0))
        assert engine.decode_compile_count() == 1

    def test_preemption_requires_priority_aware_scheduler(
            self, setup, monkeypatch):
        """The FIFO scheduler would hand every freed slot back to the
        front-inserted victim (eviction churn + priority inversion):
        explicit preemption=True on it is refused loudly; the env knob
        — weaker than explicit config, same contract as
        PT_SERVING_PAGED — resolves to off instead of forcing it."""
        model, cfg, eng16, _ = setup
        eng16.reset()
        with pytest.raises(ValueError, match="priority-aware"):
            Server(eng16, Scheduler(), preemption=True)
        with pytest.raises(ValueError, match="priority-aware"):
            Frontend(eng16, scheduler=Scheduler(), preemption=True)
        monkeypatch.setenv("PT_SERVING_PREEMPTION", "1")
        srv = Server(eng16, Scheduler())        # env-armed: degrades
        assert not srv.preemption
        srv2 = Server(eng16, FairScheduler())   # env-armed: applies
        assert srv2.preemption

    def test_queue_wait_measured_from_requeue_not_arrival(self, setup):
        """A preempted victim's decode time is service, not queue wait:
        the max-queue-wait gate measures from the requeue stamp, so a
        long-served victim is not killed the moment it re-enters the
        queue (deadlines stay end-to-end). Pinned directly against
        _expire with a crafted wait_from."""
        model, cfg, eng16, _ = setup
        eng16.reset()
        p = _prompts(cfg, 20, (5,))[0]
        srv = Server(eng16, FairScheduler(), resilience=ResilienceConfig(
            max_queue_wait_ticks=15))
        rid = srv.submit(p, max_new_tokens=4)
        (req,) = srv.scheduler._queue
        srv._clock = 40
        req.wait_from = 30               # requeued at tick 30: waited 10
        srv._expire()
        assert rid not in srv.results    # survives (10 <= 15)
        req.wait_from = None             # pre-fix semantics: lifetime 40
        srv._expire()
        assert isinstance(srv.results[rid], RequestFailure)
        assert srv.results[rid].reason == "timeout"

    def test_no_preemption_into_a_held_batching_gate(self, setup):
        """Evicting while the admission gate holds would idle the freed
        slot and waste the victim's progress — preemption defers until
        the gate would release."""
        model, cfg, _, paged = setup
        paged.reset()
        prompts = _prompts(cfg, 21, (5, 9, 12))
        fe = Frontend(paged, scheduler=FairScheduler(
            min_admit=3, max_wait_steps=100), preemption=True)
        for p in prompts[:2]:
            fe.submit(p, max_new_tokens=24, priority=0)
        fe.pump()
        fe.pump()
        fe.submit(prompts[2], max_new_tokens=4, priority=5)
        for _ in range(3):
            fe.pump()
        assert fe.stats()["preemptions"] == 0     # gate held: 1 < 3
        # two more visible requests open the gate -> eviction proceeds
        fe.submit(prompts[0], max_new_tokens=4, priority=5)
        fe.submit(prompts[1], max_new_tokens=4, priority=5)
        res = fe.run_until_idle()
        assert fe.stats()["preemptions"] >= 1
        for rid, v in res.items():
            assert not isinstance(v, RequestFailure)

    class _TPLikeEngine:
        """Proxy wearing the TP marker (tp_degree() > 1) over a real
        engine — the Server guard keys on the method, and a REAL
        sharded backend in this process would trip the documented
        jaxlib compile-cache heap landmine (same stub discipline as
        test_serving.py's _FingerprintBackend)."""

        def __init__(self, inner):
            self._inner = inner

        def tp_degree(self):
            return 2

        def __getattr__(self, name):
            return getattr(self.__dict__["_inner"], name)

    def test_preemption_refused_on_tp_engine(self, setup,
                                             monkeypatch):
        """Untested composition: preemption with tensor-parallel
        engines is refused loudly on explicit config and degrades to
        off when only the env knob armed it. (Spec engines compose
        since PR 14 — pinned in test_serving_spec.py's
        TestSpecPreemption.)"""
        model, cfg, eng16, _ = setup
        eng16.reset()
        tp = self._TPLikeEngine(eng16)
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            Server(tp, FairScheduler(), preemption=True)
        monkeypatch.setenv("PT_SERVING_PREEMPTION", "1")
        srv = Server(tp, FairScheduler())     # env-armed: degrades
        assert not srv.preemption

    def test_equal_priority_never_preempts(self, setup):
        model, cfg, _, paged = setup
        paged.reset()
        prompts = _prompts(cfg, 8, (5, 9, 12))
        fe = Frontend(paged, preemption=True)
        for p in prompts[:2]:
            fe.submit(p, max_new_tokens=12, priority=3)
        fe.pump()
        fe.pump()
        fe.submit(prompts[2], max_new_tokens=4, priority=3)
        fe.run_until_idle()
        assert fe.stats()["preemptions"] == 0

    def test_preempt_resume_are_span_events_one_terminal(self, setup):
        """Observability contract: preempt/resume appear as span events
        on the victim's trace — its decode span closes, the preempt and
        resume instants land — and the request still terminates EXACTLY
        once, as completed."""
        model, cfg, _, paged = setup
        paged.reset()
        prompts = _prompts(cfg, 9, (5, 9, 12))
        fe = Frontend(paged, preemption=True,
                      observability=ObservabilityConfig(
                          trace_requests=True))
        low = [fe.submit(p, max_new_tokens=20, priority=0)
               for p in prompts[:2]]
        fe.pump()
        fe.pump()
        hi = fe.submit(prompts[2], max_new_tokens=4, priority=5)
        fe.run_until_idle()
        tracer = fe.server.tracer
        assert fe.stats()["preemptions"] >= 1
        preempted = [rid for rid in low if "preempt" in
                     tracer.traces[rid].span_names()]
        assert preempted, "no victim trace carries the preempt event"
        for rid in preempted:
            names = tracer.traces[rid].span_names()
            assert "resume" in names
            assert tracer.traces[rid].terminals == ["completed"]
        for rid in low + [hi]:
            assert len(tracer.traces[rid].terminals) == 1

    def test_preempted_request_survives_snapshot_restore(
            self, setup, tmp_path, _no_compile_cache):
        """A queued request CARRYING resume state (preempted, not yet
        re-admitted) rides Server.snapshot through request_to_meta and
        finishes bit-identical after restore — the portable-state
        bridge the disaggregated-fleet item builds on."""
        model, cfg, _, paged = setup
        prompts = _prompts(cfg, 10, (5, 9, 12))

        def drive(fe):
            low = [fe.submit(p, max_new_tokens=16, priority=0,
                             arrival_step=0) for p in prompts[:2]]
            hi = fe.submit(prompts[2], max_new_tokens=12, priority=5,
                           arrival_step=2)
            return low + [hi]

        paged.reset()                       # uninterrupted reference
        fe_ref = Frontend(paged, preemption=True)
        rids = drive(fe_ref)
        ref = fe_ref.run_until_idle()

        paged.reset()
        fe_kill = Frontend(paged, preemption=True)
        assert drive(fe_kill) == rids
        seen = 0
        for _ in range(40):                 # run until a preemption,
            fe_kill.pump()                  # then stop mid-stream
            seen = fe_kill.stats()["preemptions"]
            if seen:
                break
        assert seen >= 1
        assert any(r.resume is not None
                   for r in fe_kill.scheduler._queue)
        path = str(tmp_path / "frontdoor.npz")
        fe_kill.server.snapshot(path)

        paddle.seed(0)
        model2 = LlamaForCausalLM(cfg)      # fresh-process simulation
        engine2 = ContinuousBatchingEngine(
            model2, num_slots=2, max_len=64, decode_block=4,
            paged=True, block_size=8, prefill_chunk=8)
        srv = Server.restore(path, engine2, FairScheduler())
        assert srv.preemption                # saved policy survives
        res = srv.run_until_idle()
        for rid in rids:
            np.testing.assert_array_equal(res[rid], ref[rid])
        engine2.manager.assert_consistent()
        assert engine2.decode_compile_count() == 1


class TestStreamRestore:
    def test_kill_restore_reattach_sees_only_unseen_tokens(
            self, setup, tmp_path, _no_compile_cache):
        """The PR 13 follow-up fixed: each stream's DELIVERED offset
        rides Server.snapshot (the frontend's snapshot-extras
        provider), so a consumer re-attached after a kill/restore sees
        exactly the tokens it never consumed — no token twice, none
        lost, buffered-but-unconsumed tokens re-deliver."""
        model, cfg, _, paged = setup
        paged.reset()
        p = _prompts(cfg, 30, (6,))[0]
        ref = _ref(model, p, 12, temperature=0.0)
        tail = [int(t) for t in ref[6:]]
        fe = Frontend(paged)
        s = fe.submit(p, max_new_tokens=12, stream=True)
        consumed = [next(s) for _ in range(6)]   # then "crash"
        # tokens arrive in bursts (the prefill token, then 4-token
        # decode blocks): 6 next() calls sit mid-burst with tokens
        # still buffered, so the snapshot's buffered-subtraction
        # branch is genuinely exercised
        assert len(s._buf) > 0
        path = str(tmp_path / "stream.npz")
        fe.server.snapshot(path)

        paddle.seed(0)
        model2 = LlamaForCausalLM(cfg)           # fresh process sim
        engine2 = ContinuousBatchingEngine(
            model2, num_slots=2, max_len=64, decode_block=4,
            paged=True, block_size=8, prefill_chunk=8)
        fe2 = Frontend.restore(path, engine2)
        s2 = fe2.attach_stream(s.request_id)
        rest = s2.read_all()
        assert consumed + rest == tail           # exactly-once stream
        assert s2.done and s2.failure is None
        np.testing.assert_array_equal(
            fe2.results[s.request_id], ref)
        engine2.manager.assert_consistent()

    def test_live_reattach_transfers_buffered_tokens(self, setup):
        """Re-attaching over a LIVE stream must not lose its
        buffered-but-unconsumed tokens — they move to the new stream,
        so the old + new consumers together see the stream exactly
        once."""
        model, cfg, _, paged = setup
        paged.reset()
        p = _prompts(cfg, 32, (6,))[0]
        ref = _ref(model, p, 8, temperature=0.0)
        fe = Frontend(paged)
        s = fe.submit(p, max_new_tokens=8, stream=True)
        consumed = [next(s) for _ in range(2)]
        assert len(s._buf) > 0               # mid-block leftovers
        s2 = fe.attach_stream(s.request_id)
        rest = s2.read_all()
        assert consumed + rest == [int(t) for t in ref[6:]]

    def test_delivered_offset_recorded_in_snapshot_meta(
            self, setup, tmp_path, _no_compile_cache):
        """The wire-level half of the contract: the frontend's
        snapshot-extras provider records the CONSUMED offset (3 here —
        buffered-but-unconsumed tokens subtracted) in the snapshot's
        server meta, which is exactly what Frontend.restore rehydrates
        from (the full kill/restore/re-attach behavior is pinned
        above)."""
        model, cfg, _, paged = setup
        paged.reset()
        p = _prompts(cfg, 31, (6,))[0]
        fe = Frontend(paged)
        s = fe.submit(p, max_new_tokens=8, stream=True)
        [next(s) for _ in range(3)]
        path = str(tmp_path / "stream2.npz")
        fe.server.snapshot(path)
        import json
        import numpy as _np
        with _np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z["__meta__"]))
        ex = meta["server"]["extras"]["frontend"]
        assert ex["emitted"][str(s.request_id)] == 3
        fe.run_until_idle()                      # drain the original


class TestFrontdoorChaos:
    def test_chaos_with_preemption_and_wfq(self, setup):
        """Seeded chaos (~1% step faults plus transient allocator and
        harvest failures) against the full front door: 3 weighted
        tenants, mixed priorities, preemption armed, tracing on.
        Invariants: every request ends in EXACTLY one terminal,
        preempted slots leak zero blocks, the arena is consistent at
        teardown, and completed greedy rows are STILL bit-identical —
        transient faults and preemptions are both semantically
        invisible."""
        model, cfg, _, paged = setup
        paged.reset()
        rs = np.random.RandomState(123)
        lens = rs.randint(4, 16, size=9)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in lens]
        tenants = {"a": TenantConfig(weight=1.0),
                   "b": TenantConfig(weight=2.0),
                   "c": TenantConfig(weight=3.0)}
        fe = Frontend(
            paged, tenants=tenants, preemption=True,
            observability=ObservabilityConfig(trace_requests=True),
            resilience=ResilienceConfig(
                retry_attempts=3, retry_backoff_s=0.001,
                breaker_threshold=12, deadline_ticks=80))
        names = list(tenants)
        rids = []
        for i, p in enumerate(prompts):
            rids.append(fe.submit(
                p, max_new_tokens=int(4 + (i % 3) * 4),
                tenant=names[i % 3], priority=(2 if i % 4 == 0 else 0),
                arrival_step=i, stream=(i % 2 == 0)))
        rids = [r.request_id if hasattr(r, "request_id") else r
                for r in rids]
        spec = ("serving.step_block:p=0.01;serving.harvest:p=0.01;"
                "serving.allocate:p=0.05;serving.prefill_tick:p=0.02;"
                "server.tick:p=0.02")
        with faults.injected(spec, seed=5):
            res = fe.run_until_idle(max_ticks=400)
        # termination + completeness
        assert fe.scheduler.pending() == 0 and not paged.has_live()
        news = [4 + (i % 3) * 4 for i in range(len(prompts))]
        for rid, p, mn in zip(rids, prompts, news):
            assert rid in res, f"request {rid} vanished"
            v = res[rid]
            if isinstance(v, RequestFailure):
                assert v.reason in ("timeout", "poisoned",
                                    "circuit_open", "shed")
            else:
                np.testing.assert_array_equal(
                    v, _ref(model, p, mn, temperature=0.0))
        # exactly one terminal per request — preemptions never terminate
        for rid in rids:
            assert len(fe.server.tracer.traces[rid].terminals) == 1
        # zero leaks: slots empty, no pending jobs, arena exact
        assert all(s is None for s in paged._slots)
        assert not paged._jobs and not paged._prefill_slots
        assert not paged.manager._ref
        paged.manager.assert_consistent()
        assert paged.decode_compile_count() == 1
        assert paged.prefill_compile_count() == 1
