"""Speculative draft-verify serving (paddle_tpu/serving/spec.py):
greedy spec-mode streams bit-identical to non-speculative decode and
per-request generate() (a whole prompt in one chunk or in many, eos inside an
accepted span), acceptance edge cases (k=0, all-k-accepted via an
oracle drafter), the sampled-traffic k=0 key-schedule fallback, the
compile-count pin (ONE verify program), chaos schedules with spec
enabled, and mid-stream snapshot/restore."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (ContinuousBatchingEngine,
                                PrefillPagedEngine, RequestFailure,
                                ResilienceConfig, Scheduler, Server,
                                SpecConfig, SpecPagedEngine, ngram_propose)
from paddle_tpu.utils import faults


@pytest.fixture(scope="module")
def spec_setup():
    """One model + two speculative engines for the whole file: one at
    the default geometry (16-token blocks, a whole prompt in one chunk)
    and one at 8-token blocks and chunks (reset() frees slots/blocks,
    never the compiled verify/chunk programs). Constructed through the
    ContinuousBatchingEngine factory so the spec= routing is on the
    tested path."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    spec16 = ContinuousBatchingEngine(
        model, num_slots=2, max_len=96, decode_block=4,
        spec=SpecConfig(k=4))
    paged = ContinuousBatchingEngine(
        model, num_slots=2, max_len=96, decode_block=4, paged=True,
        block_size=8, prefill_chunk=8, spec=SpecConfig(k=4))
    assert isinstance(spec16, SpecPagedEngine)
    assert isinstance(paged, SpecPagedEngine)
    return model, cfg, spec16, paged


@pytest.fixture(autouse=True)
def _paged_invariants(spec_setup):
    """Arena accounting must hold after every test in this file."""
    yield
    spec_setup[2].manager.assert_consistent()
    spec_setup[3].manager.assert_consistent()


@pytest.fixture
def _no_compile_cache():
    """Same environment guard as tests/test_resilience.py: tests that
    compile a SECOND identical backend in one process must bypass the
    persistent jax compilation cache — with the default pytest plugins
    loaded, this jaxlib build corrupts the native heap (garbage
    numerics / NaN logits) when an identical program round-trips
    through the on-disk cache next to a fresh compile."""
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


def _oracle(engine, continuation_by_rid):
    """Perfect drafter: proposes the request's TRUE greedy continuation
    — every proposed draft must be accepted (the acceptance-rule pin).
    ``continuation_by_rid``: request_id -> the full generated tail from
    a reference generate() run."""

    def propose():
        S, k = engine.num_slots, engine.spec_k
        draft = np.zeros((S, k), np.int32)
        n = np.zeros((S,), np.int32)
        for slot, run in enumerate(engine._slots):
            if run is None or slot in engine._prefill_slots:
                continue
            gen = continuation_by_rid[run.request.request_id]
            done = len(run.tokens)
            cap = min(k, int(engine._remaining_host[slot]) - 1)
            nxt = gen[done:done + max(cap, 0)]
            draft[slot, :len(nxt)] = nxt
            n[slot] = len(nxt)
        return draft, n

    return propose


class TestSpecBitExactness:
    def test_dense_greedy_stream_bit_exact_one_compile(self,
                                                       spec_setup):
        """5 ragged greedy requests through 2 speculative slots: every
        output bit-identical to standalone generate(), ONE verify
        program compiled across all admissions/retirements."""
        model, cfg, spec16, _ = spec_setup
        spec16.reset()
        prompts = _prompts(cfg, 0, (5, 9, 12, 5, 9))
        news = [12, 8, 10, 9, 12]
        srv = Server(spec16)
        rids = [srv.submit(p, max_new_tokens=mn)
                for p, mn in zip(prompts, news)]
        res = srv.run_until_idle()
        for rid, p, mn in zip(rids, prompts, news):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, mn, temperature=0.0))
        assert spec16.decode_compile_count() == 1
        st = srv.stats()
        assert st["spec_k"] == 4
        assert st["spec_verify_steps"] == spec16.verify_steps > 0

    def test_paged_chunked_stream_bit_exact_one_compile(self,
                                                        spec_setup):
        """Paged + chunked prefill + spec: a long prompt prefilled in
        8-token chunks under a tiny per-tick budget while another
        request decodes speculatively — outputs equal generate(), ONE
        verify program + ONE chunk program."""
        model, cfg, paged, = spec_setup[0], spec_setup[1], spec_setup[3]
        paged.reset()
        rs = np.random.RandomState(7)
        long_p = rs.randint(0, cfg.vocab_size, (21,)).astype(np.int32)
        short_p = rs.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
        srv = Server(paged, Scheduler(prefill_token_budget=8))
        r0 = srv.submit(short_p, max_new_tokens=12)
        r1 = srv.submit(long_p, max_new_tokens=8, arrival_step=1)
        res = srv.run_until_idle()
        np.testing.assert_array_equal(
            res[r0], _ref(model, short_p, 12, temperature=0.0))
        np.testing.assert_array_equal(
            res[r1], _ref(model, long_p, 8, temperature=0.0))
        assert paged.decode_compile_count() == 1
        assert paged.prefill_compile_count() == 1

    def test_spec_stream_equals_plain_engine_stream(self, spec_setup):
        """The spec engine's results also equal the plain engine's on
        the same stream (the bit-identity is engine-level, not just
        per-request)."""
        model, cfg, spec16, _ = spec_setup
        plain = ContinuousBatchingEngine(
            model, num_slots=2, max_len=96, decode_block=4)
        assert not isinstance(plain, SpecPagedEngine)
        prompts = _prompts(cfg, 3, (5, 9, 12))
        outs = {}
        for eng in (spec16, plain):
            eng.reset()
            srv = Server(eng)
            rids = [srv.submit(p, max_new_tokens=9, arrival_step=i)
                    for i, p in enumerate(prompts)]
            res = srv.run_until_idle()
            outs[eng is spec16] = [res[r] for r in rids]
        for a, b in zip(outs[True], outs[False]):
            np.testing.assert_array_equal(a, b)

    def test_mixed_sampled_traffic_key_schedule_fallback(self,
                                                         spec_setup):
        """Sampled slots never speculate (k=0 fallback): a sampled
        request decoding NEXT TO a speculating greedy request still
        matches generate(seed) token-for-token — the per-request key
        schedule survives because its verify steps emit exactly one
        token through the same split+sample sequence."""
        model, cfg, spec16, _ = spec_setup
        spec16.reset()
        pg, pk = _prompts(cfg, 2, (5, 9))
        srv = Server(spec16)
        rg = srv.submit(pg, max_new_tokens=8)
        rk = srv.submit(pk, max_new_tokens=8, temperature=1.0, top_k=50,
                        seed=7)
        res = srv.run_until_idle()
        np.testing.assert_array_equal(
            res[rg], _ref(model, pg, 8, temperature=0.0))
        np.testing.assert_array_equal(
            res[rk], _ref(model, pk, 8, do_sample=True, temperature=1.0,
                          top_k=50, seed=7))


class TestAcceptance:
    def test_oracle_drafter_accepts_full_window(self, spec_setup,
                                                monkeypatch):
        """With a perfect drafter every proposed token is accepted:
        acceptance rate == 1.0, the stream advances k+1 tokens per
        verify step (ragged at the budget tail), and the output stays
        bit-identical. max_new=14 at k=4: steps emit 5/5/4 after the
        prefill token -> exactly 3 verify steps."""
        model, cfg, spec16, _ = spec_setup
        spec16.reset()
        p = _prompts(cfg, 5, (6,))[0]
        ref = _ref(model, p, 14, temperature=0.0)
        cont = ref[len(p):].astype(np.int32)    # [tok0, tail...]
        srv = Server(spec16)
        rid = srv.submit(p, max_new_tokens=14)
        monkeypatch.setattr(spec16, "_propose", _oracle(spec16, {rid: cont}))
        res = srv.run_until_idle()
        np.testing.assert_array_equal(res[rid], ref)
        assert spec16.acceptance_rate() == 1.0
        assert spec16.verify_steps == 3
        assert spec16.draft_accepted == 10       # 4 + 4 + 2

    def test_eos_inside_accepted_span(self, spec_setup, monkeypatch):
        """An eos landing mid-span cuts the ragged advance at the eos
        (one verify step retires the slot) and the result equals
        generate(eos_token_id=...) including its eos padding."""
        model, cfg, spec16, _ = spec_setup
        spec16.reset()
        p = _prompts(cfg, 6, (7,))[0]
        free = _ref(model, p, 14, temperature=0.0)
        cont = free[len(p):].astype(np.int32)
        eos = int(cont[3])          # 4th generated token: mid first span
        assert eos not in cont[:3]  # genuinely mid-span, not at an edge
        ref = _ref(model, p, 14, temperature=0.0, eos_token_id=eos)
        srv = Server(spec16)
        rid = srv.submit(p, max_new_tokens=14, eos_token_id=eos)
        monkeypatch.setattr(spec16, "_propose", _oracle(spec16, {rid: cont}))
        res = srv.run_until_idle()
        np.testing.assert_array_equal(res[rid], ref)
        assert (res[rid][len(p) + 4:] == eos).all()
        assert spec16.verify_steps == 1          # retired inside span 1

    def test_k0_degenerates_to_plain_decode(self, spec_setup):
        """k=0: the (S, 1) verify window emits exactly one token per
        step — still bit-identical, still one compile, zero drafts."""
        model, cfg, _, _ = spec_setup
        eng = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4,
            spec=SpecConfig(k=0))
        assert isinstance(eng, SpecPagedEngine)
        prompts = _prompts(cfg, 8, (5, 9, 12))
        srv = Server(eng)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        res = srv.run_until_idle()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 6, temperature=0.0))
        assert eng.decode_compile_count() == 1
        assert eng.draft_proposed == 0 and eng.draft_accepted == 0

    def test_repetitive_stream_actually_speculates(self, spec_setup):
        """The real n-gram drafter on a repetitive continuation: some
        drafts must be accepted (the speculation path actually fires —
        bit-identity alone would also pass with a dead drafter)."""
        model, cfg, spec16, _ = spec_setup
        spec16.reset()
        p = np.full((16,), 7, np.int32)     # heavy-repetition prompt
        srv = Server(spec16)
        rid = srv.submit(p, max_new_tokens=40)
        res = srv.run_until_idle()
        np.testing.assert_array_equal(
            res[rid], _ref(model, p, 40, temperature=0.0))
        assert spec16.draft_proposed > 0
        assert spec16.verify_steps < 39      # strictly fewer steps than
        #                                     tokens -> multi-token steps


class TestDrafter:
    def test_ngram_lookup_longest_match_wins(self):
        h = np.array([1, 2, 3, 9, 1, 2, 3, 5, 1, 2, 3], np.int32)
        # trigram [1,2,3] most recently continued with 5
        np.testing.assert_array_equal(ngram_propose(h, 1, 3, 1), [5])

    def test_cycle_self_extends_past_period(self):
        h = np.array([4, 7, 4, 7, 4, 7], np.int32)
        # period-2 cycle must still fill a k=6 window
        np.testing.assert_array_equal(ngram_propose(h, 6, 3, 1),
                                      [4, 7, 4, 7, 4, 7])

    def test_no_match_returns_empty(self):
        h = np.array([1, 2, 3, 4, 5, 6], np.int32)
        assert ngram_propose(h, 4, 3, 1).size == 0
        assert ngram_propose(np.array([1], np.int32), 4, 3, 1).size == 0
        assert ngram_propose(h, 0, 3, 1).size == 0


class TestRouting:
    def test_env_knob_routes_and_sizes_k(self, spec_setup, monkeypatch):
        model = spec_setup[0]
        monkeypatch.setenv("PT_SERVING_SPEC", "3")
        eng = ContinuousBatchingEngine(model, num_slots=2, max_len=32,
                                       decode_block=4)
        assert isinstance(eng, SpecPagedEngine) and eng.spec_k == 3

    def test_env_never_reroutes_explicit_backend(self, spec_setup,
                                                 monkeypatch):
        """An explicitly passed NON-spec backend stays non-spec even
        with PT_SERVING_SPEC armed (same contract as quant/tp)."""
        model, cfg, spec16, paged = spec_setup
        plain = ContinuousBatchingEngine(
            model, num_slots=2, max_len=96, decode_block=4)
        monkeypatch.setenv("PT_SERVING_SPEC", "4")
        again = ContinuousBatchingEngine(backend=plain.backend)
        assert not isinstance(again, SpecPagedEngine)

    def test_spec_backend_is_the_decision(self, spec_setup):
        """A spec backend routes without the keyword (backend carries
        the config), at either geometry."""
        model, cfg, spec16, paged = spec_setup
        d2 = ContinuousBatchingEngine(backend=spec16.backend)
        assert isinstance(d2, SpecPagedEngine) and d2.spec_k == 4
        p2 = ContinuousBatchingEngine(backend=paged.backend)
        assert isinstance(p2, SpecPagedEngine) and p2.spec_k == 4

    def test_direct_subclass_with_spec_kw_refused(self, spec_setup):
        """spec= on a direct non-factory constructor is a hard error,
        not silently ignored."""
        model = spec_setup[0]
        with pytest.raises(ValueError, match="factory"):
            PrefillPagedEngine(model, num_slots=2, max_len=64,
                               decode_block=4, block_size=8,
                               spec=SpecConfig(k=2))

    def test_direct_ctor_paged_mismatch_refused(self, spec_setup):
        """SpecPagedEngine(paged=False) is a hard error, not a
        silently-ignored kwarg: the dense slot-pool engine was removed —
        same contract as spec= on a direct non-factory constructor."""
        model = spec_setup[0]
        with pytest.raises(ValueError, match="dense slot-pool engine"):
            SpecPagedEngine(model, num_slots=2, max_len=64,
                            decode_block=4, block_size=8, paged=False,
                            spec=SpecConfig(k=2))

    def test_spec_plus_tp_refused(self, spec_setup):
        from paddle_tpu.serving import TPConfig
        model = spec_setup[0]
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            ContinuousBatchingEngine(model, num_slots=2, max_len=32,
                                     decode_block=4,
                                     spec=SpecConfig(k=2),
                                     tp=TPConfig(axes=("mp",)))


class TestSpecPreemption:
    """PR 13 follow-up lifted: priority preemption composes with
    speculative engines. Drafting is a pure host function of history —
    a resumed slot re-drafts exactly what the uninterrupted run would
    have, so preempted spec streams stay bit-identical to generate()
    (greedy) / generate(seed) (sampled, which never speculates)."""

    @pytest.mark.parametrize("which", ["paged"])
    def test_greedy_preempt_resume_bit_identical(self, spec_setup,
                                                 which):
        from paddle_tpu.serving import Frontend
        model, cfg, _, engine = spec_setup
        engine.reset()
        prompts = _prompts(cfg, 30, (5, 9, 12))
        fe = Frontend(engine, preemption=True)
        low = [fe.submit(p, max_new_tokens=20, priority=0)
               for p in prompts[:2]]
        for _ in range(3):
            fe.pump()
        hi = fe.submit(prompts[2], max_new_tokens=4, priority=5)
        res = fe.run_until_idle()
        st = fe.stats()
        assert st["preemptions"] >= 1 and st["resumes"] >= 1
        for rid, p, mn in zip(low + [hi], prompts, (20, 20, 4)):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, mn, temperature=0.0))
        assert engine.decode_compile_count() == 1
        assert all(s is None for s in engine._slots)

    def test_seeded_sampled_preempt_resume_bit_identical(
            self, spec_setup):
        """A sampled slot on a spec engine (the in-graph k=0 fallback)
        carries its rng key through the eviction — the resumed stream
        follows the exact generate(seed) key schedule."""
        from paddle_tpu.serving import Frontend
        model, cfg, spec16, _ = spec_setup
        spec16.reset()
        prompts = _prompts(cfg, 31, (5, 9, 12))
        fe = Frontend(spec16, preemption=True)
        rs_ = fe.submit(prompts[0], max_new_tokens=20, priority=0,
                        temperature=0.9, top_k=40, seed=11)
        rg = fe.submit(prompts[1], max_new_tokens=20, priority=0)
        for _ in range(3):
            fe.pump()
        hi = fe.submit(prompts[2], max_new_tokens=4, priority=5)
        res = fe.run_until_idle()
        assert fe.stats()["preemptions"] >= 1
        np.testing.assert_array_equal(
            res[rs_], _ref(model, prompts[0], 20, do_sample=True,
                           temperature=0.9, top_k=40, seed=11))
        np.testing.assert_array_equal(
            res[rg], _ref(model, prompts[1], 20, temperature=0.0))
        np.testing.assert_array_equal(
            res[hi], _ref(model, prompts[2], 4, temperature=0.0))
        assert spec16.decode_compile_count() == 1

    def test_explicit_preemption_no_longer_refused(self, spec_setup):
        """The PR 13 NotImplementedError guard is gone: explicit
        preemption=True on a spec engine constructs (TP engines are
        still refused — see test_frontend.py)."""
        from paddle_tpu.serving import FairScheduler, Server
        model, cfg, spec16, _ = spec_setup
        spec16.reset()
        srv = Server(spec16, FairScheduler(), preemption=True)
        assert srv.preemption


class TestSpecResilience:
    def test_chaos_schedule_with_spec_holds_invariants(self,
                                                       spec_setup):
        """Seeded transient faults (step/harvest/prefill/allocate/tick)
        + one poison against the speculative paged engine: every
        request completes or fails explicitly, completed greedy rows
        stay bit-identical (transient faults are semantically invisible
        — a step-fault retry re-drafts the identical proposal), no slot
        or block leaks, compile counts pinned."""
        model, cfg, _, paged = spec_setup
        paged.reset()
        rs = np.random.RandomState(105)
        lens = rs.randint(4, 20, size=6)
        news = rs.randint(3, 10, size=6)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in lens]
        srv = Server(paged, Scheduler(prefill_token_budget=8),
                     resilience=ResilienceConfig(
                         retry_attempts=3, retry_backoff_s=0.001,
                         breaker_threshold=12, deadline_ticks=80,
                         seed=5))
        rids = [srv.submit(p, max_new_tokens=int(mn), arrival_step=i)
                for i, (p, mn) in enumerate(zip(prompts, news))]
        spec_str = ("serving.step_block:p=0.06;serving.harvest:p=0.05;"
                    "serving.prefill_tick:p=0.08;serving.allocate:p=0.2;"
                    "server.tick:p=0.05;serving.poison:at=4,times=1")
        with faults.injected(spec_str, seed=5):
            res = srv.run_until_idle(max_ticks=400)
        assert srv.scheduler.pending() == 0 and not paged.has_live()
        for rid, p, mn in zip(rids, prompts, news):
            assert rid in res, f"request {rid} vanished"
            v = res[rid]
            if isinstance(v, RequestFailure):
                assert v.reason in ("timeout", "poisoned",
                                    "circuit_open", "shed")
            else:
                np.testing.assert_array_equal(
                    v, _ref(model, p, int(mn), temperature=0.0))
        assert all(s is None for s in paged._slots)
        assert not paged._jobs and not paged._prefill_slots
        assert not paged.manager._ref
        paged.manager.assert_consistent()
        assert paged.decode_compile_count() == 1
        assert paged.prefill_compile_count() == 1

    def test_poison_quarantines_only_that_slot(self, spec_setup):
        """The NaN sentinel rides the verify block: the poisoned slot
        fails as 'poisoned', its neighbour's stream is untouched."""
        model, cfg, spec16, _ = spec_setup
        spec16.reset()
        p0, p1 = _prompts(cfg, 9, (5, 9))
        srv = Server(spec16)
        r0 = srv.submit(p0, max_new_tokens=10)
        r1 = srv.submit(p1, max_new_tokens=10, arrival_step=1)
        with faults.injected("serving.poison:at=2,times=1", seed=0):
            res = srv.run_until_idle(max_ticks=100)
        outcomes = {rid: res[rid] for rid in (r0, r1)}
        poisoned = [rid for rid, v in outcomes.items()
                    if isinstance(v, RequestFailure)]
        assert len(poisoned) == 1
        assert outcomes[poisoned[0]].reason == "poisoned"
        survivor = r1 if poisoned == [r0] else r0
        pv = p1 if survivor == r1 else p0
        np.testing.assert_array_equal(
            outcomes[survivor], _ref(model, pv, 10, temperature=0.0))

    def test_kill_restore_mid_stream_bit_identical(self, spec_setup,
                                                   tmp_path,
                                                   _no_compile_cache):
        """Mid-stream snapshot/restore of the speculative engine into a
        fresh process simulation: every stream finishes bit-identical
        and the spec counters survive the round trip."""
        model, cfg, spec16, _ = spec_setup
        prompts = _prompts(cfg, 11, (5, 9, 12))
        news = [10, 8, 9]

        def submit_all(srv):
            return [srv.submit(p, max_new_tokens=mn, arrival_step=i)
                    for i, (p, mn) in enumerate(zip(prompts, news))]

        spec16.reset()
        srv_ref = Server(spec16)
        rids = submit_all(srv_ref)
        ref = srv_ref.run_until_idle()

        spec16.reset()
        srv_kill = Server(spec16)
        assert submit_all(srv_kill) == rids
        srv_kill.run_until_idle(max_ticks=3)
        assert spec16.has_live()
        steps_at_kill = spec16.verify_steps
        path = str(tmp_path / "spec.npz")
        srv_kill.snapshot(path)

        paddle.seed(0)
        model2 = LlamaForCausalLM(cfg)
        engine2 = ContinuousBatchingEngine(
            model2, num_slots=2, max_len=96, decode_block=4,
            spec=SpecConfig(k=4))
        srv_new = Server.restore(path, engine2)
        assert engine2.verify_steps == steps_at_kill
        res = srv_new.run_until_idle()
        for rid in rids:
            np.testing.assert_array_equal(res[rid], ref[rid])
        assert engine2.decode_compile_count() == 1

    def test_restore_refuses_mismatched_k(self, spec_setup, tmp_path):
        """A snapshot taken at k=4 cannot restore into a k=2 engine
        (different verify window) — loud error, not silent resume."""
        model, cfg, spec16, _ = spec_setup
        spec16.reset()
        path = str(tmp_path / "k4.npz")
        spec16.snapshot(path)
        engine2 = ContinuousBatchingEngine(
            model, num_slots=2, max_len=96, decode_block=4,
            spec=SpecConfig(k=2))
        with pytest.raises(ValueError, match="k=4"):
            engine2.restore(path)
