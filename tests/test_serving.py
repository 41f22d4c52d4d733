"""Continuous-batching serving engine (paddle_tpu/serving/): greedy
bit-exactness vs per-request generate(), slot retire/refill under
staggered arrivals, mixed per-slot sampling in one program, and the
static-shape invariant (exactly ONE compiled decode program across all
admissions/retirements)."""
import numpy as np
import jax
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (ContinuousBatchingEngine, Request,
                                Scheduler, Server)


@pytest.fixture(scope="module")
def serving_setup():
    """One model + one engine for the whole file: the engine's decode
    program compiles once and every test's workload rides it (reset()
    frees the slots, never the compiled programs)."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    engine = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                      decode_block=4,
                                      prompt_buckets=(8, 16))
    return model, cfg, engine


def _ref(model, prompt, max_new, **kw):
    return model.generate(paddle.to_tensor(prompt[None, :]),
                          max_new_tokens=max_new, **kw).numpy()[0]


class TestContinuousBatching:
    def test_greedy_bit_exact_on_ragged_stream_one_compile(
            self, serving_setup):
        """(a)+(d): 5 ragged greedy requests through 2 slots — every
        output bit-identical to a standalone generate() call, and the
        decode program compiled exactly once across all admissions."""
        model, cfg, engine = serving_setup
        engine.reset()
        rs = np.random.RandomState(0)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 9, 12, 5, 9)]
        news = [6, 4, 7, 5, 6]
        srv = Server(engine)
        rids = [srv.submit(p, max_new_tokens=mn)
                for p, mn in zip(prompts, news)]
        res = srv.run_until_idle()
        for rid, p, mn in zip(rids, prompts, news):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, mn, temperature=0.0))
        assert engine.decode_compile_count() == 1
        stats = srv.stats()
        assert stats["requests_completed"] == 5
        assert stats["tokens_emitted"] == sum(news)
        assert 0.0 < stats["slot_occupancy"] <= 1.0

    def test_slot_retire_refill_staggered_arrivals(self, serving_setup):
        """(b): arrivals spread over the engine-block clock force
        retire→refill churn (5 requests, 2 slots); outputs must still
        match per-request generate(), including an eos retirement."""
        model, cfg, engine = serving_setup
        engine.reset()
        rs = np.random.RandomState(1)
        prompts = [rs.randint(0, cfg.vocab_size, (5 + i,)).astype(np.int32)
                   for i in range(5)]
        news = [8, 3, 6, 4, 5]
        # request 0 retires at its second generated token via eos
        ref0 = _ref(model, prompts[0], news[0], temperature=0.0)
        eos0 = int(ref0[len(prompts[0]) + 1])
        srv = Server(engine)
        rids = [srv.submit(p, max_new_tokens=mn, arrival_step=2 * i,
                           eos_token_id=eos0 if i == 0 else None)
                for i, (p, mn) in enumerate(zip(prompts, news))]
        res = srv.run_until_idle()
        np.testing.assert_array_equal(
            res[rids[0]],
            _ref(model, prompts[0], news[0], temperature=0.0,
                 eos_token_id=eos0))
        for i in range(1, 5):
            np.testing.assert_array_equal(
                res[rids[i]],
                _ref(model, prompts[i], news[i], temperature=0.0))
        assert engine.decode_compile_count() == 1

    def test_eos_beyond_poll_window_static_shape(self, serving_setup):
        """generate()'s eos early-exit returns the full (b, s+max_new)
        eos-padded shape even when the exit lands past the
        eos_check_every polling window — and the served result matches
        it bit-exactly (the parity invariant at max_new > 8)."""
        model, cfg, engine = serving_setup
        engine.reset()
        rs = np.random.RandomState(4)
        p = rs.randint(0, cfg.vocab_size, (5,)).astype(np.int32)
        free = _ref(model, p, 16, temperature=0.0, use_scan_decode=False)
        eos = int(free[len(p) + 1])     # eos hits at the 2nd new token
        ref = _ref(model, p, 16, temperature=0.0, eos_token_id=eos)
        assert ref.shape[0] == len(p) + 16
        assert (ref[len(p) + 1:] == eos).all()
        srv = Server(engine)
        rid = srv.submit(p, max_new_tokens=16, eos_token_id=eos)
        res = srv.run_until_idle()
        np.testing.assert_array_equal(res[rid], ref)

    def test_mixed_sampling_params_one_program(self, serving_setup):
        """(c): greedy + top-k sampled + top-p sampled requests decode
        concurrently in ONE program (per-slot param arrays). The greedy
        row stays bit-identical to generate(); sampled rows follow the
        same per-request key schedule as generate(seed=...)."""
        model, cfg, engine = serving_setup
        engine.reset()
        rs = np.random.RandomState(2)
        pg = rs.randint(0, cfg.vocab_size, (5,)).astype(np.int32)
        pk = rs.randint(0, cfg.vocab_size, (9,)).astype(np.int32)
        pp = rs.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
        srv = Server(engine)
        rg = srv.submit(pg, max_new_tokens=6)
        rk = srv.submit(pk, max_new_tokens=6, temperature=1.0, top_k=50,
                        seed=7)
        rp = srv.submit(pp, max_new_tokens=6, temperature=0.8, top_p=0.9,
                        seed=11)
        res = srv.run_until_idle()
        np.testing.assert_array_equal(res[rg],
                                      _ref(model, pg, 6, temperature=0.0))
        np.testing.assert_array_equal(
            res[rk], _ref(model, pk, 6, do_sample=True, temperature=1.0,
                          top_k=50, seed=7))
        np.testing.assert_array_equal(
            res[rp], _ref(model, pp, 6, do_sample=True, temperature=0.8,
                          top_p=0.9, seed=11))
        # same stream again: reproducible
        engine.reset()
        srv2 = Server(engine)
        rk2 = srv2.submit(pk, max_new_tokens=6, temperature=1.0, top_k=50,
                          seed=7)
        rk3 = srv2.submit(pk, max_new_tokens=6, temperature=1.0, top_k=50,
                          seed=8)
        res2 = srv2.run_until_idle()
        np.testing.assert_array_equal(res[rk], res2[rk2])
        assert not np.array_equal(res2[rk2], res2[rk3])
        assert engine.decode_compile_count() == 1

    def test_capacity_and_bucket_validation(self, serving_setup):
        model, cfg, engine = serving_setup
        engine.reset()
        srv = Server(engine)
        with pytest.raises(ValueError, match="slot capacity"):
            srv.submit(np.ones((8,), np.int32), max_new_tokens=60)
            srv.run_until_idle()
        with pytest.raises(ValueError, match="largest bucket"):
            engine.bucket_len(17)


class TestScheduler:
    def _req(self, rid, arrival=0):
        return Request(request_id=rid, prompt=np.ones((4,), np.int32),
                       arrival_step=arrival)

    def test_fifo_and_arrival_visibility(self):
        s = Scheduler()
        s.submit(self._req(0, arrival=3))
        s.submit(self._req(1, arrival=0))
        assert [r.request_id for r in
                s.pop_ready(0, free_slots=4, engine_idle=True)] == [1]
        assert s.pop_ready(1, 4, True) == []        # id 0 not yet visible
        assert [r.request_id for r in s.pop_ready(3, 4, True)] == [0]

    def test_max_wait_batching_gate(self):
        s = Scheduler(max_wait_steps=5, min_admit=3)
        s.submit(self._req(0, arrival=0))
        # gate holds while the engine is busy and the queue is short...
        assert s.pop_ready(1, 4, engine_idle=False) == []
        s.submit(self._req(1, arrival=1))
        assert s.pop_ready(2, 4, engine_idle=False) == []
        # ...releases at min_admit...
        s.submit(self._req(2, arrival=2))
        assert len(s.pop_ready(3, 4, engine_idle=False)) == 3
        # ...or when the oldest waited max_wait_steps...
        s.submit(self._req(3, arrival=3))
        assert s.pop_ready(4, 4, engine_idle=False) == []
        assert len(s.pop_ready(8, 4, engine_idle=False)) == 1
        # ...or when the engine would idle
        s.submit(self._req(4, arrival=9))
        assert len(s.pop_ready(9, 4, engine_idle=True)) == 1

    def test_respects_free_slots(self):
        s = Scheduler()
        for i in range(5):
            s.submit(self._req(i))
        assert len(s.pop_ready(0, free_slots=2, engine_idle=True)) == 2
        assert s.pending() == 3


class TestArtifactServing:
    def test_exported_engine_serves_same_stream(self, serving_setup,
                                                tmp_path):
        """The AOT artifact (export_decoder(engine_slots=...)) serves
        the SAME engine: greedy results bit-identical to both the
        in-process engine and per-request generate()."""
        from paddle_tpu.inference import GenerationPredictor, \
            export_decoder
        model, cfg, engine = serving_setup
        path = export_decoder(model, str(tmp_path / "srv"), batch=1,
                              prompt_len=8, max_len=64, engine_slots=2,
                              engine_decode_block=4,
                              engine_prompt_buckets=(8, 16))
        served = GenerationPredictor(path)
        rs = np.random.RandomState(3)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 9, 12)]
        res = served.serve([{"prompt": p, "max_new_tokens": 5}
                            for p in prompts])
        for rid, p in enumerate(prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 5, temperature=0.0))

    def test_artifact_block_arity_both_directions(self, serving_setup,
                                                  tmp_path):
        """New exports record block_outputs=5 so the serving host knows
        the artifact carries the NaN-sentinel flags; an old artifact
        (no arity key — simulated by stripping it) still loads, with
        carries_nan_flags False."""
        import pickle
        from paddle_tpu.inference import export_decoder
        from paddle_tpu.serving.engine import ArtifactStepBackend
        model, cfg, engine = serving_setup
        path = export_decoder(model, str(tmp_path / "arity"), batch=1,
                              prompt_len=8, max_len=64, engine_slots=2,
                              engine_decode_block=4,
                              engine_prompt_buckets=(8,))
        with open(path, "rb") as f:
            blob = pickle.load(f)
        assert blob["engine"]["config"]["block_outputs"] == 5
        back = ArtifactStepBackend(blob)
        assert back.carries_nan_flags
        # artifact identity: stable per blob, sensitive to the config
        fp = back.artifact_fingerprint
        assert fp == ArtifactStepBackend(blob).artifact_fingerprint
        del blob["engine"]["config"]["block_outputs"]
        legacy = ArtifactStepBackend(blob)
        assert not legacy.carries_nan_flags
        assert legacy.artifact_fingerprint != fp


class TestArtifactSnapshotIdentity:
    """PR 5 carried follow-up: engine snapshots record the backing AOT
    artifact's fingerprint, and a restore onto a DIFFERENT artifact is
    refused. Pinned with a stub backend; the artifact-level fingerprint
    computation rides the TestArtifactServing tests."""

    class _FingerprintBackend:
        """Stub of an ArtifactStepBackend: proxies the live model
        backend and carries an artifact fingerprint."""

        def __init__(self, inner, fingerprint):
            self._inner = inner
            self.artifact_fingerprint = fingerprint

        def __getattr__(self, name):
            return getattr(self.__dict__["_inner"], name)

    def test_stub_kill_restore_round_trip(self, serving_setup,
                                          tmp_path):
        """Kill mid-stream on an artifact-backed engine, restore into a
        fresh engine on the SAME artifact: streams finish bit-identical
        (the ArtifactStepBackend snapshot/restore contract)."""
        model, cfg, engine = serving_setup
        rs = np.random.RandomState(31)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 9, 12)]

        def build(fp):
            return ContinuousBatchingEngine(
                backend=self._FingerprintBackend(engine.backend, fp),
                prompt_buckets=(8, 16))

        def submit_all(srv):
            return [srv.submit(p, max_new_tokens=8, arrival_step=i)
                    for i, p in enumerate(prompts)]

        art = build("sha1:abc123")
        srv_ref = Server(art)
        rids = submit_all(srv_ref)
        ref = srv_ref.run_until_idle()

        art2 = build("sha1:abc123")
        srv_kill = Server(art2)
        assert submit_all(srv_kill) == rids
        srv_kill.run_until_idle(max_ticks=2)
        assert art2.has_live()
        path = str(tmp_path / "art.npz")
        srv_kill.snapshot(path)

        art3 = build("sha1:abc123")       # fresh process, same artifact
        srv_new = Server.restore(path, art3)
        res = srv_new.run_until_idle()
        for rid in rids:
            np.testing.assert_array_equal(res[rid], ref[rid])

    def test_restore_refuses_different_artifact(self, serving_setup,
                                                tmp_path):
        model, cfg, engine = serving_setup
        art = ContinuousBatchingEngine(
            backend=self._FingerprintBackend(engine.backend, "sha1:aaa"),
            prompt_buckets=(8, 16))
        path = str(tmp_path / "aaa.npz")
        art.snapshot(path)
        other = ContinuousBatchingEngine(
            backend=self._FingerprintBackend(engine.backend, "sha1:bbb"),
            prompt_buckets=(8, 16))
        with pytest.raises(ValueError, match="different AOT artifact"):
            other.restore(path)

    def test_model_backed_engines_stay_compatible(self, serving_setup,
                                                  tmp_path):
        """Either side lacking a fingerprint (model-backed engine) keeps
        the old behavior — pool_specs validation only — so existing
        snapshots and mixed artifact/model restores still load."""
        model, cfg, engine = serving_setup
        engine.reset()
        path = str(tmp_path / "plain.npz")
        engine.snapshot(path)
        art = ContinuousBatchingEngine(
            backend=self._FingerprintBackend(engine.backend, "sha1:xyz"),
            prompt_buckets=(8, 16))
        art.restore(path)                  # saved None, current set: ok
        art.reset()
        path2 = str(tmp_path / "art.npz")
        art.snapshot(path2)
        engine.restore(path2)              # saved set, current None: ok
        engine.reset()


class TestDecodeBlockArity:
    """The PR 5 NaN-sentinel grew the decode block from 4 outputs
    (cache, state, toks, lives) to 5 (+ per-step (S,) ok flags).
    Serving hosts meet BOTH generations: new programs carry the flags;
    old 4-output AOT artifacts are padded with flags=None by
    engine.step_block, which makes the sentinel inert for them without
    touching the stream."""

    class _LegacyBackend:
        """A pre-sentinel artifact: its decode block returns 4 values."""
        def __init__(self, inner):
            self._inner = inner
            self.carries_nan_flags = False

        def __getattr__(self, name):
            return getattr(self.__dict__["_inner"], name)

        def decode_block(self, cache_flat, state):
            return self._inner.decode_block(cache_flat, state)[:4]

    def test_new_block_emits_five_outputs(self, serving_setup):
        model, cfg, engine = serving_setup
        engine.reset()
        out = engine.backend.decode_block(engine._cache, engine._state)
        assert len(out) == 5        # (cache, state, toks, lives, oks)
        engine.reset()              # the direct call donated cache/state

    def test_legacy_four_output_stream_bit_identical(self,
                                                     serving_setup):
        """A 4-output backend serves the same greedy stream: the engine
        pads the missing ok flags with None and the armed sentinel
        (Server default) skips quarantine instead of crashing."""
        model, cfg, engine = serving_setup
        legacy = ContinuousBatchingEngine(
            backend=self._LegacyBackend(engine.backend))
        rs = np.random.RandomState(21)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 9, 12)]
        srv = Server(legacy)
        assert legacy.nan_sentinel          # armed, inert on None flags
        rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
        res = srv.run_until_idle()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 5, temperature=0.0))
