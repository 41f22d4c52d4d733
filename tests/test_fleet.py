"""Disaggregated prefill/decode fleet (serving/fleet.py + handoff.py):
a request prefilled on worker A and decoded on worker B streams
BIT-IDENTICAL to a single-replica Server (greedy AND seeded-sampled;
16- and 8-token blocks, kv_int8) with decode/prefill compile counts pinned
at 1 and zero new compiled programs on the decode steady path. Plus:
the versioned bytes-true wire format (int8 codes ship quantized, never
dequantized in transit), chained-SHA1 prefix-affinity routing with
queue-depth spillover (the PR 4 prefix cache as a fleet-wide asset),
handoff failures riding the PR 5 retry/backoff/breaker machinery, live
decode-worker migration via snapshot/restore, and a seeded chaos
schedule over the new handoff fault sites with zero block leaks on
BOTH workers' arenas."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (ContinuousBatchingEngine, DecodeWorker,
                                Fleet, FleetRouter, KVHandoff,
                                PrefillPagedEngine, PrefillWorker,
                                RequestFailure,
                                ResilienceConfig, Server, decode_handoff,
                                encode_handoff, reshard_kv_chunks)
from paddle_tpu.utils import faults


@pytest.fixture(scope="module")
def setup():
    """One model + the 2-prefill/2-decode engine set at 8-token blocks,
    a 1/1 pair at the default 16-token geometry and the int8 1/1 pair
    for the whole file (reset() frees slots/blocks, never the compiled
    programs)."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    kw = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)
    pf = [PrefillPagedEngine(model, **kw) for _ in range(2)]
    dc = [ContinuousBatchingEngine(model, paged=True, **kw)
          for _ in range(2)]
    pf_d = PrefillPagedEngine(model, num_slots=2, max_len=64,
                              decode_block=4)
    dc_d = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                    decode_block=4)
    pf_8 = PrefillPagedEngine(model, kv_int8=True, **kw)
    dc_8 = ContinuousBatchingEngine(model, paged=True, kv_int8=True,
                                    **kw)
    return model, cfg, pf, dc, (pf_d, dc_d), (pf_8, dc_8)


@pytest.fixture(autouse=True)
def _disarmed():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def _no_compile_cache():
    """Same environment guard as tests/test_resilience.py: tests that
    compile a fresh paged backend in this process must bypass the
    persistent jax compilation cache (the documented jaxlib
    second-identical-compile heap landmine)."""
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


def _reset(*engines):
    for e in engines:
        e.reset()


def _fleet(pf_engines, dc_engines, **kw):
    return Fleet([PrefillWorker(e) for e in pf_engines],
                 [DecodeWorker(e) for e in dc_engines], **kw)


def _check_clean(fleet):
    """Zero-leak teardown: empty slots/outboxes/queues and exact arena
    accounting on EVERY worker, both specialties."""
    assert not fleet.busy()
    for w in fleet.prefill:
        assert not w.engine._outbox
        assert all(s is None for s in w.engine._slots)
        if hasattr(w.engine, "manager"):
            assert not w.engine.manager._ref
            w.engine.manager.assert_consistent()
    for d in fleet.decode:
        assert all(s is None for s in d.engine._slots)
        if hasattr(d.engine, "manager"):
            assert not d.engine.manager._ref
            d.engine.manager.assert_consistent()


class TestWireFormat:
    def test_roundtrip_and_refusals(self):
        h = KVHandoff(
            meta={"kind": "paged", "request": {"request_id": 7},
                  "tok0": 3, "pos0": 5, "rem0": 4},
            arrays={"prompt": np.arange(5, dtype=np.int32),
                    "kv_0": np.ones((2, 8, 4, 32), np.int8)})
        data = encode_handoff(h)
        assert isinstance(data, bytes) and len(data) > 0
        back = decode_handoff(data)
        assert back.meta["kind"] == "paged" and back.request_id == 7
        np.testing.assert_array_equal(back.arrays["prompt"],
                                      h.arrays["prompt"])
        assert back.arrays["kv_0"].dtype == np.int8
        with pytest.raises(ValueError, match="not a KV handoff"):
            decode_handoff(_corrupt())
        h.meta["version"] = 99       # meta keys override the stamp
        with pytest.raises(ValueError, match="version"):
            decode_handoff(encode_handoff(h))

    @pytest.mark.parametrize("kind", ["dense", "prefix", None])
    def test_foreign_kind_refused_on_decode(self, kind):
        """A hand-off payload is input from outside the process: one
        whose kind is not "paged" (the removed engine's "dense", a
        prefix-fetch kind, none) is refused when it is decoded."""
        meta = {"request": {"request_id": 7}, "tok0": 3, "pos0": 5,
                "rem0": 4}
        if kind is not None:
            meta["kind"] = kind
        h = KVHandoff(meta=meta,
                      arrays={"prompt": np.arange(5, dtype=np.int32)})
        with pytest.raises(ValueError, match="payload in a"):
            decode_handoff(encode_handoff(h))

    def test_reshard_kv_chunks_identity(self):
        rs = np.random.RandomState(0)
        full = rs.randn(3, 8, 6, 4).astype(np.float32)
        for src, dst in ((2, 3), (3, 2), (1, 6), (6, 1)):
            chunks = np.split(full, src, axis=2)
            out = reshard_kv_chunks(chunks, dst, axis=2)
            assert len(out) == dst
            np.testing.assert_array_equal(
                np.concatenate(out, axis=2), full)
        with pytest.raises(ValueError, match="does not divide"):
            reshard_kv_chunks(np.split(full, 2, axis=2), 4, axis=2)

    def test_int8_payload_ships_codes_never_dequantized(self, setup):
        """The wire pin: an int8-arena handoff carries int8 codes +
        fp32 scales at storage size — the fp32-equivalent of the same
        positions is ~3.6x larger (4d/(d+4) at head_dim 32)."""
        model, cfg, *_, (pf_8, _dc) = setup
        _reset(pf_8)
        w = PrefillWorker(pf_8)
        p = _prompts(cfg, 3, (17,))[0]       # 3 shipped blocks
        w.server.submit(p, max_new_tokens=6)
        for _ in range(5):
            w.tick()
        (ph,) = pf_8.take_handoffs()
        h = pf_8.extract_handoff(ph, source="t")
        kv = [a for k, a in h.arrays.items() if k.startswith("kv_")]
        assert any(a.dtype == np.int8 for a in kv)
        assert all(a.dtype in (np.int8, np.float32) for a in kv)
        wire = decode_handoff(encode_handoff(h))
        assert any(a.dtype == np.int8 for k, a in wire.arrays.items()
                   if k.startswith("kv_"))
        fp32_equiv = sum(a.nbytes * 4 for a in kv
                         if a.dtype == np.int8)
        ratio = fp32_equiv / h.kv_bytes()
        assert ratio > 3.3, f"int8 wire ratio {ratio}"
        pf_8.release_handoff(ph)
        pf_8.manager.assert_consistent()

    def test_only_prompt_blocks_ship(self, setup):
        """Decode-position blocks are junk the decode worker writes
        before reading — they must cost zero wire bytes."""
        model, cfg, pf, dc, *_ = setup
        _reset(pf[0])
        w = PrefillWorker(pf[0])
        p = _prompts(cfg, 4, (9,))[0]        # 2 prompt blocks...
        w.server.submit(p, max_new_tokens=20)   # ...4 total allocated
        for _ in range(5):
            w.tick()
        (ph,) = pf[0].take_handoffs()
        h = pf[0].extract_handoff(ph)
        assert h.meta["n_ship"] == 2 and h.meta["n_blocks"] == 4
        for k, a in h.arrays.items():
            if k.startswith("kv_"):
                assert a.shape[0] == 2
        pf[0].release_handoff(ph)
        pf[0].manager.assert_consistent()


def _corrupt() -> bytes:
    # valid npz whose meta is not a handoff
    import io
    bio = io.BytesIO()
    np.savez(bio, __meta__=np.array('{"format": "other"}'))
    return bio.getvalue()


class TestFleetBitIdentity:
    def test_paged_greedy_staggered_bit_identical_one_compile(
            self, setup):
        """The headline pin: prefill-on-A → handoff → decode-on-B
        streams equal a single-replica Server AND generate() exactly,
        across a 2x2 fleet with staggered arrivals and more requests
        than any worker has slots."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        prompts = _prompts(cfg, 5, (5, 9, 12, 7, 10, 6))
        news = [6, 4, 7, 5, 8, 6]
        fleet = _fleet(pf, dc)
        rids = [fleet.submit(p, max_new_tokens=mn, arrival_step=i)
                for i, (p, mn) in enumerate(zip(prompts, news))]
        res = fleet.run_until_idle(max_ticks=300)
        # single-replica twin on one of the SAME engines (already
        # compiled: the comparison adds zero programs)
        _reset(*dc)
        srv = Server(dc[0])
        srids = [srv.submit(p, max_new_tokens=mn, arrival_step=i)
                 for i, (p, mn) in enumerate(zip(prompts, news))]
        sres = srv.run_until_idle()
        for rid, srid, p, mn in zip(rids, srids, prompts, news):
            np.testing.assert_array_equal(res[rid], sres[srid])
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, mn, temperature=0.0))
        assert fleet.stats()["handoffs"] == len(prompts)
        for d in fleet.decode:
            assert d.engine.decode_compile_count() == 1
        for w in fleet.prefill:
            assert w.engine.prefill_compile_count() == 1
        _check_clean(fleet)

    def test_paged_seeded_sampled_bit_identical(self, setup):
        """The carried rng key is the NEXT step's split input: a
        sampled stream decoded on a different worker follows the exact
        generate(seed) key schedule."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        prompts = _prompts(cfg, 6, (5, 9, 12))
        fleet = _fleet(pf, dc)
        r0 = fleet.submit(prompts[0], max_new_tokens=6,
                          temperature=0.9, top_k=40, seed=11)
        r1 = fleet.submit(prompts[1], max_new_tokens=5,
                          temperature=1.1, top_p=0.9, seed=3)
        r2 = fleet.submit(prompts[2], max_new_tokens=6)
        res = fleet.run_until_idle(max_ticks=200)
        np.testing.assert_array_equal(
            res[r0], _ref(model, prompts[0], 6, do_sample=True,
                          temperature=0.9, top_k=40, seed=11))
        np.testing.assert_array_equal(
            res[r1], _ref(model, prompts[1], 5, do_sample=True,
                          temperature=1.1, top_p=0.9, seed=3))
        np.testing.assert_array_equal(
            res[r2], _ref(model, prompts[2], 6, temperature=0.0))
        _check_clean(fleet)

    def test_dense_greedy_and_sampled_bit_identical(self, setup):
        model, cfg, _, _, (pf_d, dc_d), _ = setup
        _reset(pf_d, dc_d)
        prompts = _prompts(cfg, 7, (5, 9, 12))
        fleet = _fleet([pf_d], [dc_d])
        rg = [fleet.submit(p, max_new_tokens=6) for p in prompts[:2]]
        rs_ = fleet.submit(prompts[2], max_new_tokens=5,
                           temperature=0.9, top_k=40, seed=7)
        res = fleet.run_until_idle(max_ticks=200)
        for rid, p in zip(rg, prompts[:2]):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 6, temperature=0.0))
        np.testing.assert_array_equal(
            res[rs_], _ref(model, prompts[2], 5, do_sample=True,
                           temperature=0.9, top_k=40, seed=7))
        assert dc_d.decode_compile_count() == 1
        _check_clean(fleet)

    def test_paged_kv_int8_bit_identical(self, setup):
        """The fully quantized stack crosses the wire: int8 codes +
        scales adopt at wire size and the fleet stream equals an int8
        single-replica Server token for token."""
        model, cfg, _, _, _, (pf_8, dc_8) = setup
        _reset(pf_8, dc_8)
        prompts = _prompts(cfg, 8, (5, 9, 12))
        fleet = _fleet([pf_8], [dc_8])
        rids = [fleet.submit(p, max_new_tokens=6, arrival_step=i)
                for i, p in enumerate(prompts)]
        res = fleet.run_until_idle(max_ticks=200)
        _reset(dc_8)
        srv = Server(dc_8)
        srids = [srv.submit(p, max_new_tokens=6, arrival_step=i)
                 for i, p in enumerate(prompts)]
        sres = srv.run_until_idle()
        for rid, srid in zip(rids, srids):
            np.testing.assert_array_equal(res[rid], sres[srid])
        assert dc_8.decode_compile_count() == 1
        _check_clean(fleet)

    def test_cross_tp_degree_adopt_bit_identical(self, setup,
                                                 _no_compile_cache):
        """Source and target TP degrees differ: a payload extracted
        from a 1-chip prefill worker adopts onto a mesh-sharded decode
        worker — the wire format is layout-free (host-logical arrays)
        and the adopt path re-commits through the backend's
        ``commit_arrays`` hook, the same portable-redistribution path
        snapshot restore uses. Streams stay bit-identical."""
        import jax
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 (simulated) devices")
        from paddle_tpu.distributed.mesh import build_device_mesh
        from paddle_tpu.serving import TPConfig
        model, cfg, pf, dc, *_ = setup
        paddle.seed(0)
        cfg8 = llama_tiny_config(num_attention_heads=8,
                                 num_key_value_heads=8)
        model8 = LlamaForCausalLM(cfg8)
        mesh = build_device_mesh({"mp": 2}, allow_subset=True)
        pf1 = PrefillPagedEngine(model8, num_slots=2, max_len=64,
                                 decode_block=4, block_size=8,
                                 prefill_chunk=8)
        dc2 = ContinuousBatchingEngine(
            model8, num_slots=2, max_len=64, decode_block=4,
            paged=True, block_size=8, prefill_chunk=8,
            tp=TPConfig(axes=("mp",), mesh=mesh))
        assert dc2.tp_degree() == 2
        fleet = _fleet([pf1], [dc2])
        prompts = _prompts(cfg8, 17, (5, 9))
        rids = [fleet.submit(p, max_new_tokens=8) for p in prompts]
        res = fleet.run_until_idle(max_ticks=100)
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model8, p, 8, temperature=0.0))
        assert dc2.decode_compile_count() == 1
        _check_clean(fleet)

    def test_finished_at_prefill_never_ships(self, setup):
        """max_new==1 (or eos on the first token) completes on the
        prefill worker — no payload, no decode-worker involvement."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        p = _prompts(cfg, 9, (6,))[0]
        fleet = _fleet(pf, dc)
        rid = fleet.submit(p, max_new_tokens=1)
        res = fleet.run_until_idle(max_ticks=50)
        np.testing.assert_array_equal(
            res[rid], _ref(model, p, 1, temperature=0.0))
        assert fleet.stats()["handoffs"] == 0
        _check_clean(fleet)


class TestRouter:
    def test_affinity_is_deterministic_and_prefix_keyed(self):
        r = FleetRouter(block_size=8, affinity=True, spill_depth=100)
        rs = np.random.RandomState(0)
        sys_p = rs.randint(0, 512, (8,)).astype(np.int32)
        group = [np.concatenate([sys_p,
                                 rs.randint(0, 512, (k,)).astype(
                                     np.int32)])
                 for k in (1, 4, 9)]
        eligible = [0, 1, 2]
        picks = {r.route(p, [0, 0, 0], eligible) for p in group}
        assert len(picks) == 1       # same first block -> same worker
        assert r.route(group[0], [0, 0, 0], eligible) == picks.pop()

    def test_spillover_diverts_from_deep_queue(self):
        r = FleetRouter(block_size=8, affinity=True, spill_depth=2)
        p = np.arange(12, dtype=np.int32)
        home = r.route(p, [0, 0], [0, 1])
        depths = [0, 0]
        depths[home] = 5             # affinity target is backlogged
        other = r.route(p, depths, [0, 1])
        assert other != home
        assert r.spillovers == 1

    def test_env_knobs_route_through_flags(self, monkeypatch):
        monkeypatch.setenv("PT_SERVING_FLEET_AFFINITY", "0")
        monkeypatch.setenv("PT_SERVING_FLEET_SPILL_DEPTH", "3")
        r = FleetRouter(block_size=8)
        assert r.affinity is False and r.spill_depth == 3
        monkeypatch.delenv("PT_SERVING_FLEET_AFFINITY")
        assert FleetRouter(block_size=8).affinity is True
        with pytest.raises(ValueError, match="spill_depth"):
            FleetRouter(block_size=8, spill_depth=0)

    def test_fleet_wide_prefix_cache_via_affinity(self, setup):
        """The shared-system-prompt workload (each group's prefix warm
        from one earlier request — the hot-tenant steady state):
        affinity lands every member of a group on the ONE prefill
        worker holding its registered blocks, so the fleet-wide burst
        hit rate matches the single-replica rate; scattering the same
        burst without affinity pays the prefix cold on the other
        worker."""
        model, cfg, pf, dc, *_ = setup
        rs = np.random.RandomState(10)
        groups, warm = [], []
        for g in range(2):
            sys_p = rs.randint(0, cfg.vocab_size, (16,)).astype(
                np.int32)
            warm.append(np.concatenate(
                [sys_p, rs.randint(0, cfg.vocab_size, (2,))
                 .astype(np.int32)]))
            groups.append([np.concatenate(
                [sys_p, rs.randint(0, cfg.vocab_size, (3 + k,))
                 .astype(np.int32)]) for k in range(3)])

        def burst_rate(submit, run, engines):
            for p in warm:                   # warm the prefix caches
                submit(p)
            run()
            pt0 = sum(e.prompt_tokens for e in engines)
            st0 = sum(e.shared_tokens for e in engines)
            rids = {g: [submit(p) for p in groups[g]] for g in (0, 1)}
            res = run()
            pt = sum(e.prompt_tokens for e in engines) - pt0
            st = sum(e.shared_tokens for e in engines) - st0
            return rids, res, st / pt

        _reset(*pf, *dc)
        fleet = _fleet(pf, dc, affinity=True, spill_depth=100)
        rids, res, fleet_rate = burst_rate(
            lambda p: fleet.submit(p, max_new_tokens=4),
            lambda: fleet.run_until_idle(max_ticks=300),
            [w.engine for w in fleet.prefill])
        for g in (0, 1):
            # rid // 1e6 encodes the owning prefill worker
            assert len({rid // 1_000_000 for rid in rids[g]}) == 1, \
                "a group split across workers"
            for rid, p in zip(rids[g], groups[g]):
                np.testing.assert_array_equal(
                    res[rid], _ref(model, p, 4, temperature=0.0))
        _check_clean(fleet)

        _reset(dc[0])                        # single-replica twin
        srv = Server(dc[0])
        _, _, single_rate = burst_rate(
            lambda p: srv.submit(p, max_new_tokens=4),
            lambda: srv.run_until_idle(), [dc[0]])

        _reset(*pf, *dc)                     # same burst, no affinity
        # prefix_cache=False: with the PR 16 fetch tier on, a scattered
        # request FETCHES the warm prefix instead of paying it cold —
        # tests/test_prefix_cache.py pins that recovery; this test pins
        # the affinity-routing claim in isolation
        off = _fleet(pf, dc, affinity=False, prefix_cache=False)
        _, _, off_rate = burst_rate(
            lambda p: off.submit(p, max_new_tokens=4),
            lambda: off.run_until_idle(max_ticks=300),
            [w.engine for w in off.prefill])

        assert fleet_rate >= single_rate - 1e-9, \
            (fleet_rate, single_rate)
        assert fleet_rate > 0.5
        assert off_rate < fleet_rate, (off_rate, fleet_rate)


class TestFleetResilience:
    def test_transport_failure_fails_explicitly_then_breaks(
            self, setup):
        """A permanently dead wire: every request ends in an explicit
        RequestFailure (handoff, then circuit_open once the breaker
        trips), the prefill side releases every slot and block, and
        nothing leaks."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        prompts = _prompts(cfg, 11, (5, 9, 12))
        fleet = _fleet([pf[0]], [dc[0]], resilience=ResilienceConfig(
            retry_attempts=1, retry_backoff_s=0.001,
            breaker_threshold=4))
        rids = [fleet.submit(p, max_new_tokens=6) for p in prompts]
        with faults.injected("fleet.transport:every=1"):
            res = fleet.run_until_idle(max_ticks=100)
        reasons = {res[r].reason for r in rids}
        assert all(isinstance(res[r], RequestFailure) for r in rids)
        assert reasons <= {"handoff", "circuit_open"}
        assert "handoff" in reasons
        assert fleet.stats()["breaker_open"]
        _check_clean(fleet)

    def test_transient_adopt_fault_is_retried_invisibly(self, setup):
        """One adopt fault with retry budget left: the payload adopts
        on the retry and the stream is bit-identical — transient
        handoff faults are semantically invisible."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        p = _prompts(cfg, 12, (9,))[0]
        fleet = _fleet([pf[0]], [dc[0]])
        rid = fleet.submit(p, max_new_tokens=6)
        with faults.injected("fleet.adopt:at=1"):
            res = fleet.run_until_idle(max_ticks=100)
        np.testing.assert_array_equal(
            res[rid], _ref(model, p, 6, temperature=0.0))
        assert fleet.stats()["handoff_retries"] >= 1
        _check_clean(fleet)

    def test_chaos_handoff_sites_hold_invariants(self, setup):
        """The satellite pin: a seeded schedule with ~1-3% faults at
        serialize/transport/adopt PLUS the PR 5 serving sites, against
        the 2x2 fleet. Every request completes-or-explicitly-fails,
        completed greedy rows are bit-identical, and BOTH sides'
        arenas account for every block."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        rs = np.random.RandomState(123)
        lens = rs.randint(4, 16, size=10)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in lens]
        news = [4 + (i % 3) * 4 for i in range(len(prompts))]
        fleet = _fleet(pf, dc, resilience=ResilienceConfig(
            retry_attempts=3, retry_backoff_s=0.001,
            breaker_threshold=16))
        rids = [fleet.submit(p, max_new_tokens=mn, arrival_step=i)
                for i, (p, mn) in enumerate(zip(prompts, news))]
        spec = ("serving.step_block:p=0.01;serving.harvest:p=0.01;"
                "serving.allocate:p=0.03;serving.prefill_tick:p=0.02;"
                "fleet.serialize:p=0.02;fleet.transport:p=0.02;"
                "fleet.adopt:p=0.02")
        with faults.injected(spec, seed=5):
            res = fleet.run_until_idle(max_ticks=500)
        for rid, p, mn in zip(rids, prompts, news):
            assert rid in res, f"request {rid} vanished"
            v = res[rid]
            if isinstance(v, RequestFailure):
                assert v.reason in ("timeout", "poisoned",
                                    "circuit_open", "shed", "handoff")
            else:
                np.testing.assert_array_equal(
                    v, _ref(model, p, mn, temperature=0.0))
        for d in fleet.decode:
            assert d.engine.decode_compile_count() == 1
        for w in fleet.prefill:
            assert w.engine.prefill_compile_count() == 1
        _check_clean(fleet)


class TestMigrationAndScale:
    def test_decode_worker_live_migration_bit_identical(
            self, setup, tmp_path, _no_compile_cache):
        """Live migration = PR 5 snapshot/restore: a decode worker
        snapshots mid-decode, a successor restores into a fresh engine
        under the same name, and every in-flight stream finishes
        bit-identical."""
        model, cfg, pf, dc, *_ = setup
        _reset(pf[0], dc[0])
        prompts = _prompts(cfg, 13, (5, 9))
        fleet = _fleet([pf[0]], [dc[0]])
        rids = [fleet.submit(p, max_new_tokens=16) for p in prompts]
        for _ in range(2):
            fleet.tick()
        assert dc[0].has_live(), "expected mid-decode state"
        fresh = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            block_size=8, prefill_chunk=8)
        fleet.migrate_decode_worker(0, fresh,
                                    str(tmp_path / "mig.npz"))
        res = fleet.run_until_idle(max_ticks=200)
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 16, temperature=0.0))
        assert fleet.stats()["migrations"] == 1
        assert fresh.decode_compile_count() == 1
        _check_clean(fleet)

    def test_add_decode_worker_scales_mid_stream(self, setup):
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        prompts = _prompts(cfg, 14, (5, 7, 9, 11))
        fleet = _fleet([pf[0]], [dc[0]])
        rids = [fleet.submit(p, max_new_tokens=8, arrival_step=i)
                for i, p in enumerate(prompts)]
        fleet.tick()
        fleet.add_decode_worker(DecodeWorker(dc[1]))
        res = fleet.run_until_idle(max_ticks=200)
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 8, temperature=0.0))
        _check_clean(fleet)

    def test_drain_prefill_worker_reroutes_then_removes(self, setup):
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        prompts = _prompts(cfg, 15, (5, 9, 12))
        fleet = _fleet(pf, dc, spill_depth=100)
        fleet.drain_prefill_worker(0)
        rids = [fleet.submit(p, max_new_tokens=4) for p in prompts]
        assert all(rid // 1_000_000 == 2 for rid in rids)
        res = fleet.run_until_idle(max_ticks=200)
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 4, temperature=0.0))
        removed = fleet.remove_prefill_worker(0)
        assert removed.engine is pf[0]
        with pytest.raises(ValueError, match="last routable"):
            fleet.drain_prefill_worker(0)

    def test_prefill_snapshot_serializes_pending_outbox(self, setup):
        """PR 20 LIFTED the un-shipped-handoff refusal: a snapshot
        taken with a parked outbox serializes every pending handoff —
        rng key, in-hand token, prompt — so a coordinated fleet
        checkpoint can land at ANY tick boundary (the whole-fleet
        crash-recovery suite pins the full round trip)."""
        model, cfg, pf, dc, *_ = setup
        _reset(pf[0], dc[0])
        w = PrefillWorker(pf[0])
        p = _prompts(cfg, 16, (6,))[0]
        w.server.submit(p, max_new_tokens=6)
        for _ in range(4):
            w.tick()
        assert pf[0]._outbox
        meta, arrays = pf[0].snapshot_state()   # no longer refused
        (ph,) = pf[0].take_handoffs()
        (e,) = meta["outbox"]
        assert e["tok0"] == ph.tok0
        assert np.array_equal(arrays["ob0_key"], np.asarray(ph.key))
        assert np.array_equal(arrays["ob0_prompt"],
                              np.asarray(ph.prompt, np.int32))
        pf[0].release_handoff(ph)
        pf[0].manager.assert_consistent()


class TestCompatAndRefusals:
    def test_mixed_fleet_and_geometry_refused(self, setup):
        model, cfg, pf, dc, (pf_d, dc_d), (pf_8, dc_8) = setup
        _reset(pf[0], dc_d, dc[0], dc_8)
        with pytest.raises(ValueError, match="mismatch"):
            _fleet([pf[0]], [dc_d])   # 16-token blocks beside 8-token
        with pytest.raises(ValueError, match="layout mismatch"):
            _fleet([pf[0]], [dc_8])   # int8 arena: different leaves

    def test_add_decode_worker_checks_compat(self, setup):
        """Scale-up runs the SAME compatibility contract as
        construction — an incompatible engine is refused at add time,
        never discovered as a failed adopt mid-stream."""
        model, cfg, pf, dc, (pf_d, dc_d), _ = setup
        _reset(pf[0], dc[0], dc_d)
        fleet = _fleet([pf[0]], [dc[0]])
        with pytest.raises(ValueError, match="mismatch"):
            fleet.add_decode_worker(DecodeWorker(dc_d))
        with pytest.raises(ValueError, match="already in the fleet"):
            fleet.add_decode_worker(DecodeWorker(dc[1],
                                                 name="decode0"))

    def test_worker_role_mismatch_refused(self, setup):
        model, cfg, pf, dc, *_ = setup
        with pytest.raises(ValueError, match="prefill-only"):
            PrefillWorker(dc[0])
        with pytest.raises(ValueError, match="decoding engine"):
            DecodeWorker(pf[0])

    def test_impossible_request_refused_at_the_door(self, setup):
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        fleet = _fleet(pf, dc)
        with pytest.raises(ValueError):
            fleet.submit(np.ones((4,), np.int32), max_new_tokens=1000)
        _check_clean(fleet)

    def test_resume_carrying_request_refused_on_prefill_worker(
            self, setup):
        from paddle_tpu.serving import Request, ResumeState
        model, cfg, pf, dc, *_ = setup
        _reset(pf[0])
        req = Request(request_id=1, prompt=np.ones((5,), np.int32),
                      max_new_tokens=8,
                      resume=ResumeState(tokens=[1, 2],
                                         key=np.zeros(2, np.uint32)))
        with pytest.raises(NotImplementedError, match="resume"):
            pf[0].try_admit(req)
