"""Tensor-parallel serving (paddle_tpu/serving/tp.py): the ONE compiled
decode block sharded over a simulated 2x4 device mesh.

The defining contract: exact-mode sharded streams — greedy AND seeded
sampling, 8- and 16-token blocks, under staggered arrivals — are BIT-IDENTICAL
to the 1-chip engine, with decode/prefill compile counts still pinned
at 1. Plus: the KV cache really shards its kv-head dim (the per-chip
HBM win), the psum-mode int8 hidden-state all-reduce exposes its
runtime-queryable error bound and refuses to run over an armed budget,
the PT_SERVING_TP env knobs route through utils.flags, and snapshot/
restore round-trips through the mesh re-commit path."""
import dataclasses

import numpy as np
import jax
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import (build_device_mesh,
                                         set_current_mesh)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                Server, TPConfig)
from paddle_tpu.serving.tp import ShardedPagedStepBackend, resolve_tp_config

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 (simulated) devices for the 2x4 mesh")


@pytest.fixture(scope="module")
def mesh():
    return build_device_mesh({"dp": 2, "mp": 4})


@pytest.fixture(scope="module")
def setup(mesh):
    """One model + the 1-chip and sharded engines, at 8-token blocks
    and chunks, for the whole file (compiled programs persist across
    reset())."""
    paddle.seed(0)
    # 8 kv heads: divisible by the full 2x4 degree so the KV arena
    # shards whole heads per device
    cfg = llama_tiny_config(num_attention_heads=8,
                            num_key_value_heads=8)
    model = LlamaForCausalLM(cfg)
    ref = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                   decode_block=4, block_size=8,
                                   prefill_chunk=8)
    tp = ContinuousBatchingEngine(
        model, num_slots=2, max_len=64, decode_block=4, block_size=8,
        prefill_chunk=8, tp=TPConfig(axes=("dp", "mp"), mesh=mesh))
    return model, cfg, ref, tp


@pytest.fixture(scope="module")
def paged_setup(setup, mesh):
    model, cfg, _, _ = setup
    ref = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                   decode_block=4, paged=True)
    tp = ContinuousBatchingEngine(
        model, num_slots=2, max_len=64, decode_block=4, paged=True,
        tp=TPConfig(axes=("dp", "mp"), mesh=mesh))
    return model, cfg, ref, tp


def _prompts(cfg, seed, lens):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
            for L in lens]


def _serve(engine, prompts, news, stagger=0, **kw):
    engine.reset()
    srv = Server(engine)
    rids = [srv.submit(p, max_new_tokens=mn,
                       arrival_step=i * stagger, **kw)
            for i, (p, mn) in enumerate(zip(prompts, news))]
    res = srv.run_until_idle()
    return [res[r] for r in rids]


class TestDenseTPParity:
    def test_greedy_staggered_bit_exact_one_compile(self, setup):
        """5 ragged greedy requests, arrivals spread over the block
        clock (retire→refill churn through 2 slots): every sharded
        stream bit-identical to the 1-chip engine, ONE compiled decode
        program on the mesh."""
        model, cfg, ref, tp = setup
        prompts = _prompts(cfg, 0, (5, 9, 12, 5, 9))
        news = [6, 4, 7, 5, 6]
        want = _serve(ref, prompts, news, stagger=2)
        got = _serve(tp, prompts, news, stagger=2)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert tp.decode_compile_count() == 1
        assert tp.tp_degree() == 8

    def test_seeded_sampling_bit_exact(self, setup):
        """Per-slot sampled rows ride the same per-request key schedule
        sharded: seeded sampling matches the 1-chip engine exactly
        (the logits the sampler sees are bit-identical, so the drawn
        tokens are too)."""
        model, cfg, ref, tp = setup
        prompts = _prompts(cfg, 1, (5, 9, 7))
        news = [6, 5, 6]
        kw = dict(temperature=0.8, top_k=40, top_p=0.9, seed=7)
        want = _serve(ref, prompts, news, **kw)
        got = _serve(tp, prompts, news, **kw)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    def test_matches_per_request_generate(self, setup):
        """Transitivity made explicit: the sharded stream equals a
        standalone generate() call, not just the 1-chip engine."""
        model, cfg, _, tp = setup
        prompts = _prompts(cfg, 2, (5, 9))
        got = _serve(tp, prompts, [5, 5])
        for p, g in zip(prompts, got):
            want = model.generate(paddle.to_tensor(p[None, :]),
                                  max_new_tokens=5,
                                  temperature=0.0).numpy()[0]
            np.testing.assert_array_equal(want, g)

    def test_kv_cache_shards_head_dim(self, setup):
        """The per-chip HBM claim: every KV pool leaf's kv-head dim is
        split 8 ways — one chip holds 1/8th of the arena."""
        model, cfg, _, tp = setup
        for leaf in tp._cache:
            shard = leaf.addressable_shards[0].data
            assert shard.shape[2] == leaf.shape[2] // 8
        # weights: column-sharded projections live split too
        q = tp.backend._pv[
            [i for i, (n, _) in enumerate(model.named_parameters())
             if "q_proj" in n][0]]
        assert q.addressable_shards[0].data.shape != q.shape

    def test_server_stats_carry_tp_degree(self, setup):
        model, cfg, ref, tp = setup
        got = _serve(tp, _prompts(cfg, 3, (5,)), [4])
        assert len(got) == 1
        tp.reset()
        srv = Server(tp)
        srv.submit(_prompts(cfg, 3, (5,))[0], max_new_tokens=4)
        srv.run_until_idle()
        assert srv.stats()["tp_degree"] == 8
        ref.reset()
        srv1 = Server(ref)
        srv1.submit(_prompts(cfg, 3, (5,))[0], max_new_tokens=4)
        srv1.run_until_idle()
        assert "tp_degree" not in srv1.stats()


class TestPagedTPParity:
    def test_greedy_staggered_bit_exact_one_compile(self, paged_setup):
        """Paged sharded streams (shared arena sharded on kv-heads,
        block tables replicated, chunked prefill under shard_map) are
        bit-identical to the 1-chip paged engine; decode AND chunk
        programs each compile once."""
        model, cfg, ref, tp = paged_setup
        prompts = _prompts(cfg, 4, (5, 9, 12, 5, 9))
        news = [6, 4, 7, 5, 6]
        want = _serve(ref, prompts, news, stagger=2)
        got = _serve(tp, prompts, news, stagger=2)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert tp.decode_compile_count() == 1
        assert tp.prefill_compile_count() == 1
        tp.manager.assert_consistent()

    def test_seeded_sampling_bit_exact(self, paged_setup):
        model, cfg, ref, tp = paged_setup
        prompts = _prompts(cfg, 5, (5, 9, 7))
        news = [6, 5, 6]
        kw = dict(temperature=0.8, top_k=40, top_p=0.9, seed=11)
        want = _serve(ref, prompts, news, **kw)
        got = _serve(tp, prompts, news, **kw)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    def test_int8_kv_arena_sharded_bit_exact(self, setup, mesh):
        """kv_int8=True under TP: the code arena AND the 3D per-(pos,
        head) scale arrays shard their kv-head dim, and because the
        absmax scales never cross heads the sharded int8 engine is
        bit-identical to the 1-chip int8 engine."""
        model, cfg, _, _ = setup
        ref = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                       decode_block=4, paged=True,
                                       kv_int8=True)
        tp = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            kv_int8=True, tp=TPConfig(axes=("dp", "mp"), mesh=mesh))
        prompts = _prompts(cfg, 13, (5, 9, 12))
        news = [6, 5, 6]
        want = _serve(ref, prompts, news, stagger=2)
        got = _serve(tp, prompts, news, stagger=2)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        # every pool leaf — 4D code arenas AND 3D scale arrays —
        # really lives split 8 ways on its kv-head dim (dim 2)
        assert any(leaf.ndim == 3 for leaf in tp._cache)
        for leaf in tp._cache:
            shard = leaf.addressable_shards[0].data
            assert shard.shape[2] == leaf.shape[2] // 8
        tp.manager.assert_consistent()

    def test_chunked_prefill_budget_bit_exact(self, paged_setup):
        """A long prompt paced by a small prefill budget crosses chunk
        boundaries under shard_map — results still bit-identical."""
        model, cfg, ref, tp = paged_setup
        rs = np.random.RandomState(6)
        long_p = rs.randint(0, cfg.vocab_size, (21,)).astype(np.int32)
        short_p = rs.randint(0, cfg.vocab_size, (5,)).astype(np.int32)

        def run(engine):
            engine.reset()
            srv = Server(engine, Scheduler(prefill_token_budget=8))
            a = srv.submit(long_p, max_new_tokens=6)
            b = srv.submit(short_p, max_new_tokens=8, arrival_step=1)
            res = srv.run_until_idle()
            return res[a], res[b]

        for w, g in zip(run(ref), run(tp)):
            np.testing.assert_array_equal(w, g)


class TestPsumInt8:
    """Megatron row-parallel mode: o_proj/down_proj partial sums
    all-reduced per layer, optionally over the EQuARX int8 wire
    format. Sums reassociate — no bit-identity claim — but streams
    must complete, the error bound must be queryable from the live
    state, and the armed budget gate must refuse over-budget runs."""

    @pytest.fixture(scope="class")
    def psum8(self, setup, mesh):
        model, cfg, _, _ = setup
        return ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4,
            tp=TPConfig(axes=("mp",), mode="psum", int8=True,
                        mesh=mesh))

    def test_stream_completes_decodes_real_tokens(self, setup, psum8):
        model, cfg, _, _ = setup
        got = _serve(psum8, _prompts(cfg, 7, (5, 9)), [6, 5])
        assert all(len(g) > 0 for g in got)
        assert psum8.decode_compile_count() == 1
        assert psum8.tp_degree() == 4

    def test_int8_bound_queryable_from_live_state(self, setup, psum8):
        model, cfg, _, _ = setup
        _serve(psum8, _prompts(cfg, 8, (5,)), [4])
        bound = psum8.tp_int8_error_bound()
        assert 0.0 < bound < 1.0
        # the probe is a separate tiny program: the decode block's
        # compile count must not have moved
        assert psum8.decode_compile_count() == 1

    def test_budget_gate_refuses_over_budget(self, setup, psum8):
        """Arming int8_max_error below the live bound must abort the
        FIRST decode block with the measured bound in the message
        (reuses the class engine's compiled programs via the backend's
        pending-gate flag — the gate is a host-side check)."""
        model, cfg, _, _ = setup
        backend = psum8.backend
        old_tp = backend.tp
        backend.tp = dataclasses.replace(old_tp, int8_max_error=1e-12)
        backend._int8_gate_pending = True
        try:
            with pytest.raises(RuntimeError, match="error bound"):
                _serve(psum8, _prompts(cfg, 9, (5,)), [4])
            # the refusal must leave the gate ARMED: re-driving the
            # engine is refused again, never silently served
            assert backend._int8_gate_pending
            with pytest.raises(RuntimeError, match="error bound"):
                _serve(psum8, _prompts(cfg, 9, (5,)), [4])
        finally:
            backend.tp = old_tp
            backend._int8_gate_pending = False

    def test_fp32_bound_is_zero(self, setup):
        model, cfg, ref, tp = setup
        assert tp.tp_int8_error_bound() == 0.0
        assert ref.tp_int8_error_bound() == 0.0


class TestSnapshotRestore:
    def test_tp_snapshot_restores_onto_mesh_bit_identical(
            self, setup, tmp_path):
        """Kill mid-stream, restore into a fresh Server over the SAME
        sharded backend: the host arrays re-commit onto the mesh
        (commit_arrays) and every stream finishes bit-identical."""
        model, cfg, _, tp = setup
        prompts = _prompts(cfg, 10, (5, 9, 12))

        def submit(srv):
            for i, p in enumerate(prompts):
                srv.submit(p, max_new_tokens=6, arrival_step=i)

        tp.reset()
        srv = Server(tp)
        submit(srv)
        ref = dict(srv.run_until_idle())

        tp.reset()
        srv_kill = Server(tp)
        submit(srv_kill)
        srv_kill.run_until_idle(max_ticks=2)
        path = str(tmp_path / "tp.npz")
        srv_kill.snapshot(path)

        eng2 = ContinuousBatchingEngine(backend=tp.backend)
        srv_new = Server.restore(path, eng2)
        res = srv_new.run_until_idle()
        for rid in ref:
            np.testing.assert_array_equal(res[rid], ref[rid])
        # restored arrays really live sharded on the mesh again
        for leaf in eng2._cache:
            assert leaf.addressable_shards[0].data.shape[2] \
                == leaf.shape[2] // 8


class TestObservability:
    def test_mesh_gauges_and_collective_accounting(self, setup):
        """With the registry armed, a served stream notes the mesh
        topology gauges and per-block collective traffic (logical
        bytes/calls, op=tp_block mode=tp_graph) — the numbers the
        serving-tp bench stage reads back every round."""
        from paddle_tpu.observability import metrics
        model, cfg, _, tp = setup
        prev = metrics.enabled()
        metrics.enable(True)
        try:
            bytes_c = metrics.counter(
                "pt_collectives_bytes_total",
                "payload bytes handed to collectives",
                labels=("op", "mode"))
            b0 = bytes_c.value(op="tp_block", mode="tp_graph")
            _serve(tp, _prompts(cfg, 12, (5, 9)), [4, 4])
            assert bytes_c.value(op="tp_block",
                                 mode="tp_graph") > b0
            assert metrics.gauge(
                "pt_serving_tp_devices",
                "devices the serving decode block is sharded over "
                "(1 = TP off)").value() == 8
            ax = metrics.gauge(
                "pt_serving_tp_mesh_axis_size",
                "mesh axis sizes of the serving TP mesh",
                labels=("axis",))
            assert ax.value(axis="dp") == 2
            assert ax.value(axis="mp") == 4
        finally:
            metrics.enable(prev)


class TestEnvFlagsAndValidation:
    def test_env_knobs_route_through_flags(self, monkeypatch):
        monkeypatch.setenv("PT_SERVING_TP", "1")
        monkeypatch.setenv("PT_SERVING_TP_AXES", " dp , mp ")
        monkeypatch.setenv("PT_SERVING_TP_MODE", "psum")
        monkeypatch.setenv("PT_SERVING_TP_INT8", "1")
        cfg = resolve_tp_config(None)
        assert cfg.axes == ("dp", "mp")
        assert cfg.mode == "psum" and cfg.int8

    def test_env_off_means_off(self, monkeypatch):
        monkeypatch.delenv("PT_SERVING_TP", raising=False)
        assert resolve_tp_config(None) is None
        assert resolve_tp_config(False) is None
        assert resolve_tp_config(True) == TPConfig()

    def test_env_flag_constructs_sharded_backend(self, setup, mesh,
                                                 monkeypatch):
        """PT_SERVING_TP=1 + the process-current mesh routes a plain
        engine construction to the sharded backend (jits are lazy —
        construction itself compiles nothing)."""
        model, cfg, _, _ = setup
        monkeypatch.setenv("PT_SERVING_TP", "1")
        monkeypatch.setenv("PT_SERVING_TP_AXES", "mp")
        set_current_mesh(mesh)
        try:
            eng = ContinuousBatchingEngine(model, num_slots=1,
                                           max_len=32, decode_block=2)
            assert isinstance(eng.backend, ShardedPagedStepBackend)
            assert eng.tp_degree() == 4
        finally:
            set_current_mesh(None)

    def test_explicit_backend_never_rerouted(self, setup, monkeypatch):
        model, cfg, ref, _ = setup
        monkeypatch.setenv("PT_SERVING_TP", "1")
        eng = ContinuousBatchingEngine(backend=ref.backend)
        assert not isinstance(eng.backend, ShardedPagedStepBackend)
        assert eng.tp_degree() == 1

    def test_config_validation(self, setup, mesh):
        model, cfg, _, _ = setup
        with pytest.raises(ValueError, match="expected"):
            TPConfig(mode="fast")
        with pytest.raises(ValueError, match="psum"):
            TPConfig(int8=True)           # exact mode has no reduction
        with pytest.raises(ValueError, match="needs a mesh"):
            set_current_mesh(None)
            ContinuousBatchingEngine(model, num_slots=1, max_len=32,
                                     decode_block=2, tp=TPConfig())
        with pytest.raises(ValueError, match="not in mesh"):
            ContinuousBatchingEngine(
                model, num_slots=1, max_len=32, decode_block=2,
                tp=TPConfig(axes=("nope",), mesh=mesh))
        with pytest.raises(ValueError, match="nothing to shard"):
            ContinuousBatchingEngine(
                model, num_slots=1, max_len=32, decode_block=2,
                tp=TPConfig(axes=("pp",), mesh=mesh))

    def test_indivisible_heads_rejected(self, mesh):
        paddle.seed(1)
        m4 = LlamaForCausalLM(llama_tiny_config())   # 4 heads
        with pytest.raises(ValueError, match="divisible"):
            ContinuousBatchingEngine(
                m4, num_slots=1, max_len=32, decode_block=2,
                tp=TPConfig(axes=("dp", "mp"), mesh=mesh))

    def test_model_without_specs_rejected(self, mesh):
        paddle.seed(1)
        m = LlamaForCausalLM(llama_tiny_config(tensor_parallel=False))
        with pytest.raises(ValueError, match="partition specs"):
            ContinuousBatchingEngine(
                m, num_slots=1, max_len=32, decode_block=2,
                tp=TPConfig(axes=("mp",), mesh=mesh))
