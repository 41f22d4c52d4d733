"""Paged-KV serving engine (serving/paging.py): paged greedy streams
bit-identical to generate(), chunked-vs-whole prefill
equivalence, ref-counted prefix sharing (release on eos, no
double-free, hash-collision fallback), int8 KV error inside the
runtime-queryable bound, and the static-shape invariant (ONE decode
program + ONE chunk-prefill program across everything)."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paged_walk_cases import BS, WALK_CASES, chunk_rows, walk_inputs

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (BlockManager, ContinuousBatchingEngine,
                                Request, Scheduler, Server)


_LIVE_MANAGERS = []      # every BlockManager the module's tests built


@pytest.fixture(scope="module")
def paged_setup():
    """One model + one paged engine for the whole file (reset() frees
    slots/blocks, never the two compiled programs). Constructed through
    ContinuousBatchingEngine(paged=True), as the benchmark builds it, so
    the factory routing is on the tested path."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    engine = ContinuousBatchingEngine(
        model, num_slots=2, max_len=64, decode_block=4, paged=True,
        block_size=8, prefill_chunk=8)
    assert type(engine) is ContinuousBatchingEngine
    _LIVE_MANAGERS.append(engine.manager)
    return model, cfg, engine


@pytest.fixture(autouse=True)
def _arena_invariants():
    """Teardown for EVERY test in this file: the arena accounting
    invariants must hold after each stream (PR-5 satellite — a leak
    caught here names the test that caused it, not a later victim)."""
    yield
    for m in _LIVE_MANAGERS:
        m.assert_consistent()


def _ref(model, prompt, max_new, **kw):
    return model.generate(paddle.to_tensor(prompt[None, :]),
                          max_new_tokens=max_new, **kw).numpy()[0]


class TestPagedBitExactness:
    def test_greedy_ragged_stream_bit_exact_one_compile(self,
                                                        paged_setup):
        """5 ragged greedy requests through 2 paged slots: every output
        bit-identical to standalone generate(); exactly ONE decode
        program and ONE chunk-prefill program compiled."""
        model, cfg, engine = paged_setup
        engine.reset()
        rs = np.random.RandomState(0)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 9, 12, 5, 9)]
        news = [6, 4, 7, 5, 6]
        srv = Server(engine, Scheduler(prefill_token_budget=8))
        rids = [srv.submit(p, max_new_tokens=mn)
                for p, mn in zip(prompts, news)]
        res = srv.run_until_idle()
        for rid, p, mn in zip(rids, prompts, news):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, mn, temperature=0.0))
        assert engine.decode_compile_count() == 1
        assert engine.prefill_compile_count() == 1
        stats = srv.stats()
        assert stats["requests_completed"] == 5
        assert stats["ttft_p95_s"] >= stats["ttft_p50_s"] > 0.0

    def test_chunked_equals_whole_prefill(self, paged_setup):
        """A 21-token prompt prefilled in 8-token chunks under a tiny
        per-tick budget (interleaved with another request's decode)
        equals the unbudgeted whole-prompt path AND generate()."""
        model, cfg, engine = paged_setup
        engine.reset()
        rs = np.random.RandomState(7)
        long_p = rs.randint(0, cfg.vocab_size, (21,)).astype(np.int32)
        short_p = rs.randint(0, cfg.vocab_size, (4,)).astype(np.int32)

        def run(budget):
            engine.reset()
            srv = Server(engine,
                         Scheduler(prefill_token_budget=budget))
            r0 = srv.submit(short_p, max_new_tokens=10)
            r1 = srv.submit(long_p, max_new_tokens=6, arrival_step=1)
            res = srv.run_until_idle()
            return res[r0], res[r1]

        chunked = run(8)
        whole = run(None)
        np.testing.assert_array_equal(chunked[0], whole[0])
        np.testing.assert_array_equal(chunked[1], whole[1])
        np.testing.assert_array_equal(
            chunked[1], _ref(model, long_p, 6, temperature=0.0))
        assert engine.decode_compile_count() == 1
        assert engine.prefill_compile_count() == 1

    def test_sampled_row_matches_generate_seed(self, paged_setup):
        """Sampled traffic follows generate(seed)'s key schedule
        through chunked prefill + paged decode."""
        model, cfg, engine = paged_setup
        engine.reset()
        rs = np.random.RandomState(2)
        p = rs.randint(0, cfg.vocab_size, (9,)).astype(np.int32)
        srv = Server(engine)
        rid = srv.submit(p, max_new_tokens=6, temperature=1.0,
                         top_k=50, seed=7)
        res = srv.run_until_idle()
        np.testing.assert_array_equal(
            res[rid], _ref(model, p, 6, do_sample=True, temperature=1.0,
                           top_k=50, seed=7))

    def test_eos_retirement_releases_blocks(self, paged_setup):
        """A request retiring early on eos releases every arena block
        it held (free+cached back to full) and still matches
        generate()'s eos-padded static shape."""
        model, cfg, engine = paged_setup
        engine.reset()
        rs = np.random.RandomState(4)
        p = rs.randint(0, cfg.vocab_size, (5,)).astype(np.int32)
        free0 = engine.manager.available()
        ref_free = _ref(model, p, 16, temperature=0.0,
                        use_scan_decode=False)
        eos = int(ref_free[len(p) + 1])
        srv = Server(engine)
        rid = srv.submit(p, max_new_tokens=16, eos_token_id=eos)
        res = srv.run_until_idle()
        np.testing.assert_array_equal(
            res[rid], _ref(model, p, 16, temperature=0.0,
                           eos_token_id=eos))
        assert engine.manager.available() == free0
        assert not engine.manager._ref     # no block left referenced


class TestPrefixSharing:
    def test_hits_refcounts_and_retention(self, paged_setup):
        """Two concurrent same-prefix requests share the prefix blocks
        (refcount 2 while both live); after retirement the blocks park
        in the LRU cache and a LATER request still hits them."""
        model, cfg, engine = paged_setup
        engine.reset()
        rs = np.random.RandomState(1)
        prefix = rs.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
        tails = [rs.randint(0, cfg.vocab_size, (5,)).astype(np.int32)
                 for _ in range(3)]
        prompts = [np.concatenate([prefix, t]) for t in tails]
        srv = Server(engine)
        # r1 arrives AFTER r0's prefill tick, so r0's registered prefix
        # blocks are matchable (same-tick admissions can't share yet —
        # registration happens at prefill completion)
        r0 = srv.submit(prompts[0], max_new_tokens=5)
        r1 = srv.submit(prompts[1], max_new_tokens=5, arrival_step=2)
        res = srv.run_until_idle()
        for rid, p in zip((r0, r1), prompts[:2]):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 5, temperature=0.0))
        assert engine.shared_tokens == 16      # request 2 skipped 2 blocks
        assert len(engine.manager._cached) >= 2   # retained, refcount 0
        srv2 = Server(engine)                  # no reset: cache persists
        r2 = srv2.submit(prompts[2], max_new_tokens=5)
        res2 = srv2.run_until_idle()
        np.testing.assert_array_equal(
            res2[r2], _ref(model, prompts[2], 5, temperature=0.0))
        assert engine.shared_tokens == 32      # 3rd request hit the cache
        assert engine.prefix_cache_hit_rate() > 0.0

    def test_concurrent_refcount_two(self, paged_setup):
        """Mid-flight, a shared prefix block's refcount is exactly 2
        and it is absent from the LRU cache (un-evictable)."""
        model, cfg, engine = paged_setup
        engine.reset()
        rs = np.random.RandomState(3)
        prefix = rs.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
        p0 = np.concatenate([prefix, rs.randint(
            0, cfg.vocab_size, (3,)).astype(np.int32)])
        p1 = np.concatenate([prefix, rs.randint(
            0, cfg.vocab_size, (4,)).astype(np.int32)])
        engine.try_admit(Request(request_id=0, prompt=p0,
                                 max_new_tokens=4))
        engine.prefill_tick(None)              # fills + registers p0
        engine.try_admit(Request(request_id=1, prompt=p1,
                                 max_new_tokens=4))
        shared = engine.manager.match_prefix(p1)   # 3rd acquire
        assert len(shared) == 2
        assert all(engine.manager._ref[b] == 3 for b in shared)
        engine.manager.release(shared)
        assert all(engine.manager._ref[b] == 2 for b in shared)
        engine.prefill_tick(None)
        while engine.has_live():
            engine.step_block()
        engine.drain_finished()
        assert not engine.manager._ref

    def test_decode_time_block_sharing_extends_the_chain(
            self, paged_setup):
        """A COMPLETED stream registers every fully-written block of
        prompt + generated history — decode positions included — so a
        follow-up that quotes the generated text shares blocks the
        prompt alone never covered (the multi-turn steady state: turn
        N+1's prompt is turn N's transcript)."""
        model, cfg, engine = paged_setup
        engine.reset()
        rs = np.random.RandomState(9)
        p0 = rs.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
        srv = Server(engine)
        r0 = srv.submit(p0, max_new_tokens=12)
        seq = srv.run_until_idle()[r0]
        np.testing.assert_array_equal(
            seq, _ref(model, p0, 12, temperature=0.0))
        # the prompt alone covers 1 shareable block ((12-1)//8); the
        # completed 24-token sequence registered 2 ((24-1)//8) — the
        # 2nd block holds 4 DECODE positions (12..15)
        assert max(engine.manager.registered_chains().values()) == 2
        st0 = engine.shared_tokens
        p1 = np.concatenate([seq[:20].astype(np.int32),
                             rs.randint(0, cfg.vocab_size, (4,))
                             .astype(np.int32)])
        r1 = srv.submit(p1, max_new_tokens=4)
        np.testing.assert_array_equal(
            srv.run_until_idle()[r1],
            _ref(model, p1, 4, temperature=0.0))
        assert engine.shared_tokens - st0 == 16   # both blocks hit
        assert not engine.manager._ref

    def test_hash_collision_falls_back_to_recompute(self, paged_setup):
        """A degenerate hash (every block collides) must never share
        mismatched blocks: the stored-token comparison rejects the hit
        and the stream stays bit-identical, with zero shared tokens."""
        model, cfg, engine = paged_setup
        backend = engine.backend
        bad = ContinuousBatchingEngine(
            backend=backend, hash_fn=lambda parent, toks: b"collide")
        _LIVE_MANAGERS.append(bad.manager)
        rs = np.random.RandomState(5)
        pa = rs.randint(0, cfg.vocab_size, (17,)).astype(np.int32)
        pb = rs.randint(0, cfg.vocab_size, (17,)).astype(np.int32)
        srv = Server(bad)
        ra = srv.submit(pa, max_new_tokens=4)
        rb = srv.submit(pb, max_new_tokens=4)
        res = srv.run_until_idle()
        np.testing.assert_array_equal(
            res[ra], _ref(model, pa, 4, temperature=0.0))
        np.testing.assert_array_equal(
            res[rb], _ref(model, pb, 4, temperature=0.0))
        assert bad.shared_tokens == 0          # collision never shared

    def test_tight_pool_requeue_and_block_reuse(self, paged_setup):
        """A pool too small for two concurrent requests defers the
        second (Server re-queues) and re-uses the first's freed blocks
        — outputs still bit-identical, no corruption from the dead
        slot's trash-redirected writes."""
        model, cfg, engine = paged_setup
        tight = ContinuousBatchingEngine(backend=engine.backend)
        # shrink the usable pool via a fresh manager over fewer blocks
        tight.manager = BlockManager(6, tight.kv_block_size)
        tight.num_kv_blocks = 6
        tight.reset()
        _LIVE_MANAGERS.append(tight.manager)
        rs = np.random.RandomState(6)
        prompts = [rs.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
                   for _ in range(3)]
        srv = Server(tight)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        res = srv.run_until_idle()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 6, temperature=0.0))


class TestBlockManager:
    def test_double_free_guard(self):
        m = BlockManager(8, 4)
        blocks = m.allocate(3)
        m.release(blocks)
        with pytest.raises(RuntimeError, match="double free"):
            m.release(blocks)
        m.assert_consistent()

    def test_lru_eviction_of_cached_prefixes(self):
        m = BlockManager(4, 2)           # 3 usable blocks
        prompt = np.asarray([1, 2, 3, 4, 5], np.int32)  # 2 full blocks
        held = m.allocate(3)
        m.register_prefix(prompt, held)
        m.release(held)                  # 2 registered -> cached, 1 free
        assert m.available() == 3
        assert len(m._cached) == 2
        again = m.match_prefix(prompt)
        assert len(again) == 2           # cache hit after release
        m.release(again)
        got = m.allocate(3)              # forces evicting both cached
        assert sorted(got) == sorted(held)
        assert m.match_prefix(prompt) == []   # index emptied by evict
        m.release(got)
        m.assert_consistent()

    def test_allocate_refuses_oversubscription(self):
        m = BlockManager(4, 2)
        assert m.allocate(4) is None     # only 3 usable (trash block)
        held = m.allocate(3)
        assert m.allocate(1) is None
        m.release(held)
        assert m.allocate(1) is not None
        m.release([b for b in m._ref])
        m.assert_consistent()


class TestInt8KV:
    def test_write_path_error_within_runtime_bound(self):
        """Measured dequant error of K/V written through the paged int8
        path vs the fp32 values, elementwise under the per-vector bound
        AND under the engine-style global bound from the max scale."""
        from paddle_tpu.models.generation import cached_attention
        from paddle_tpu.ops.pallas.paged_attention import (
            dequantize_kv, kv_int8_error_bound)
        rs = np.random.RandomState(0)
        b, s, h, kvh, d = 2, 4, 4, 2, 16
        nb, bs = 6, 4
        q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
        kv = jnp.asarray(3 * rs.randn(b, s, kvh, d).astype(np.float32))
        vv = jnp.asarray(rs.randn(b, s, kvh, d).astype(np.float32))
        ck = jnp.zeros((nb, bs, kvh, d), jnp.int8)
        cv = jnp.zeros((nb, bs, kvh, d), jnp.int8)
        sk = jnp.zeros((nb, bs, kvh), jnp.float32)
        sv = jnp.zeros((nb, bs, kvh), jnp.float32)
        tbl = jnp.asarray([[1, 2, 0], [3, 4, 0]], np.int32)
        pos = jnp.asarray([0, 4], jnp.int32)
        out = cached_attention(q, kv, vv, ck, cv, pos,
                               scale=d ** -0.5, block_table=tbl,
                               kv_scales=(sk, sv))
        _, nck, ncv, nsk, nsv = out
        for r in range(b):
            for i in range(s):
                t = int(pos[r]) + i
                blk = int(tbl[r, t // bs])
                off = t % bs
                deq = dequantize_kv(nck[blk, off], nsk[blk, off])
                err = np.abs(np.asarray(deq) - np.asarray(kv[r, i]))
                bound = np.asarray(kv_int8_error_bound(
                    nsk[blk, off]))[:, None]
                assert (err <= bound + 1e-7).all()
        global_bound = float(kv_int8_error_bound(jnp.max(nsk)))
        assert global_bound >= float(np.asarray(kv_int8_error_bound(
            nsk)).max())            # engine-style query dominates

    def test_constant_vectors_round_trip_exactly(self):
        from paddle_tpu.ops.pallas.paged_attention import (
            dequantize_kv, quantize_kv)
        x = jnp.full((3, 2, 16), -2.75, jnp.float32)
        c, s = quantize_kv(x)
        np.testing.assert_array_equal(np.asarray(dequantize_kv(c, s)),
                                      np.asarray(x))

    def test_int8_engine_stream_and_queryable_bound(self, paged_setup):
        """The int8 engine serves a greedy stream (compile counts stay
        1+1), reports a positive runtime bound, and its KV HBM per slot
        is ~3.6x below the fp32 arena's."""
        model, cfg, engine = paged_setup
        e8 = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            block_size=8, prefill_chunk=8, kv_int8=True)
        _LIVE_MANAGERS.append(e8.manager)
        rs = np.random.RandomState(8)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 9)]
        srv = Server(e8)
        rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
        res = srv.run_until_idle()
        assert len(res) == 2
        for rid, p in zip(rids, prompts):
            assert res[rid].shape == (len(p) + 5,)
        assert e8.decode_compile_count() == 1
        assert e8.prefill_compile_count() == 1
        assert 0.0 < e8.kv_error_bound() < 1.0
        assert engine.backend.kv_bytes_per_slot() \
            > 3 * e8.backend.kv_bytes_per_slot()


class TestPagedKernel:
    @pytest.mark.parametrize("case", sorted(WALK_CASES))
    def test_interpret_kernel_matches_reference(self, case, monkeypatch):
        """The Pallas paged-attention kernel (interpret mode on CPU)
        matches the gathered-dense reference at every edge of its walk
        over a slot's live pages (tests/paged_walk_cases.py), GQA heads
        and dead slots included."""
        pytest.importorskip("jax.experimental.pallas")
        import paddle_tpu.ops.pallas.fused as fused
        from paddle_tpu.ops.pallas import paged_attention as pa
        monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
        monkeypatch.setattr(pa, "_CHUNK_ROWS", chunk_rows())
        q, ka, va, tbl, lens = walk_inputs(case)
        assert pa._kernel_ok(ka)
        out = pa.paged_attention_decode(q, ka, va, tbl, lens,
                                        scale=q.shape[-1] ** -0.5)
        ref = pa.paged_attention_reference(
            q[:, None], ka, va, tbl, lens, scale=q.shape[-1] ** -0.5)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_another_slots_nan_never_leaks_through_a_chunk_buffer(
            self, monkeypatch):
        """Pages past a slot's last live one are not copied, so their
        place in the chunk buffer still holds what an earlier chunk left
        there. A poisoned slot's NaN pages must not reach the next slot
        through it (the quarantine's blast radius is one slot): slot 0's
        five pages are NaN, and slot 1's one live page lands in the buffer
        that last held two of them."""
        import paddle_tpu.ops.pallas.fused as fused
        from paddle_tpu.ops.pallas import paged_attention as pa
        monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
        monkeypatch.setattr(pa, "_CHUNK_ROWS", chunk_rows())
        q, ka, va, _, _ = walk_inputs("full_table_width_not_multiple_of_chunk")
        tbl = jnp.asarray([[1, 2, 3, 4, 5], [6, 0, 0, 0, 0]], jnp.int32)
        lens = jnp.asarray([5 * BS, 3], jnp.int32)
        ka = ka.at[1:6].set(jnp.nan)
        va = va.at[1:6].set(jnp.nan)
        out = np.asarray(pa.paged_attention_decode(q, ka, va, tbl, lens,
                                                   scale=0.25))
        ref = np.asarray(pa.paged_attention_reference(
            q[:, None], ka, va, tbl, lens, scale=0.25)[:, 0])
        assert np.isnan(out[0]).all() and np.isfinite(out[1]).all()
        np.testing.assert_allclose(out[1], ref[1], atol=1e-5)

    def test_bf16_arena_is_read_as_stored(self, monkeypatch):
        """A bf16 arena under a bf16 q: the products take bf16 operands
        and accumulate in fp32, as the oracle's cast-then-multiply."""
        import paddle_tpu.ops.pallas.fused as fused
        from paddle_tpu.ops.pallas import paged_attention as pa
        monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
        monkeypatch.setattr(pa, "_CHUNK_ROWS", chunk_rows())
        q, ka, va, tbl, lens = (
            a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
            for a in walk_inputs("all_edges_mixed_with_dead_slot"))
        out = pa.paged_attention_decode(q, ka, va, tbl, lens, scale=0.25)
        ref = pa.paged_attention_reference(
            q[:, None], ka, va, tbl, lens, scale=0.25)[:, 0]
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=2e-2)

    def test_walk_counts_matches_a_plain_loop(self):
        from paddle_tpu.ops.pallas.paged_attention import walk_counts
        rs = np.random.RandomState(5)
        for mb, bs, ppc in ((5, 8, 2), (256, 16, 16), (3, 4, 1), (7, 2, 3)):
            lengths = rs.randint(0, mb * bs + 2 * bs, (4, 9))
            live = copied = chunks = 0
            for n in lengths.reshape(-1):
                pages = -(-min(int(n), mb * bs) // bs)
                live += pages
                copied += max(pages, 1)      # the walk never reads nothing
                chunks += -(-max(pages, 1) // ppc)
            assert walk_counts(lengths, mb, bs, ppc) \
                == (live, copied, chunks)
        assert walk_counts([563] * 32, 256, 16)[:2] == (36 * 32, 36 * 32)

    def test_shapes_the_walk_cannot_tile_take_the_reference(self,
                                                            monkeypatch):
        """On a TPU the kernel reads a page as a (bs * kvh, d) matrix: a
        head_dim that is not whole 128-lane rows, kv heads that do not
        fill 8 sublanes, an int8 scale page that is not whole lane rows,
        or a latent row that is not whole lane tiles would be re-laid out
        arena-wide on every call, so they route to the gathered reference
        (int8: the per-block scan) instead."""
        import paddle_tpu.ops.pallas.fused as fused
        from paddle_tpu.ops.pallas import paged_attention as pa
        monkeypatch.setattr(fused, "_on_tpu", lambda: True)
        ok = lambda shape, dt=jnp.bfloat16: pa._kernel_ok(   # noqa: E731
            jax.ShapeDtypeStruct(shape, dt))
        assert ok((5121, 16, 8, 128))                  # the served shape
        assert ok((1025, 16, 32, 128), jnp.float32)    # Llama-2-7B heads
        assert not ok((64, 16, 8, 64))                 # head_dim 64
        assert not ok((64, 16, 4, 128))                # 4 kv heads
        assert not ok((64, 16, 8, 128), jnp.float16)   # dtype, as before
        # a latent arena (nb, bs, w): one row a token for all heads, its
        # page the (bs, w) matrix it is in memory
        assert ok((16385, 16, 640))                    # the served shape
        assert not ok((64, 16, 576))                   # 4.5 lane tiles
        assert not ok((64, 8, 640))                    # half a bf16 group
        ok8 = lambda shape: pa._kernel_ok_int8(        # noqa: E731
            jax.ShapeDtypeStruct(shape, jnp.int8))
        assert ok8((5121, 16, 8, 128))
        assert not ok8((64, 8, 8, 128))                # 64-wide scale page
        assert not ok8((64, 16, 8, 64))

    def test_kernel_not_dispatched_on_cpu(self):
        """Without TPU or forced interpret, the paged read must take
        the reference path (the bit-identity lane)."""
        import paddle_tpu.ops.pallas.fused as fused
        from paddle_tpu.ops.pallas.paged_attention import _kernel_ok
        if jax.default_backend() == "cpu" and not fused._FORCE_INTERPRET:
            assert not _kernel_ok(jnp.zeros((2, 4, 2, 8), jnp.float32))


# The walk's run copies: an aligned group of 8 table columns that names
# consecutive blocks, rising or falling, moves as one copy. Block size 4, a
# 40-column table (five groups), 16 pages a chunk (two groups): each slot's
# row holds runs, broken groups or both, and its length ends mid-page,
# mid-group, on a group's end, past the table, or reads a dead slot's
# zeroed row.
RUN_BS, RUN_MB, RUN_NB = 4, 40, 260


def _run_table_and_lengths():
    rising = np.arange(1, 41)
    falling = np.arange(200, 160, -1)
    broken = np.concatenate([
        np.arange(41, 49),                       # rising
        [49, 50, 52, 51, 53, 54, 55, 56],        # two swapped
        np.arange(64, 56, -1),                   # falling
        [90, 91, 92, 93, 80, 81, 82, 83],        # a run off the alignment
        [84, 85, 86, 87, 88, 89, 94, 95]])
    rows = [(rising, 3 * 32 + 2 * RUN_BS + 1),   # mid-page of group 3
            (falling, 3 * 32),                   # on group 2's end
            (falling - 50, 4 * 32 - 2),          # group 3's last page short
            (broken, 4 * 32 + 5 * RUN_BS + 2),   # mid-group, mid-page
            (np.zeros(RUN_MB), 37),              # a dead slot
            (rising + 200, 1),
            (falling, RUN_MB * RUN_BS + 9),      # past the table: clamped
            (falling - 100, 32),                 # one group, exactly
            (rising, 20)]                        # mid-group 0: no run
    tbl = np.stack([r for r, _ in rows]).astype(np.int32)
    return jnp.asarray(tbl), jnp.asarray([n for _, n in rows], jnp.int32)


def _run_case(kind, rs):
    """(kernel output, reference output, arena page shapes) on the run
    tables, fp32 throughout."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    tbl, lens = _run_table_and_lengths()
    if kind.startswith("swa"):
        # a windowed walk clamps a length past its table, as the engine
        # never asks; its reference does not
        lens = jnp.minimum(lens, RUN_MB * RUN_BS)
    b, h, kvh, d, w, rank = tbl.shape[0], 4, 2, 16, 32, 16

    def rnd(*shape):
        return jnp.asarray(rs.randn(*shape).astype(np.float32))

    if kind in ("mla", "swa_mla"):
        q, arena = rnd(b, h, w), rnd(RUN_NB, RUN_BS, w)
        window = 13 if kind == "swa_mla" else None
        fn = pa.mla_paged_attention_decode if window is None else \
            functools.partial(pa.swa_mla_paged_attention_decode,
                              window=window)
        out = fn(q, arena, tbl, lens, scale=0.2, rank=rank)
        ref = pa.mla_paged_attention_reference(
            q[:, None], arena, tbl, lens, scale=0.2, rank=rank,
            window=window)[:, 0]
        return out, ref, [(arena.shape[1:], arena.dtype)]
    if kind in ("packed", "swa_packed"):
        q, arena = rnd(b, h, w), rnd(RUN_NB, RUN_BS * kvh, w)
        kw = dict(scale=0.2, kvh=kvh, dv=8)
        if kind == "packed":
            out = pa.packed_paged_attention_decode(q, arena, tbl, lens, **kw)
            sinks = window = None
        else:
            sinks, window = rnd(h), 13
            out = pa.swa_paged_attention_decode(q, arena, tbl, lens, sinks,
                                                window=window, **kw)
        ref = pa.packed_paged_attention_reference(
            q[:, None], arena, tbl, lens, window=window, sinks=sinks,
            **kw)[:, 0]
        return out, ref, [(arena.shape[1:], arena.dtype)]
    q = rnd(b, h, d)
    ka, va = rnd(RUN_NB, RUN_BS, kvh, d), rnd(RUN_NB, RUN_BS, kvh, d)
    if kind == "paged":
        out = pa.paged_attention_decode(q, ka, va, tbl, lens, scale=0.25)
        ref = pa.paged_attention_reference(q[:, None], ka, va, tbl, lens,
                                           scale=0.25)[:, 0]
        arenas = (ka, va)
    else:
        (kc, ks), (vc, vs) = pa.quantize_kv(ka), pa.quantize_kv(va)
        out = pa.paged_attention_decode_int8(q, kc, vc, ks, vs, tbl, lens,
                                             scale=0.25)
        ref = pa.paged_attention_int8_reference(
            q[:, None], kc, vc, ks, vs, tbl, lens, scale=0.25)[:, 0]
        arenas = (kc, vc, ks, vs)
    return out, ref, [(pa._page_view(a.shape)[1:], a.dtype) for a in arenas]


class TestWalkRuns:
    @pytest.mark.parametrize("kind", ["mla", "paged", "packed", "int8",
                                      "swa_mla", "swa_packed"])
    def test_walk_matches_reference_over_runs(self, kind, monkeypatch):
        """Every walk (interpret mode) matches its gathered reference on
        tables that hold rising, falling and broken groups: the four walks
        without a window move each marked group as ONE copy (a falling
        one lands in reverse), the windowed two page by page as before."""
        pytest.importorskip("jax.experimental.pallas")
        import paddle_tpu.ops.pallas.fused as fused
        from paddle_tpu.ops.pallas import paged_attention as pa
        monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
        # 16 pages a chunk: two groups, three chunks over the table
        page_rows = RUN_BS * (1 if kind in ("mla", "swa_mla") else 2)
        monkeypatch.setattr(pa, "_CHUNK_ROWS", 16 * page_rows)
        out, ref, pages = _run_case(kind, np.random.RandomState(3))
        windowed = kind.startswith("swa")
        ppc, run = pa._walk_chunk(RUN_MB, pages,
                                  13 if windowed else None)
        assert run == (1 if windowed else pa._RUN)
        if not windowed:
            assert ppc == 16
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_the_tables_hold_every_kind_of_group(self):
        """The run tables above mark rising (+1), falling (-1) and
        page-by-page (0) groups; a group ending in a partly written page
        is no run under ``whole`` (the softmax walk's rule) and is one
        under the indexer's."""
        from paddle_tpu.ops.pallas import paged_attention as pa
        tbl, lens = _run_table_and_lengths()
        ways = np.asarray(pa._run_directions(tbl, lens, RUN_BS, pa._RUN,
                                              whole=True))
        assert ways.tolist() == [
            [1, 1, 1, 0, 0], [-1, -1, -1, 0, 0], [-1, -1, -1, 0, 0],
            [1, 0, -1, 0, 0],
            [0] * 5, [0] * 5, [-1] * 5, [-1, 0, 0, 0, 0], [0] * 5]
        # 126 rows: 32 pages, the last of them two rows short
        short = lens.at[0].set(4 * 32 - 2)
        assert [np.asarray(pa._run_directions(tbl, short, RUN_BS, pa._RUN,
                                              whole=whole))[0].tolist()
                for whole in (True, False)] \
            == [[1, 1, 1, 0, 0], [1, 1, 1, 1, 0]]

    def test_run_pages_match_a_plain_loop(self):
        """``run_groups`` + ``walk_run_pages`` (the engine's counter)
        count what a plain loop over each row and length finds: ``_RUN``
        pages for every aligned group of consecutive blocks whose rows all
        lie below the length; and they agree with ``_run_directions``."""
        from paddle_tpu.ops.pallas import paged_attention as pa
        rs = np.random.RandomState(11)
        for mb, bs in ((40, 4), (37, 2), (7, 8), (320, 16)):
            tbl = rs.randint(1, 500, (9, mb)).astype(np.int32)
            for i in range(0, 9, 2):             # some rows in runs
                start = rs.randint(1, 600)
                tbl[i] = start + np.arange(mb) * (1 if i % 4 else -1) \
                    + 1000
            seg = tbl[3, 8:24]                   # two runs in a row
            seg[:] = 5000 + np.arange(seg.size)
            lengths = rs.randint(0, mb * bs + 3 * bs, (4, 9))
            want = 0
            for ln in lengths:
                for row, n in zip(tbl, ln):
                    for g in range(mb // pa._RUN):
                        cols = row[g * pa._RUN:(g + 1) * pa._RUN]
                        steps = np.diff(cols.astype(np.int64))
                        if ((steps == 1).all() or (steps == -1).all()) \
                                and (g + 1) * pa._RUN * bs <= min(n, mb * bs):
                            want += pa._RUN
            groups = pa.run_groups(tbl)
            assert pa.walk_run_pages(groups, lengths, bs) == want
            ways = np.stack([np.asarray(pa._run_directions(
                jnp.asarray(tbl), jnp.asarray(ln, jnp.int32), bs, pa._RUN,
                whole=True)) for ln in lengths])
            assert pa._RUN * int((ways != 0).sum()) == want

    def test_chunks_hold_whole_groups_where_a_walk_takes_runs(self):
        """A walk without a window whose chunk holds at least ``_RUN``
        pages cuts it to whole groups (no group straddles two chunks), at
        every served page shape; a windowed walk, or a table under a
        group, copies page by page at the chunk it had; ``walk_counts``
        counts the chunks of that ``ppc``."""
        from paddle_tpu.ops.pallas import paged_attention as pa
        bf, i8, f32 = jnp.bfloat16, jnp.int8, jnp.float32
        served = {                               # (mb, pages): ppc
            (320, (((16, 640), bf),)): 96,       # the latent arena
            (256, (((128, 128), bf),) * 2): 16,  # 8 KV heads, K and V
            (256, (((128, 128), i8),) * 2
             + (((1, 128), f32),) * 2): 16,      # the int8 walk
            (64, (((256, 128), bf),) * 2): 8,    # 16 KV heads
            (512, (((64, 384), bf),)): 32,       # a packed arena, 4 heads
            (640, (((128, 256), bf),)): 16}      # a packed arena, 8 heads
        for (mb, pages), ppc in served.items():
            pages = list(pages)
            assert pa._walk_chunk(mb, pages) == (ppc, pa._RUN)
            assert ppc <= pa._pages_per_chunk(mb, pages)
            assert pa._walk_chunk(mb, pages, window=128) \
                == (pa._pages_per_chunk(mb, pages), 1)
        for mb in range(1, 70):
            ppc, run = pa._walk_chunk(mb, [((16, 640), bf)])
            assert (run == 1) == (mb < pa._RUN)
            assert ppc % run == 0 and 1 <= ppc <= mb
        lengths = [1, 600, 5119, 3500]
        assert pa.walk_counts(lengths, 320, 16, 96)[2] \
            == sum(-(-(-(-n // 16)) // 96) for n in lengths)


def _serve_counting_the_walk(engine, cfg, requests):
    """Serve ``requests`` ((prompt, new tokens), two slots: a later one
    refills a freed slot, uneven budgets leave a dead slot beside a live
    one) and reckon, from the DEVICE's pos / remaining / live / table
    before each block, what the walk does: live pages, copied pages, and
    the live pages in aligned groups of 8 consecutive blocks (rising or
    falling) whose rows all lie below the length — by a plain loop over
    each live slot's table row. Returns the ``serving.decode_block``
    spans and those three sums."""
    import time
    from paddle_tpu.observability import tracing
    from paddle_tpu.ops.pallas.paged_attention import _RUN, walk_counts
    engine.reset()
    want = [0, 0, 0]
    step_block = engine.step_block
    bs, mb = engine.kv_block_size, engine.max_blocks

    def spying_step_block():
        if engine._pending_block is None and engine.has_decoding():
            st = {k: np.asarray(engine._state[k])
                  for k in ("pos", "remaining", "live", "table")}
            np.testing.assert_array_equal(st["pos"], engine._pos_host)
            stays = np.where(st["live"], np.minimum(
                st["remaining"], engine.decode_block), 0)
            for k in range(engine.decode_block):
                lengths = st["pos"] + np.minimum(k, stays) + 1
                live, copied, _ = walk_counts(lengths, mb, bs)
                want[0] += live
                want[1] += copied
                for row, n, on in zip(st["table"], lengths, k < stays):
                    for g in range(mb // _RUN if on else 0):
                        d = np.diff(row[g * _RUN:(g + 1) * _RUN])
                        if ((d == 1).all() or (d == -1).all()) \
                                and (g + 1) * _RUN * bs <= min(n, mb * bs):
                            want[2] += _RUN
        step_block()

    engine.step_block = spying_step_block
    try:
        rs = np.random.RandomState(4)
        srv = Server(engine)
        for n_prompt, n_new in requests:
            srv.submit(rs.randint(0, cfg.vocab_size, (n_prompt,))
                       .astype(np.int32), max_new_tokens=n_new)
        t0 = time.perf_counter()
        srv.run_until_idle()
    finally:
        del engine.step_block
    return [sp for sp in tracing.since(t0)
            if sp.name == "serving.decode_block"], want


class TestKvWalkCounters:
    def test_decode_block_counts_the_walk_it_ran(self, paged_setup):
        """Every decode block adds to ``engine.kv_pages_live`` /
        ``kv_pages_copied`` / ``kv_pages_in_runs``, and carries on its
        ``serving.decode_block`` span, exactly what the walk does for the
        lengths the block's steps read — reckoned here from the DEVICE's
        pos / remaining / live / table before each block, which the
        engine's host mirrors must agree with (dead and mid-prefill slots
        included: they re-read the length they were left at)."""
        model, cfg, engine = paged_setup
        spans, want = _serve_counting_the_walk(
            engine, cfg, ((5, 7), (19, 12), (9, 5)))
        assert spans and want[0] > 0
        assert set(spans[0].ids) == {"kv_pages_live", "kv_pages_copied",
                                     "kv_pages_in_runs", "kv_rows_live",
                                     "sampled_steps", "arm_ns", "starved_ns"}
        assert [engine.kv_pages_live, engine.kv_pages_copied,
                engine.kv_pages_in_runs] == want
        assert [sum(sp.ids[k] for sp in spans)
                for k in ("kv_pages_live", "kv_pages_copied",
                          "kv_pages_in_runs")] == want
        # copies are issued per live page: all of the kernel's KV traffic
        # is live KV
        assert engine.kv_pages_live == engine.kv_pages_copied
        np.testing.assert_array_equal(np.asarray(engine._state["pos"]),
                                      engine._pos_host)

    def test_pages_in_runs_are_counted_from_the_armed_rows(self,
                                                             paged_setup):
        """At two-token blocks a 32-column table holds four groups of 8:
        a request's blocks come from ONE allocation out of a fresh pool,
        falling and consecutive, so its whole groups move as runs, and
        ``kv_pages_in_runs`` (span, engine total, snapshot) counts exactly
        what a plain loop over the device's table finds; a refill into a
        freed slot re-derives the slot's groups from its new row."""
        model, cfg, _ = paged_setup
        engine = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            block_size=2, prefill_chunk=8)
        _LIVE_MANAGERS.append(engine.manager)
        spans, want = _serve_counting_the_walk(
            engine, cfg, ((21, 14), (35, 9), (17, 12)))
        assert want[2] > 0 and want[2] < want[0]
        assert engine.kv_pages_in_runs == want[2]
        assert sum(sp.ids["kv_pages_in_runs"] for sp in spans) == want[2]
        meta, _ = engine.snapshot_state()
        assert meta["paged_counters"]["kv_pages_in_runs"] == want[2]


class TestPagedScheduling:
    def test_pop_ready_token_budget(self):
        s = Scheduler(prefill_token_budget=10)
        for i, L in enumerate((6, 6, 2)):
            s.submit(Request(request_id=i,
                             prompt=np.ones((L,), np.int32)))
        got = s.pop_ready(0, free_slots=4, engine_idle=True)
        assert [r.request_id for r in got] == [0]    # 6+6 > 10
        got = s.pop_ready(0, free_slots=4, engine_idle=True)
        assert [r.request_id for r in got] == [1, 2]  # 6+2 <= 10

    def test_pop_ready_budget_never_starves(self):
        s = Scheduler(prefill_token_budget=4)
        s.submit(Request(request_id=0, prompt=np.ones((64,), np.int32)))
        assert len(s.pop_ready(0, 4, True)) == 1   # oversize: admit solo

    def test_requeue_lands_before_same_tick_peers(self):
        s = Scheduler()
        a = Request(request_id=0, prompt=np.ones((4,), np.int32))
        b = Request(request_id=1, prompt=np.ones((4,), np.int32))
        s.submit(a)
        s.submit(b)
        got = s.pop_ready(0, 1, True)
        assert got[0].request_id == 0
        s.requeue(got[0])
        assert [r.request_id for r in
                s.pop_ready(0, 2, True)] == [0, 1]

    def test_validate_rejects_oversized_at_the_door(self, paged_setup):
        model, cfg, engine = paged_setup
        engine.reset()
        srv = Server(engine)
        with pytest.raises(ValueError, match="slot capacity"):
            srv.submit(np.ones((8,), np.int32), max_new_tokens=60)
        small = ContinuousBatchingEngine(backend=engine.backend)
        small.manager = BlockManager(3, small.kv_block_size)
        small.num_kv_blocks = 3
        with pytest.raises(ValueError, match="KV blocks"):
            Server(small).submit(np.ones((30,), np.int32),
                                 max_new_tokens=10)


def _factory_case(case, model):
    """The engine ``ContinuousBatchingEngine`` builds for ``case``, and
    the class it must be."""
    from paddle_tpu.models.mimo_v2 import (MiMoV2ForCausalLM,
                                           mimo_v2_tiny_config)
    from paddle_tpu.serving import HybridPagedEngine, SpecPagedEngine
    from paddle_tpu.serving.spec import SpecConfig
    kw = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)
    if case == "plain":
        return ContinuousBatchingEngine(model, **kw), \
            ContinuousBatchingEngine
    if case == "two-groups":
        two = MiMoV2ForCausalLM(mimo_v2_tiny_config())
        return ContinuousBatchingEngine(two, **kw), HybridPagedEngine
    if case == "spec":
        return ContinuousBatchingEngine(
            model, spec=SpecConfig(k=2), **kw), SpecPagedEngine
    spec = ContinuousBatchingEngine(model, spec=SpecConfig(k=2), **kw)
    return ContinuousBatchingEngine(backend=spec.backend), SpecPagedEngine


@pytest.mark.parametrize("case", ["plain", "two-groups", "spec",
                                  "spec-backend"])
def test_the_factory_picks_from_what_it_observes(case, paged_setup,
                                                 monkeypatch):
    """ContinuousBatchingEngine(...) is the factory: the hybrid engine
    for a model whose cache keeps two groups of layers, the speculative
    engine under spec= (or over a verify-capable backend), the engine
    itself otherwise. paged=True and the environment decide nothing."""
    monkeypatch.setenv("PT_SERVING_PAGED", "0")
    monkeypatch.delenv("PT_SERVING_SPEC", raising=False)
    eng, want = _factory_case(case, paged_setup[0])
    assert type(eng) is want
    if case != "spec-backend":
        monkeypatch.setenv("PT_SERVING_PAGED", "1")
        again, _ = _factory_case(case, paged_setup[0])
        assert type(again) is want


@pytest.mark.parametrize("case", ["plain", "two-groups", "spec"])
def test_paged_false_is_refused(case, paged_setup):
    """The dense slot-pool engine was removed: paged=False raises, by
    name, whichever engine the factory would have built."""
    from paddle_tpu.models.mimo_v2 import (MiMoV2ForCausalLM,
                                           mimo_v2_tiny_config)
    from paddle_tpu.serving.spec import SpecConfig
    model = paged_setup[0] if case != "two-groups" else \
        MiMoV2ForCausalLM(mimo_v2_tiny_config())
    extra = dict(spec=SpecConfig(k=2)) if case == "spec" else {}
    with pytest.raises(ValueError, match="dense slot-pool engine was "
                                         "removed"):
        ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                 block_size=8, paged=False, **extra)


@pytest.fixture(scope="module")
def chunk_streams():
    """A 65- and a 300-token prompt served greedily by two engines that
    differ in the chunk alone (32 passed, and none passed): ``(model,
    prompts, {chunk: (engine, stats, streams)})``. The two engines' decode
    blocks are one program compiled twice, which stays out of the
    persistent compile cache (tests/test_resilience.py
    ``_no_compile_cache``)."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False,
                            max_position_embeddings=512)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(32)
    prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (65, 300)]
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        runs = {}
        for chunk in (32, None):
            engine = ContinuousBatchingEngine(
                model, num_slots=2, max_len=512, decode_block=4,
                paged=True, block_size=16, prefill_chunk=chunk)
            _LIVE_MANAGERS.append(engine.manager)
            srv = Server(engine)
            rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
            res = srv.run_until_idle()
            runs[engine.prefill_chunk_len] = (
                engine, srv.stats(), [res[r] for r in rids])
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    return model, prompts, runs


# case -> (PT_SERVING_PREFILL_CHUNK, engine arguments, the chunk it gets)
_CHUNK_CASES = {
    "rule_at_block_16_max_len_4096":
        (None, dict(block_size=16, max_len=4096), 256),
    "whole_blocks_of_24": (None, dict(block_size=24, max_len=480), 240),
    "max_len_64": (None, dict(block_size=16, max_len=64), 64),
    "max_len_100": (None, dict(block_size=4, max_len=100), 100),
    "argument_wins":
        ("40", dict(block_size=8, max_len=64, prefill_chunk=24), 24),
    "env_wins": ("40", dict(block_size=8, max_len=64), 40),
}


@pytest.mark.parametrize("case", [
    *_CHUNK_CASES, "stream_65_tokens", "stream_300_tokens",
    "chunk_fill_share"])
def test_default_prefill_chunk(case, request, paged_setup, monkeypatch):
    """The chunk a paged engine picks when none is passed
    (``paging.default_prefill_chunk``): the chunk program's ridge, in
    whole KV blocks, never wider than the table; what a caller passes
    still wins; and a stream does not depend on it."""
    from paddle_tpu.serving.paging import (PREFILL_CHUNK_RIDGE,
                                           default_prefill_chunk)
    if case in _CHUNK_CASES:
        env, kw, want = _CHUNK_CASES[case]
        if env is None:
            monkeypatch.delenv("PT_SERVING_PREFILL_CHUNK", raising=False)
        else:
            monkeypatch.setenv("PT_SERVING_PREFILL_CHUNK", env)
        engine = ContinuousBatchingEngine(   # compiles nothing until served
            paged_setup[0], num_slots=2, decode_block=4, paged=True, **kw)
        _LIVE_MANAGERS.append(engine.manager)
        assert engine.prefill_chunk_len == want
        # the rule alone, at this table and at KV blocks of 16
        for bs in (kw["block_size"], 16):
            chunk = default_prefill_chunk(bs, kw["max_len"])
            assert bs <= chunk <= min(PREFILL_CHUNK_RIDGE, kw["max_len"])
            assert chunk % bs == 0
        if env is None:
            assert want == default_prefill_chunk(kw["block_size"],
                                                 kw["max_len"])
        return
    model, prompts, runs = request.getfixturevalue("chunk_streams")
    assert sorted(runs) == [32, PREFILL_CHUNK_RIDGE]
    if case == "chunk_fill_share":
        for chunk, chunks in ((32, 3 + 10), (PREFILL_CHUNK_RIDGE, 1 + 2)):
            engine, stats, _ = runs[chunk]
            assert engine.prefill_chunks == chunks
            assert engine.prefilled_tokens == 65 + 300
            assert engine.prefill_compile_count() == 1
            assert stats["chunk_fill_share"] == round(
                engine.prefilled_tokens / (chunks * chunk), 4)
        return
    # the short prompt's table is 5 blocks wide: at the default its one
    # chunk's pad columns past it land in the trash block
    i = ("stream_65_tokens", "stream_300_tokens").index(case)
    np.testing.assert_array_equal(runs[32][2][i],
                                  runs[PREFILL_CHUNK_RIDGE][2][i])
    np.testing.assert_array_equal(
        runs[32][2][i], _ref(model, prompts[i], 6, temperature=0.0))


class TestPagedArtifact:
    """PR 4 carried follow-up: export_decoder(engine_slots=N) ships
    the engine's TWO programs with recorded arities, and
    PagedArtifactStepBackend serves them: a stub-backend test plus
    the artifact-level test through jax.export."""

    class _PagedProxyBackend:
        """Stands in for a PagedArtifactStepBackend: proxies the live
        backend and carries the artifact's fingerprint."""

        def __init__(self, inner):
            self._inner = inner
            self.artifact_fingerprint = "sha1:paged-stub"

        def __getattr__(self, name):
            return getattr(self.__dict__["_inner"], name)

    def test_stub_paged_backend_routes_and_serves(self, paged_setup):
        """An explicit backend builds the engine itself, with no other
        keyword (how the AOT serve() path constructs it), and serves a
        bit-identical stream."""
        model, cfg, engine = paged_setup
        eng = ContinuousBatchingEngine(
            backend=self._PagedProxyBackend(engine.backend))
        assert type(eng) is ContinuousBatchingEngine
        _LIVE_MANAGERS.append(eng.manager)
        rs = np.random.RandomState(41)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 9, 12)]
        srv = Server(eng, Scheduler(prefill_token_budget=8))
        rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
        res = srv.run_until_idle()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 5, temperature=0.0))

    def test_paged_artifact_arity_and_bit_identity(self, paged_setup,
                                                   tmp_path):
        """The exported artifact records both program arities
        (block_outputs=5, chunk_outputs=2), loads through
        PagedArtifactStepBackend, and GenerationPredictor.serve builds
        the engine over it with bit-identical greedy results."""
        import pickle
        from paddle_tpu.inference import (GenerationPredictor,
                                          export_decoder)
        from paddle_tpu.serving import PagedArtifactStepBackend
        model, cfg, engine = paged_setup
        path = export_decoder(model, str(tmp_path / "paged"), batch=1,
                              prompt_len=8, max_len=64, engine_slots=2,
                              engine_decode_block=4,
                              engine_block_size=8,
                              engine_prefill_chunk=8)
        with open(path, "rb") as f:
            blob = pickle.load(f)
        cfgs = blob["engine"]["config"]
        assert cfgs["paged"] is True
        assert cfgs["block_outputs"] == 5
        assert cfgs["chunk_outputs"] == 2
        back = PagedArtifactStepBackend(blob)
        assert back.kv_block_size == 8
        served = GenerationPredictor(path)
        rs = np.random.RandomState(43)
        prompts = [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 9, 12)]
        srv = served.serve([{"prompt": p, "max_new_tokens": 5}
                            for p in prompts], run=False)
        assert type(srv.engine) is ContinuousBatchingEngine
        res = srv.run_until_idle()
        for rid, p in enumerate(prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 5, temperature=0.0))
