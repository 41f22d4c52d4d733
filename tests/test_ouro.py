"""Ouro-class looped model (one stack of sandwich-norm layers run four times
over shared weights, a key/value cache for every pass, an exit gate) against
its plain reference, at toy size on the CPU.

The model's forward, ``generate()`` and chunked paged prefill + decode
against ``benchmark/reference/ouro.py`` (logits and exit distribution); each
broken variant of the reference fails the same comparison; the paged
engine's streams equal ``generate()``'s; a pass writes only its own slice of
an arena; a prefix hit (one block id, every pass's page) gives the cold
run's logits bit for bit; snapshot / restore and preemption round-trip; and
what the looped cache cannot do yet refuses by name.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import ouro as ref
from paddle_tpu.models.ouro import (OuroConfig, OuroForCausalLM,
                                    ouro_tiny_config)

VOCAB = 512
PASSES = 4


def as_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)


def build(dtype="float32", seed=0, **kw):
    """A seeded toy model; the norms' weights and the gate's bias are drawn
    away from 1 and 0, so that dropping a norm or the bias shows."""
    paddle.seed(seed)
    paddle.set_default_dtype(dtype)
    try:
        model = OuroForCausalLM(ouro_tiny_config(dtype=dtype, **kw))
    finally:
        paddle.set_default_dtype("float32")
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = jnp.asarray(1 + 0.3 * rng.standard_normal(p.shape),
                                   p._value.dtype)
        if name.endswith("early_exit_gate.bias"):
            p._value = jnp.asarray([0.4], p._value.dtype)
    return model


def params_of(model) -> dict:
    return {k: p._value for k, p in model.named_parameters()}


def ids_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


@pytest.fixture(scope="module")
def f32_model():
    return build()


@pytest.fixture(scope="module")
def f32_outputs(f32_model):
    ids = ids_of(40)
    return (ids,) + ref.model_outputs(f32_model, ids)


# -- the model against the reference -----------------------------------------

def test_config_holds_the_published_sizes_and_refuses_an_adaptive_exit():
    c = OuroConfig()
    assert (c.num_hidden_layers, c.total_ut_steps, c.hidden_size,
            c.intermediate_size, c.num_attention_heads,
            c.num_key_value_heads, c.vocab_size) \
        == (48, 4, 2048, 5632, 16, 16, 49152)
    assert OuroConfig(head_dim=128).head_dim == 128
    with pytest.raises(ValueError, match="head_dim"):
        OuroConfig(head_dim=64)
    with pytest.raises(NotImplementedError, match="skips passes"):
        OuroConfig(early_exit_threshold=0.5)


def test_float32_forward_is_the_reference_tight(f32_model, f32_outputs):
    ids, logits, p = f32_outputs
    r = ref.compare(logits, p, params_of(f32_model),
                    as_dict(f32_model.config), ids)
    assert r["logits_err"] < 1e-5 and r["exit_err"] < 1e-5
    assert p.shape == (40, PASSES)
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    # the gate is not trivial: no pass takes everything or nothing
    assert 0.005 < p.mean(0).min() and p.mean(0).max() < 0.9


def test_reference_in_blocks_is_the_reference(f32_model, f32_outputs):
    ids, logits, p = f32_outputs
    c, params = as_dict(f32_model.config), params_of(f32_model)
    whole, _ = ref.forward(params, c, ids)
    rows = np.asarray([0, 17, 39])
    blocks, pb = ref.forward(params, c, ids, logits_at=rows, block=16)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole)[rows],
                               atol=2e-5)
    np.testing.assert_allclose(pb, p, atol=1e-5)


def test_bf16_forward_passes_the_comparison():
    model = build("bfloat16")
    ids = ids_of(40)
    r = ref.compare(*ref.model_outputs(model, ids), params_of(model),
                    as_dict(model.config), ids)
    assert r["logits_err"] <= ref.LOGITS_TOLERANCE, r
    assert r["exit_err"] <= ref.EXIT_TOLERANCE, r


@pytest.mark.parametrize("broken", ref.MUTATIONS + ("float8",))
def test_a_broken_variant_fails_the_comparison(broken, f32_model,
                                               f32_outputs):
    """One pass fewer, a pass reading another pass's keys and values, no
    norm between passes, a half's output norm dropped, 8-bit floats: each
    misses by far more than the limit."""
    ids, logits, p = f32_outputs
    kw = dict(matmul_dtype=jnp.float8_e4m3fn) if broken == "float8" \
        else dict(mutate=(broken,))
    r = ref.compare(logits, p, params_of(f32_model),
                    as_dict(f32_model.config), ids, **kw)
    assert r["logits_err"] > 2 * ref.LOGITS_TOLERANCE, r
    assert r["exit_err"] > ref.EXIT_TOLERANCE, r


def is_the_references_argmax(model, prompt, row):
    logits, _ = ref.forward(params_of(model), as_dict(model.config), row[:-1])
    return np.array_equal(row[len(prompt):],
                          np.argmax(np.asarray(logits), -1)[len(prompt) - 1:])


def test_generate_is_the_references_argmax(f32_model):
    prompt = ids_of(13, seed=3)
    row = np.asarray(f32_model.generate(paddle.to_tensor(prompt[None]),
                                        max_new_tokens=12)._value[0])
    assert is_the_references_argmax(f32_model, prompt, row)
    with pytest.raises(NotImplementedError, match="left-padded"):
        f32_model.generate(paddle.to_tensor(prompt[None]), max_new_tokens=2,
                           attention_mask=np.ones((1, 13), np.int32))


def test_chunked_paged_prefill_then_decode_matches_the_reference(f32_model):
    ids = ids_of(70, seed=5)
    rows, got, p = ref.cached_outputs(f32_model, ids, chunk=32, decode=8,
                                      block=8)
    assert list(rows) == [0, 31, 32, 61] + list(range(62, 70))
    r = ref.compare(got, p, params_of(f32_model), as_dict(f32_model.config),
                    ids, logits_at=rows)
    assert r["logits_err"] < 1e-5 and r["exit_err"] < 1e-5
    for broken in ("kv_prev_pass", "kv_last_pass", "three_passes"):
        r = ref.compare(got, p, params_of(f32_model),
                        as_dict(f32_model.config), ids, logits_at=rows,
                        mutate=(broken,))
        assert r["logits_err"] > 2 * ref.LOGITS_TOLERANCE, (broken, r)


def test_a_cache_without_a_slice_a_pass_fails_the_cached_comparison(
        f32_model, monkeypatch):
    """The SYSTEM broken: every pass through the table as it is, so a pass
    finds the last pass's keys and values for every earlier chunk."""
    from paddle_tpu.models import ouro
    real = ouro.OuroModel._one_pass

    def no_offset(self, x, arenas, pos, table):
        return real(self, x, arenas, pos, table % (arenas[0].shape[0]
                                                   // PASSES))
    monkeypatch.setattr(ouro.OuroModel, "_one_pass", no_offset)
    ids = ids_of(70, seed=5)
    rows, got, p = ref.cached_outputs(f32_model, ids, chunk=32, decode=8,
                                      block=8)
    r = ref.compare(got, p, params_of(f32_model), as_dict(f32_model.config),
                    ids, logits_at=rows)
    assert r["logits_err"] > 2 * ref.LOGITS_TOLERANCE, r


# -- the cache's geometry -----------------------------------------------------

def cache_step(model, cache, ids, table, pos):
    logits, cache = model(paddle.to_tensor(np.asarray(ids, np.int32)[None]),
                          cache=cache,
                          block_table=paddle.to_tensor(
                              np.asarray(table, np.int32)[None]),
                          pos=paddle.to_tensor(np.asarray([pos], np.int32)))
    return np.asarray(logits._value[0]), cache


def test_a_pass_writes_only_its_own_slice(f32_model):
    """12 tokens through blocks 3 and 5 of an 8-block pool: in every slice
    u of a layer's arenas exactly blocks ``u x 8 + 3`` and ``u x 8 + 5``
    are written, the slices differ, and slice 0 is what a one-pass model
    over the same weights writes."""
    nb, bs = 8, 8
    ids = ids_of(12, seed=7)
    _, cache = cache_step(f32_model, f32_model.init_paged_kv_cache(nb, bs),
                          ids, [3, 5, 0, 0], 0)
    once = OuroForCausalLM(ouro_tiny_config(total_ut_steps=1))
    once.set_state_dict(f32_model.state_dict())
    _, cache1 = cache_step(once, once.init_paged_kv_cache(nb, bs), ids,
                           [3, 5, 0, 0], 0)
    for (k, v), (k1, v1) in zip(cache["layers"], cache1["layers"]):
        for arena, arena1 in ((np.asarray(k._value), np.asarray(k1._value)),
                              (np.asarray(v._value), np.asarray(v1._value))):
            assert arena.shape[0] == PASSES * nb
            written = {int(b) for b in
                       np.flatnonzero(np.abs(arena).sum((1, 2, 3)))}
            assert written == {u * nb + b for u in range(PASSES)
                               for b in (3, 5)}
            np.testing.assert_allclose(arena[:nb], arena1, atol=1e-6)
            assert np.abs(arena[3] - arena[nb + 3]).max() > 1e-3
    assert int(np.asarray(cache["ut_counters"]._value)[1, 0]) > 0


def test_a_prefix_hit_reads_every_passes_page_bit_for_bit(f32_model):
    """One block id stands for a token range in every pass's slice: a
    second request whose table names the first one's block for tokens
    0..7 and prefills only the rest reads what the cold run read."""
    nb, bs = 8, 8
    ids = ids_of(16, seed=9)
    cache = f32_model.init_paged_kv_cache(nb, bs)
    _, cache = cache_step(f32_model, cache, ids[:8], [2, 4, 0, 0], 0)
    cold, cache = cache_step(f32_model, cache, ids[8:], [2, 4, 0, 0], 8)
    hit, cache = cache_step(f32_model, cache, ids[8:], [2, 6, 0, 0], 8)
    assert np.array_equal(cold, hit)
    k = np.asarray(cache["layers"][0][0]._value)
    assert all(np.array_equal(k[u * nb + 4], k[u * nb + 6])
               for u in range(PASSES))


# -- the paged engine ---------------------------------------------------------

def engine_of(model, **kw):
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    args = dict(paged=True, num_slots=3, max_len=96, block_size=8,
                prefill_chunk=16, decode_block=4)
    args.update(kw)
    eng = ContinuousBatchingEngine(model, **args)
    return eng, Server(eng, Scheduler())


def generate_row(model, prompt, new):
    return np.asarray(model.generate(paddle.to_tensor(prompt[None]),
                                     max_new_tokens=new)._value[0])


def test_engine_streams_equal_generate_token_for_token(f32_model):
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import ContinuousBatchingEngine
    eng, srv = engine_of(f32_model)
    assert type(eng) is ContinuousBatchingEngine     # no engine of its own
    assert (eng.cache_passes, eng.attn_sites) == (PASSES, PASSES * 3)
    prompts = [ids_of(n, seed=20 + n) for n in (21, 40, 9, 33)]
    news = (30, 12, 25, 7)
    handed_out, allocate = set(), eng.manager.allocate

    def noting(n):
        ids = allocate(n)
        handed_out.update(ids or ())
        return ids
    eng.manager.allocate = noting
    rids = [srv.submit(p, max_new_tokens=m) for p, m in zip(prompts, news)]
    srv.run_until_idle()
    for p, m, rid in zip(prompts, news, rids):
        np.testing.assert_array_equal(srv.results[rid],
                                      generate_row(f32_model, p, m))
    assert eng.decode_compile_count() == eng.prefill_compile_count() == 1
    assert eng.ut_steps == PASSES * eng.steps > 0
    assert 0 < eng.ut_exit_step_milli < 1000 * (PASSES - 1) \
        * eng.decode_tokens
    block = [s for s in tracing.since(0)
             if s.name == "serving.decode_block"][-1]
    assert block.ids["ut_steps"] == PASSES * eng.decode_block
    assert "ut_exit_step_milli" in block.ids
    chunk = [s for s in tracing.since(0)
             if s.name == "serving.prefill_chunk"
             and "ut_exit_step_milli" in s.ids][-1]
    assert chunk.ids["chunks"] >= 1
    stats = srv.stats()
    assert (stats["attn_sites"], stats["ut_steps"],
            stats["ut_exit_step_milli"]) \
        == (eng.attn_sites, eng.ut_steps, eng.ut_exit_step_milli)
    eng.manager.assert_consistent()
    assert not eng.manager._ref
    # in every slice of an arena only the blocks the manager handed out
    # are written, and block 0, the slice's trash block (dead slots' rows)
    nb = eng.num_kv_blocks
    k = np.asarray(eng._cache[0])
    assert k.shape[0] == PASSES * nb and len(handed_out) < nb - 1
    written = np.flatnonzero(np.abs(k).sum((1, 2, 3)))
    for u in range(PASSES):
        in_slice = {int(b) - u * nb for b in written if b // nb == u}
        assert in_slice - {0} == handed_out, u


def test_engine_prefix_hit_streams_as_the_cold_run(f32_model):
    eng, srv = engine_of(f32_model)
    shared = ids_of(24, seed=40)
    first = np.concatenate([shared, ids_of(5, seed=41)])
    again = np.concatenate([shared, ids_of(9, seed=42)])
    r1 = srv.submit(first, max_new_tokens=10)
    srv.run_until_idle()
    assert eng.shared_tokens == 0
    r2 = srv.submit(again, max_new_tokens=10)
    srv.run_until_idle()
    assert eng.shared_tokens == 24               # three block ids, 12 pages
    np.testing.assert_array_equal(srv.results[r1],
                                  generate_row(f32_model, first, 10))
    np.testing.assert_array_equal(srv.results[r2],
                                  generate_row(f32_model, again, 10))


def test_snapshot_and_restore_round_trip(f32_model, tmp_path):
    from paddle_tpu.serving import Scheduler, Server
    prompts = [ids_of(n, seed=50 + n) for n in (19, 30)]
    eng, srv = engine_of(f32_model)
    rids = [srv.submit(p, max_new_tokens=20) for p in prompts]
    srv.run_until_idle(max_ticks=3)
    assert eng.has_live()
    path = str(tmp_path / "looped.npz")
    srv.snapshot(path)
    eng2, _ = engine_of(f32_model)
    srv2 = Server.restore(path, eng2, Scheduler())
    srv2.run_until_idle()
    assert eng2.ut_steps == PASSES * eng2.steps
    for p, rid in zip(prompts, rids):
        np.testing.assert_array_equal(srv2.results[rid],
                                      generate_row(f32_model, p, 20))
    eng2.manager.assert_consistent()


def test_preemption_resumes_bit_identical(f32_model):
    from paddle_tpu.serving import ContinuousBatchingEngine, Frontend
    eng = ContinuousBatchingEngine(f32_model, paged=True, num_slots=2,
                                   max_len=96, block_size=8,
                                   prefill_chunk=16, decode_block=4)
    prompts = [ids_of(n, seed=60 + n) for n in (11, 17, 14)]
    fe = Frontend(eng, preemption=True)
    low = [fe.submit(p, max_new_tokens=20, priority=0) for p in prompts[:2]]
    fe.pump()
    fe.pump()                                   # both slots decoding
    hi = fe.submit(prompts[2], max_new_tokens=4, priority=5)
    res = fe.run_until_idle()
    st = fe.stats()
    assert st["preemptions"] >= 1 and st["resumes"] >= 1
    for rid, p, m in zip(low + [hi], prompts, (20, 20, 4)):
        np.testing.assert_array_equal(res[rid],
                                      generate_row(f32_model, p, m))
    assert eng.decode_compile_count() == eng.prefill_compile_count() == 1
    eng.manager.assert_consistent()
    assert not eng.manager._ref


# -- what is not carried over -------------------------------------------------

def test_what_the_looped_cache_cannot_do_refuses_by_name(f32_model, tmp_path):
    from paddle_tpu.inference import export_decoder
    from paddle_tpu.serving import ContinuousBatchingEngine
    from paddle_tpu.serving.fleet import (DecodeWorker, PrefillPagedEngine,
                                          PrefillWorker)
    from paddle_tpu.serving.spec import SpecConfig
    from paddle_tpu.serving.tp import TPConfig
    kw = dict(paged=True, num_slots=2, max_len=64, block_size=8)
    for extra, word in ((dict(kv_int8=True), "kv_int8"),
                        (dict(spec=SpecConfig(k=2)), "speculative"),
                        (dict(tp=TPConfig(mode="exact", mesh=object())),
                         "tensor-parallel")):
        with pytest.raises(NotImplementedError, match=word):
            ContinuousBatchingEngine(f32_model, **extra, **kw)
    eng, _ = engine_of(f32_model)
    with pytest.raises(NotImplementedError, match="hand-off"):
        DecodeWorker(eng)
    with pytest.raises(NotImplementedError, match="hand-off"):
        PrefillWorker(PrefillPagedEngine(f32_model, 2, 64, block_size=8))
    with pytest.raises(NotImplementedError, match="exported paged artifact"):
        export_decoder(f32_model, str(tmp_path / "a"), 1, 8, 64,
                       engine_slots=2)
    # generate()'s ragged batch pads its prompts on the left
    with pytest.raises(NotImplementedError, match="left-padded"):
        f32_model.generate(paddle.to_tensor(np.stack([ids_of(5)] * 2)),
                           max_new_tokens=3,
                           attention_mask=np.array([[0, 1, 1, 1, 1],
                                                    [1, 1, 1, 1, 1]]))
