"""LFM2-MoE-class model (gated short-conv layers whose state the paged cache
keeps under each block id, beside grouped-query attention layers with a
norm on every query and key head; sigmoid top-k experts chosen with a
selection bias) against its plain reference
(``benchmark/reference/lfm2_moe.py``), at toy size on the CPU.

The toy has every form of layer: dense + conv, experts + attention,
experts + conv x 3; hidden 64, 4 heads of 16 over 2 kv heads, 8 experts
top-2, float32; a selection bias drawn at std 0.05 as the benchmark's
configuration draws it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import lfm2_moe as ref
from paddle_tpu.models.lfm2_moe import (ATTN, CONV, Lfm2MoeConfig,
                                        Lfm2MoeForCausalLM,
                                        conv_state_slots,
                                        lfm2_moe_tiny_config)

VOCAB = 512
N = 96
BLOCK = 8


def build(dtype="float32", seed=0, bias_std=0.05, **kw):
    paddle.seed(seed)
    paddle.set_default_dtype(dtype)
    try:
        model = Lfm2MoeForCausalLM(lfm2_moe_tiny_config(dtype=dtype, **kw))
    finally:
        paddle.set_default_dtype("float32")
    for i, (name, p) in enumerate(model.named_parameters()):
        if name.endswith("e_score_correction_bias"):
            p._value = bias_std * jax.random.normal(
                jax.random.PRNGKey(seed * 100 + i), p._value.shape)
    return model


def params_of(model) -> dict:
    return {k: p._value for k, p in model.named_parameters()}


def as_dict(model) -> dict:
    return dataclasses.asdict(model.config)


def ids_of(n=N, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


@pytest.fixture(scope="module")
def f32_model():
    return build()


@pytest.fixture(scope="module")
def f32_outputs(f32_model):
    ids = ids_of()
    return (ids,) + ref.model_outputs(f32_model, ids)


# -- the published numbers ----------------------------------------------------

def test_config_reads_the_published_row():
    c = Lfm2MoeConfig()
    assert c.layer_types[:3] == (CONV, CONV, ATTN)
    assert c.layer_types.count(ATTN) == 10 and c.conv_layers == 30
    assert [i for i, t in enumerate(c.layer_types) if t == ATTN] \
        == list(range(2, 40, 4))
    assert (c.head_dim, c.kv_row, c.conv_L_cache) == (64, 128, 3)
    cut = Lfm2MoeConfig(num_hidden_layers=9, num_dense_layers=1,
                        layer_types=(CONV,) + (ATTN, CONV, CONV, CONV) * 2)
    assert cut.conv_layers == 7
    for bad in (dict(conv_bias=True), dict(tie_word_embeddings=True),
                dict(use_expert_bias=False),
                dict(layer_types=(CONV,) * 40),
                dict(layer_types=("linear_attention",) * 40),
                dict(rope_parameters={"rope_type": "yarn",
                                      "rope_theta": 1e6})):
        with pytest.raises(ValueError):
            Lfm2MoeConfig(**bad)


# -- the model against the reference ------------------------------------------

def test_float32_forward_matches_the_reference_tight(f32_model, f32_outputs):
    ids, logits, picks = f32_outputs
    r = ref.compare(logits, picks, params_of(f32_model), as_dict(f32_model),
                    ids)
    assert r["picks_agree"] == 1.0 and r["logits_err"] < 1e-5


def test_bf16_forward_passes_and_an_8bit_reference_fails():
    model = build("bfloat16", seed=1)
    ids = ids_of(seed=1)
    logits, picks = ref.model_outputs(model, ids)
    params, c = params_of(model), as_dict(model)
    r = ref.compare(logits, picks, params, c, ids)
    assert r["picks_agree"] >= ref.PICKS_TOLERANCE
    assert r["logits_err"] <= ref.LOGITS_TOLERANCE
    low = ref.compare(logits, picks, params, c, ids,
                      matmul_dtype=jnp.float8_e4m3fn)
    assert low["logits_err"] > ref.LOGITS_TOLERANCE


@pytest.mark.parametrize("broken", ref.MUTATIONS)
def test_a_broken_reference_fails_the_comparison(broken, f32_model,
                                                 f32_outputs):
    ids, logits, picks = f32_outputs
    r = ref.compare(logits, picks, params_of(f32_model), as_dict(f32_model),
                    ids, mutate=(broken,))
    assert r["picks_agree"] < ref.PICKS_TOLERANCE \
        or r["logits_err"] > ref.LOGITS_TOLERANCE, r


def test_the_expert_bias_changes_the_picks_and_not_the_weights(f32_model):
    """The bias moves which experts a token gets (the model's own picks
    with the bias zeroed are another set), and the weights stay the
    scores: held to the same picks, the logits do not move."""
    ids = ids_of(seed=3)
    logits, picks = ref.model_outputs(f32_model, ids)
    unbiased = build()
    for name, p in unbiased.named_parameters():
        if name.endswith("e_score_correction_bias"):
            p._value = jnp.zeros_like(p._value)
    _, plain_picks = ref.model_outputs(unbiased, ids)
    same = np.mean(np.sort(picks, -1) == np.sort(plain_picks, -1))
    assert same < 0.95
    params, c = params_of(f32_model), as_dict(f32_model)
    held, _ = ref.forward(params, c, ids, forced_picks=picks,
                          mutate=("bias",))
    np.testing.assert_allclose(np.asarray(held), np.asarray(logits),
                               rtol=1e-4, atol=1e-4)


# -- the conv state in the paged cache ----------------------------------------

def test_conv_state_slots_read_the_block_before_and_write_each_block_end():
    """A chunk of 16 columns at position 12 with 11 valid, blocks of 8:
    it reads block (12 - 1) // 8 = 1's entry; it touches blocks 1, 2, 3;
    block 1 gets the state at column 3 (position 15), block 2 at column 10
    (position 22, the last valid), block 3 (no valid column) nothing (the
    out-of-range id, a dropped write). A decode step at 16 reads block 1
    and writes block 2; one at 0 reads nothing."""
    table = jnp.asarray([[5, 6, 7, 8, 9, 10]], jnp.int32)
    rd, prior, wr, cols = conv_state_slots(jnp.asarray([12]), table,
                                           jnp.int32(11), 16, BLOCK, 99)
    assert (int(rd[0]), bool(prior[0])) == (6, True)
    assert np.asarray(wr).tolist() == [[6, 7, 99]]
    assert np.asarray(cols)[0, :2].tolist() == [3, 10]
    rd, prior, wr, cols = conv_state_slots(jnp.asarray([16, 0]),
                                           jnp.concatenate([table, table]),
                                           None, 1, BLOCK, 99)
    assert np.asarray(rd).tolist() == [6, 5]
    assert np.asarray(prior).tolist() == [True, False]
    assert np.asarray(wr).tolist() == [[7], [5]]


def test_chunked_paged_prefill_then_decode_matches_the_reference(f32_model):
    """96 tokens in chunks of 12 (so chunks end inside blocks of 8) then 8
    decode steps: every chunk's first and last token and every decode step
    agree with the reference's full forward."""
    ids = ids_of(seed=2)
    rows, got, picks = ref.cached_outputs(f32_model, ids, chunk=12,
                                          decode=8, block=BLOCK)
    assert rows[-1] == N - 1
    r = ref.compare(got, picks, params_of(f32_model), as_dict(f32_model),
                    ids, logits_at=rows)
    assert r["picks_agree"] == 1.0 and r["logits_err"] < 1e-5


def test_pad_columns_write_no_conv_state(f32_model):
    """One chunk of 16 columns with 11 valid from position 0, blocks of 8
    in table [1, 2, 3, 4]: block 1 holds the state at position 7, block 2
    at position 10, both as an unpadded call of exactly the 11 tokens
    leaves them; blocks 3 and 4 and the trash block 0 stay zero."""
    from paddle_tpu.jit import EvalStep
    ids = ids_of(11, seed=5)

    def after(width):
        cache = f32_model.init_paged_kv_cache(6, BLOCK)
        feed = np.zeros((1, width), np.int32)
        feed[0, :11] = ids
        step = EvalStep(f32_model, lambda m, b: m(
            b["ids"], cache=b["cache"], block_table=b["table"],
            pos=b["pos"], valid_len=b["valid"])[1])
        new = step({"ids": paddle.to_tensor(feed), "cache": cache,
                    "table": paddle.to_tensor(np.asarray([[1, 2, 3, 4]],
                                                         np.int32)),
                    "pos": paddle.to_tensor(np.asarray([0], np.int32)),
                    "valid": paddle.to_tensor(np.asarray(11, np.int32))})
        return np.asarray(new["mixer_state"]._value)

    padded, exact = after(16), after(11)
    np.testing.assert_array_equal(padded[1:3], exact[1:3])
    assert np.all(np.abs(padded[1:3]).sum(axis=(2, 3)) > 0)
    assert not padded[[0, 3, 4, 5]].any()


def test_a_prefix_hit_starts_from_the_state_at_the_cut(f32_model):
    """The first 48 tokens served as a request of their own, then a second
    request that shares their 6 blocks (conv entries included) prefills
    from the cut: its logits match the reference's full forward; with the
    shared entry at the cut zeroed (the state recomputed from nothing) they
    do not."""
    ids = ids_of(seed=6)
    params, c = params_of(f32_model), as_dict(f32_model)
    rows, got, picks = ref.cached_outputs(f32_model, ids, chunk=16,
                                          decode=8, cut=48, block=BLOCK)
    assert rows[0] == 48
    r = ref.compare(got, picks, params, c, ids, logits_at=rows)
    assert r["picks_agree"] == 1.0 and r["logits_err"] < 1e-5
    rows, got, picks = ref.cached_outputs(f32_model, ids, chunk=16,
                                          decode=8, cut=48, block=BLOCK,
                                          zero_at_cut=True)
    bad = ref.compare(got, picks, params, c, ids, logits_at=rows)
    assert bad["logits_err"] > ref.LOGITS_TOLERANCE


def _engine(model, **kw):
    from paddle_tpu.serving import ContinuousBatchingEngine
    args = dict(num_slots=2, max_len=128, decode_block=4, paged=True,
                block_size=BLOCK, prefill_chunk=16)
    args.update(kw)
    return ContinuousBatchingEngine(model, **args)


def _greedy_is_reference(model, prompt, out):
    logits, _ = ref.forward(params_of(model), as_dict(model), out[:-1])
    want = np.argmax(np.asarray(logits), -1)[len(prompt) - 1:]
    assert np.array_equal(out[len(prompt):], want)


def test_engine_serves_it_and_a_turn_hits_the_prefix(f32_model):
    """Through ``Server`` over the paged engine: a 48-token prefix served
    once (its 6 blocks registered, conv entries with them), then two turns
    on it at different depths; each turn is admitted from the prefix's
    blocks, prefills only its own tokens and emits the reference's own
    greedy tokens; the engine counts the admissions warm and cold."""
    from paddle_tpu.serving import Scheduler, Server
    from paddle_tpu.serving.paging import PagedModelStepBackend
    eng = _engine(f32_model)
    assert type(eng.backend) is PagedModelStepBackend
    assert eng.conv_state_layers == 4
    # the cache's leaves in key order: the one attention layer's packed
    # arena, the conv state, the counters last
    assert [tuple(a.shape) for a in eng._cache] == [
        (eng.num_kv_blocks, 16, 128), (eng.num_kv_blocks, 4, 2, 64), (2, 3)]
    srv = Server(eng, Scheduler())
    prefix = ids_of(48, seed=8)
    srv.submit(prefix, max_new_tokens=1)
    srv.run_until_idle()
    turns = [np.concatenate([prefix, ids_of(n, seed=9 + n)])
             for n in (5, 21)]
    rids = [srv.submit(p, max_new_tokens=30 - 8 * i)
            for i, p in enumerate(turns)]
    srv.run_until_idle()
    assert eng.shared_tokens == 2 * 48
    assert (eng.conv_warm_admissions, eng.conv_cold_admissions) == (2, 1)
    for rid, p in zip(rids, turns):
        _greedy_is_reference(f32_model, p, np.asarray(srv.results[rid]))
    assert eng.moe_picks > 0
    eng.manager.assert_consistent()


def test_generate_matches_the_engine(f32_model):
    from paddle_tpu.serving import Scheduler, Server
    prompt = ids_of(19, seed=12)
    out = f32_model.generate(paddle.to_tensor(prompt[None]),
                             max_new_tokens=12).numpy()[0]
    srv = Server(_engine(f32_model), Scheduler())
    rid = srv.submit(prompt, max_new_tokens=12)
    srv.run_until_idle()
    assert np.array_equal(np.asarray(srv.results[rid]), out)


def test_snapshot_restore_and_preemption_carry_the_conv_state(f32_model,
                                                             tmp_path):
    """Snapshot mid-stream and restore into a fresh engine; preempt a
    decoding slot and let it re-prefill from its registered prompt blocks:
    both finish with the uninterrupted run's tokens."""
    from paddle_tpu.serving import Scheduler, Server
    prompts = [ids_of(27, seed=13), ids_of(20, seed=14)]

    def serve(hook=None):
        srv = Server(_engine(f32_model), Scheduler())
        rids = [srv.submit(p, max_new_tokens=24) for p in prompts]
        srv.run_until_idle(max_ticks=4)
        if hook is not None:
            srv = hook(srv)
        srv.run_until_idle()
        return [np.asarray(srv.results[r]) for r in rids]

    want = serve()

    def snap(srv):
        srv.snapshot(str(tmp_path / "s.npz"))
        return Server.restore(str(tmp_path / "s.npz"), _engine(f32_model))

    def preempt(srv):
        slot, run = next((s, r) for s, r in srv.engine.live_runs()
                         if s not in srv.engine._prefill_slots)
        srv._do_preempt(slot, run)
        return srv

    for hook in (snap, preempt):
        got = serve(hook)
        assert all(np.array_equal(a, b) for a, b in zip(got, want)), hook


def test_the_fleet_hand_off_carries_the_conv_state(f32_model):
    """A prefill worker's engine prefills, its blocks (conv entries with
    them) ship to a decode worker, which decodes the reference's greedy
    tokens."""
    from paddle_tpu.serving import (DecodeWorker, Fleet, PrefillPagedEngine,
                                    PrefillWorker)
    kw = dict(num_slots=2, max_len=128, decode_block=4, block_size=BLOCK,
              prefill_chunk=16)
    fleet = Fleet([PrefillWorker(PrefillPagedEngine(f32_model, **kw))],
                  [DecodeWorker(_engine(f32_model))])
    prompt = ids_of(29, seed=15)
    rid = fleet.submit(prompt, max_new_tokens=14)
    fleet.run_until_idle(max_ticks=200)
    _greedy_is_reference(f32_model, prompt, np.asarray(fleet.results[rid]))


def test_what_cannot_carry_the_conv_state_refuses_by_name(f32_model):
    from paddle_tpu.serving import ContinuousBatchingEngine
    from paddle_tpu.serving.spec import SpecConfig
    from paddle_tpu.serving.tp import TPConfig
    kw = dict(paged=True, num_slots=2, max_len=64, block_size=BLOCK)
    for extra, word in ((dict(kv_int8=True), "kv_int8"),
                        (dict(spec=SpecConfig(k=2)), "speculative"),
                        (dict(tp=TPConfig(mode="exact", mesh=object())),
                         "tensor-parallel")):
        with pytest.raises(NotImplementedError, match=word):
            ContinuousBatchingEngine(f32_model, **extra, **kw)
    with pytest.raises(NotImplementedError, match="int8"):
        f32_model.init_paged_kv_cache(4, BLOCK, kv_int8=True)
    with pytest.raises(NotImplementedError, match="left-padded"):
        f32_model(paddle.to_tensor(ids_of(4)[None]),
                  cache=f32_model.init_kv_cache(1, 16),
                  pos=paddle.to_tensor(np.int32(0)),
                  pad=paddle.to_tensor(np.zeros((1,), np.int32)))
