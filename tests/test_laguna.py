"""Laguna-class model (window and full grouped-query layers with their own
query heads over the same kv heads, YaRN on the full layers, a per-head
sigmoid gate, softmax top-10 experts x 2.5 beside one shared expert, a
share of the experts held) against its plain reference
(``benchmark/reference/laguna.py``), at toy size on the CPU.

The toy has both kinds of layer twice or more (F S S S F), heads 12 / 18
over 2 kv heads, a window of 16 and YaRN over 32 original positions; the
sequences run to 96 positions, past both.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import deepseek_v3 as ds_ref
from benchmark.reference import laguna as ref
from paddle_tpu.incubate.distributed.models import moe
from paddle_tpu.models.laguna import (LagunaConfig, LagunaForCausalLM,
                                      laguna_tiny_config)
from paddle_tpu.models.mimo_v2 import YaRN
from paddle_tpu.ops.pallas import fused
from paddle_tpu.ops.pallas import paged_attention as pa

VOCAB = 512
N = 96


def build(dtype="float32", seed=0, **kw):
    paddle.seed(seed)
    paddle.set_default_dtype(dtype)
    try:
        return LagunaForCausalLM(laguna_tiny_config(dtype=dtype, **kw))
    finally:
        paddle.set_default_dtype("float32")


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

def test_config_reads_the_published_row_per_layer():
    c = LagunaConfig(num_hidden_layers=5)
    assert c.layer_types == ("full_attention",) + ("sliding_attention",) * 3 \
        + ("full_attention",)
    assert c.num_attention_heads_per_layer == (48, 72, 72, 72, 48)
    assert c.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert c.hybrid_layer_pattern == (0, 1, 1, 1, 0)
    assert (c.kv_row, c.kv_heads(0), c.kv_heads(1)) == (256, 8, 8)
    full, sliding = c.attention_sizes(0), c.attention_sizes(1)
    assert (full["heads"], full["rotary_dim"], full["theta"]) \
        == (48, 64, 500000.0)
    assert (sliding["heads"], sliding["rotary_dim"], sliding["theta"],
            sliding["yarn"]) == (72, 128, 10000.0, None)
    assert full["gate"] and sliding["gate"] and not full["sink"]
    assert full["value_scale"] == sliding["value_scale"] == 1.0
    for bad in (dict(moe_router_logit_softcapping=30.0),
                dict(moe_apply_router_weight_on_input=True),
                dict(tie_word_embeddings=True),
                dict(mlp_layer_types=("sparse",) * 5),
                dict(num_attention_heads_per_layer=(48, 70, 72, 72, 48))):
        with pytest.raises(ValueError):
            LagunaConfig(num_hidden_layers=5, **bad)


def test_yarn_pins_the_published_ramp_and_attention_factor():
    """dim 64 (half of 128), base 5e5, factor 128, 8,192 original
    positions, beta 32 / 1: corr(b) = 64 ln(8192 / (2 pi b)) / (2 ln 5e5),
    low = floor(corr(32)) = 9, high = ceil(corr(1)) = 18; the attention
    factor is 0.1 ln(128) + 1 = 1.4852030263919618. The program's
    frequencies are the reference's."""
    rp = LagunaConfig().rope_parameters["full_attention"]

    def corr(b):
        return 64 * math.log(8192 / (2 * math.pi * b)) / (2 * math.log(5e5))
    assert (math.floor(corr(32)), math.ceil(corr(1))) == (9, 18)
    assert ref.yarn_range(rp, 64) == (9, 18)
    yarn = LagunaConfig().attention_sizes(0)["yarn"]
    assert yarn.correction_range(5e5, 64) == (9, 18)
    assert yarn.attention_factor == rp["attention_factor"] \
        == pytest.approx(0.1 * math.log(128) + 1.0, abs=1e-15)
    want, factor = ref.inv_freq(rp, 64)
    assert factor == yarn.attention_factor
    got = np.asarray(yarn.inv_freq(5e5, 64))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    extra = 5e5 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:9], extra[:9], rtol=1e-6)
    np.testing.assert_allclose(got[18:], extra[18:] / 128, rtol=1e-6)
    assert np.all(got[9:18] < extra[9:18]) \
        and np.all(got[9:18] > extra[9:18] / 128)
    assert YaRN(4.0, 32, 32.0, 1.0, 1.1).correction_range(500000.0, 8) \
        == (0, 1)


# -- the model against the reference ------------------------------------------

def test_float32_forward_picks_identical_logits_tight(f32_model,
                                                      f32_outputs):
    ids, logits, picks = f32_outputs
    r = ref.compare(logits, picks, params_of(f32_model), as_dict(f32_model),
                    ids)
    assert r["picks_agree"] == 1.0 and r["logits_err"] < 1e-5
    assert picks.shape == (4, N, 10)


def test_softmax_top10_routing_matches_the_reference_picks_and_weights():
    """softmax over the router's 32, the top 10, renormalised, x 2.5: the
    program's ``route_topk`` against the reference's router and against
    the rule written out here."""
    logits = jax.random.normal(jax.random.PRNGKey(3), (20, 32)) * 2.0
    bias = jnp.zeros((32,))
    idx, w = moe.route_topk(logits, bias, top_k=10, scoring="softmax",
                            scaling=2.5)
    c = ref.router_config(as_dict(build()))
    ridx, rw = ds_ref.route(logits, bias, c, ())
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.asarray(ridx), -1))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-6)
    p = np.asarray(jax.nn.softmax(logits, -1), np.float64)
    top = np.argsort(-p, -1)[:, :10]
    want = np.take_along_axis(p, top, -1)
    want = want / want.sum(-1, keepdims=True) * 2.5
    np.testing.assert_allclose(np.asarray(rw), want, rtol=1e-5)
    assert np.allclose(np.asarray(w).sum(-1), 2.5)


def test_bf16_forward_passes_and_an_8bit_reference_fails():
    model = build("bfloat16")
    ids = ids_of(64, seed=1)
    logits, picks = ref.model_outputs(model, ids)
    params, c = params_of(model), as_dict(model)
    r = ref.compare(logits, picks, params, c, ids)
    assert r["picks_agree"] >= ref.PICKS_TOLERANCE
    assert 1e-4 < r["logits_err"] < 0.03
    f8 = ref.compare(logits, picks, params, c, ids,
                     matmul_dtype=jnp.float8_e4m3fn)
    assert f8["logits_err"] > ref.LOGITS_TOLERANCE


@pytest.mark.parametrize("broken", ref.MUTATIONS)
def test_a_broken_reference_fails_the_comparison(broken, f32_model,
                                                 f32_outputs):
    ids, logits, picks = f32_outputs
    r = ref.compare(logits, picks, params_of(f32_model), as_dict(f32_model),
                    ids, mutate=(broken,))
    assert r["logits_err"] > ref.LOGITS_TOLERANCE \
        or r["picks_agree"] < ref.PICKS_TOLERANCE, r


def _plain_rope_on_full_layers(model):
    for layer in model.model.layers:
        if layer.kind == 0:
            layer.self_attn.yarn = None


def _no_gate(model):
    for layer in model.model.layers:
        layer.self_attn.has_gate = False


def _sigmoid_router(model):
    for layer in model.model.layers:
        if layer.is_moe:
            layer.mlp.route = dict(layer.mlp.route, scoring="sigmoid")


@pytest.mark.parametrize("broken", [_plain_rope_on_full_layers, _no_gate,
                                    _sigmoid_router],
                         ids=["plain_rope", "no_gate", "sigmoid_router"])
def test_a_broken_program_fails_the_comparison(broken):
    """The PROGRAM with plain RoPE on its full layers (YaRN's frequencies
    and factor gone), with the gate removed, or routing on sigmoid
    scores, against the true reference: each fails a limit."""
    model = build(seed=5)
    broken(model)
    ids = ids_of(seed=6)
    logits, picks = ref.model_outputs(model, ids)
    r = ref.compare(logits, picks, params_of(model), as_dict(model), ids)
    assert r["logits_err"] > ref.LOGITS_TOLERANCE \
        or r["picks_agree"] < ref.PICKS_TOLERANCE, r


def test_zeroing_the_gate_halves_every_heads_output(f32_model):
    """sigmoid(0) = 0.5: with ``W_g`` zeroed each head's output, and so the
    layer's attention output, is half of what it is without the gate, in
    the program and in the reference, for both kinds of layer."""
    c = as_dict(f32_model)
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 64))
    for i in (0, 1):
        attn = f32_model.model.layers[i].self_attn
        kept = attn.head_gate.weight._value
        try:
            attn.head_gate.weight._value = jnp.zeros_like(kept)
            half = np.asarray(attn(paddle.to_tensor(y))[0]._value)
            attn.has_gate = False
            whole = np.asarray(attn(paddle.to_tensor(y))[0]._value)
        finally:
            attn.head_gate.weight._value, attn.has_gate = kept, True
        np.testing.assert_allclose(half, 0.5 * whole, rtol=1e-5, atol=1e-6)
        w = ref.layer_weights(params_of(f32_model), i)
        w = dict(w, head_gate=jnp.zeros_like(w["head_gate"]))
        kind = c["layer_types"][i]
        cos, sin, rot = ref.rope_tables(24, c["rope_parameters"][kind], 16)
        window = 16 if kind == ref.SLIDING else None
        args = (ds_ref._Ops(), y[0], w, cos, sin,
                c["num_attention_heads_per_layer"][i], 2, 16, rot, window)
        with jax.default_matmul_precision("highest"):
            r_half = ref.attention(*args, (), 512)
            r_whole = ref.attention(*args, ("gate",), 512)
        np.testing.assert_allclose(np.asarray(r_half),
                                   0.5 * np.asarray(r_whole), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(half[0], np.asarray(r_half), atol=2e-5)


def test_two_shares_of_the_experts_add_up_to_the_whole_layer(f32_model):
    """Experts (0, 16) and (16, 16) of 32, the shared expert and the router
    counted once: their parts add up to the uncut layer, in the program's
    ``DroplessMoE`` and in the reference."""
    layer = f32_model.model.layers[1]
    full = layer.mlp
    y = jax.random.normal(jax.random.PRNGKey(2), (30, 64))
    wts = {k: getattr(full, k) for k in ("gate_proj", "up_proj",
                                         "down_proj")}

    def share(first):
        part = moe.DroplessMoE(64, 32, 32, 10, experts=(first, 16),
                               scoring="softmax", scaling=2.5)
        part.gate.weight._value = full.gate.weight._value
        for k, p in wts.items():
            getattr(part, k)._value = p._value[first:first + 16]
        return np.asarray(part(paddle.to_tensor(y))[0]._value)
    whole = np.asarray(full(paddle.to_tensor(y))[0]._value)
    np.testing.assert_allclose(share(0) + share(16), whole, atol=1e-5)
    w = ref.layer_weights(params_of(f32_model), 1)
    rc = ref.router_config(as_dict(f32_model))
    def held(first):        # what a chip holding the share stacks
        return dict(w, **{k: w[k][first:first + 16] for k in wts})
    with jax.default_matmul_precision("highest"):
        a, _ = ds_ref.experts(y, held(0), rc, (), None, (0, 16))
        b, _ = ds_ref.experts(y, held(16), rc, ("shared",), None, (16, 16))
        uncut, _ = ds_ref.experts(y, w, rc, (), None, (0, 32))
        shared = ds_ref._swiglu(y, w["shared_experts.gate_proj"],
                                w["shared_experts.up_proj"],
                                w["shared_experts.down_proj"], None)
    np.testing.assert_allclose(np.asarray(a) + np.asarray(b),
                               np.asarray(uncut), atol=1e-5)
    np.testing.assert_allclose(whole + np.asarray(shared),
                               np.asarray(uncut), atol=1e-4)


def test_a_held_share_is_the_references_share():
    model = build(experts_held=(8, 16))
    ids = ids_of(48, seed=3)
    logits, picks = ref.model_outputs(model, ids)
    r = ref.compare(logits, picks, params_of(model), as_dict(model), ids)
    assert r["picks_agree"] == 1.0 and r["logits_err"] < 1e-5
    assert model.model.layers[1].mlp.gate.weight.shape[-1] == 32
    assert model.model.layers[1].mlp.gate_proj.shape[0] == 16


# -- the cache with two groups of layers --------------------------------------

def test_chunked_paged_prefill_then_decode_matches_the_reference(f32_model):
    """96 tokens = six windows of 16 and three times YaRN's 32 original
    positions, in chunks of 16 through a ring of cdiv(15 + 16, 8) + 1 = 5
    blocks of 8, then 16 decode steps: every chunk's first and last token
    and every decode step agree with the reference's full forward."""
    ids = ids_of(seed=2)
    rows, got, picks = ref.cached_outputs(f32_model, ids, chunk=16,
                                          decode=16, block=8)
    assert rows[-1] == N - 1 and len(rows) == 2 * 5 + 16
    r = ref.compare(got, picks, params_of(f32_model), as_dict(f32_model),
                    ids, logits_at=rows)
    assert r["picks_agree"] == 1.0 and r["logits_err"] < 1e-5
    # past the window and past YaRN's original positions, both layer
    # kinds on the path: the broken references fail at exactly these rows
    for broken in ("yarn_factor", "window_plus"):
        bad = ref.compare(got, picks, params_of(f32_model),
                          as_dict(f32_model), ids, logits_at=rows,
                          mutate=(broken,))
        assert bad["logits_err"] > ref.LOGITS_TOLERANCE, broken


def test_cached_read_matches_the_forward_without_a_cache(f32_model):
    ids = ids_of(80, seed=4)
    rows, got, _ = ref.cached_outputs(f32_model, ids, chunk=32, decode=16,
                                      block=8)
    plain = np.asarray(f32_model(paddle.to_tensor(ids[None]))._value[0])
    np.testing.assert_allclose(got, plain[rows], rtol=2e-5, atol=2e-5)


def test_engine_serves_it_through_the_hybrid_paged_engine(monkeypatch):
    """``ContinuousBatchingEngine(paged=True)`` builds the two-group engine
    (a ring of cdiv(15 + 16, 8) + 1 = 5 blocks, a tail of 2); two streams
    at different depths, past the window and YaRN's original positions,
    through the packed kernels in interpret mode, emit the reference's
    own greedy tokens."""
    from paddle_tpu.serving import ContinuousBatchingEngine, Scheduler, Server
    from paddle_tpu.serving.hybrid import HybridPagedEngine
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    model = build(seed=7)
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=112,
                                   decode_block=4, paged=True, block_size=8,
                                   prefill_chunk=16)
    assert isinstance(eng, HybridPagedEngine)
    assert (eng.ring_blocks, eng.tail_blocks, eng.window) == (5, 2, 16)
    # [v 16 | k 16] padded to one lane tile, 8 tokens x 2 kv heads a page
    assert [tuple(a.shape) for a in eng._cache[:2]] == [
        (eng.num_kv_blocks, 16, 128), (eng.num_window_blocks, 16, 128)]
    srv = Server(eng, Scheduler())
    prompts = [ids_of(40, seed=8), ids_of(23, seed=9)]
    rids = [srv.submit(p, max_new_tokens=56 - 8 * i)
            for i, p in enumerate(prompts)]
    srv.run_until_idle()
    params, c = params_of(model), as_dict(model)
    for rid, p in zip(rids, prompts):
        out = np.asarray(srv.results[rid])
        logits, _ = ref.forward(params, c, out[:-1])
        want = np.argmax(np.asarray(logits), -1)[len(p) - 1:]
        assert np.array_equal(out[len(p):], want)
    assert eng.moe_picks > 0
    eng.manager.assert_consistent()
    eng.window_manager.assert_consistent()


# -- the packed kernels at Laguna's head counts --------------------------------

@pytest.mark.parametrize("heads,window", [(48, None), (72, 512)],
                         ids=["full_48", "sliding_72"])
def test_packed_kernels_take_48_and_72_heads_over_8(heads, window,
                                                    monkeypatch):
    """The walk scores all h query heads against every row of a chunk in
    one product: at h = 48 (groups of 6) and 72 (groups of 9) over 8 kv
    heads of 128, ``[v 128 | k 128]`` rows (two whole lane tiles, nothing
    padded), with no sink, the kernel (interpret mode) is the gathered
    read; the arena's page view tiles, so the chip reads it as it lies."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    kvh, bs, d, w, nb, mb = 8, 16, 128, 256, 100, 40
    k = jax.random.split(jax.random.PRNGKey(heads), 3)
    arena = jax.random.normal(k[0], (nb, bs * kvh, w))
    table = jax.random.permutation(k[1], jnp.arange(1, nb))[:2 * mb] \
        .reshape(2, mb).astype(jnp.int32)
    lengths = jnp.asarray([600, 37], jnp.int32)
    q = jnp.pad(jax.random.normal(k[2], (2, heads, d)),
                ((0, 0), (0, 0), (d, 0)))
    kw = dict(scale=d ** -0.5, kvh=kvh, dv=d)
    if window is None:
        got = pa.packed_paged_attention_decode(q, arena, table, lengths,
                                               **kw)
    else:
        got = pa.swa_paged_attention_decode(q, arena, table, lengths, None,
                                            window=window, **kw)
    want = pa.packed_paged_attention_reference(q[:, None], arena, table,
                                               lengths, window=window, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=2e-5, rtol=2e-5)
    assert pa._tiles((nb, bs, kvh, w), jnp.bfloat16)
