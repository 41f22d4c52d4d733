"""DeepSeek-V3-class model (latent attention cache + dropless routed
experts) against its plain reference, at toy size on the CPU.

The reference (``benchmark/reference/deepseek_v3.py``) is itself pinned
against ``transformers.DeepseekV3ForCausalLM`` on the same weights, so the
equations are checked by an implementation nobody here wrote. Then: the
model's forward (float32: identical picks, tight logits; bf16: the
two-part comparison the benchmark's ``correct`` uses), chunked paged prefill
and decode through the latent arena, the absorbed cached read against the
expanded one, the latent kernel in interpret mode, the expert layer
dropless and shareable, a prefix hit on the latent arena, and the counters
the programs carry out.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import deepseek_v3 as ref
from paddle_tpu.incubate.distributed.models.moe import (DroplessMoE,
                                                        dropless_expert_mix,
                                                        route_topk)
from paddle_tpu.models.deepseek_v3 import (DeepseekV3ForCausalLM,
                                           deepseek_v3_tiny_config)
from paddle_tpu.ops.pallas import fused
from paddle_tpu.ops.pallas import paged_attention as pa

VOCAB = 512


def as_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)


def build(dtype="float32", seed=0, **kw):
    """A seeded toy model; the selection bias is drawn non-zero, as the
    benchmark's builder draws it, so that dropping it changes picks (wider
    here: eight scores lie further apart than 128 do)."""
    paddle.seed(seed)
    paddle.set_default_dtype(dtype)
    try:
        model = DeepseekV3ForCausalLM(deepseek_v3_tiny_config(dtype=dtype,
                                                              **kw))
    finally:
        paddle.set_default_dtype("float32")
    for i, (name, p) in enumerate(model.named_parameters()):
        if name.endswith("e_score_correction_bias"):
            p._value = 0.2 * jax.random.normal(jax.random.PRNGKey(i),
                                                p._value.shape)
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
    ids = ids_of(48)
    return (ids,) + ref.model_outputs(f32_model, ids)


# -- the reference itself, against an implementation nobody here wrote ------

def hf_to_params(state: dict, cfg) -> dict:
    """A ``transformers`` DeepseekV3 state dict under this repo's
    parameter names: Linear weights transposed to (in, out), a layer's
    experts stacked, the shared experts beside ``mlp``."""
    out = {}
    moe = {}
    for name, t in state.items():
        v = np.asarray(t.detach().numpy(), np.float32)
        if ".mlp.experts." in name:
            layer, rest = name.split(".mlp.experts.")
            j, proj, _ = rest.split(".")
            moe.setdefault((layer, proj), {})[int(j)] = v.T
            continue
        name = name.replace(".mlp.shared_experts.", ".shared_experts.") \
            .replace(".mlp.gate.e_score", ".mlp.e_score")
        out[name] = v.T if v.ndim == 2 and "embed_tokens" not in name else v
    for (layer, proj), by_j in moe.items():
        out[f"{layer}.mlp.{proj}"] = np.stack(
            [by_j[j] for j in range(cfg.n_routed_experts)])
    return out


@pytest.mark.parametrize("n_group,topk_group", [(1, 1), (4, 2)])
def test_reference_agrees_with_transformers(n_group, topk_group):
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    if not hasattr(tf, "DeepseekV3ForCausalLM"):
        pytest.skip("this transformers has no DeepseekV3ForCausalLM")
    cfg = deepseek_v3_tiny_config(n_group=n_group, topk_group=topk_group,
                                  rope_theta=1e6)
    c = as_dict(cfg)
    torch.manual_seed(0)
    hf = tf.DeepseekV3ForCausalLM(tf.DeepseekV3Config(
        num_key_value_heads=cfg.num_attention_heads, rope_scaling=None,
        attention_bias=False, hidden_act="silu",
        **{k: c[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "n_group", "topk_group",
            "norm_topk_prob", "routed_scaling_factor",
            "max_position_embeddings", "rms_norm_eps", "rope_theta",
            "rope_interleave")})).eval().float()
    with torch.no_grad():
        for name, p in hf.named_parameters():
            if p.ndim == 2:              # the default init is 0.02: dull
                p.normal_(0.0, 0.12 if "embed" not in name else 1.0)
        for name, b in hf.named_buffers():
            if name.endswith("e_score_correction_bias"):
                b.normal_(0.0, 0.05)
    ids = ids_of(40, seed=3)
    with torch.no_grad():
        want = hf(torch.tensor(ids[None].astype(np.int64))).logits[0].numpy()
    got, _ = ref.forward(hf_to_params(hf.state_dict(), cfg), c, ids)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_reference_in_blocks_is_the_reference(f32_model):
    """Attention in blocks of query rows over a sequence padded to whole
    blocks (how the long timed-path sequences fit and share programs) is
    the same forward; ``logits_at`` picks rows of it."""
    ids = ids_of(70, seed=9)
    params, c = params_of(f32_model), as_dict(f32_model.config)
    whole, picks = ref.forward(params, c, ids)
    blocks, picks_b = ref.forward(params, c, ids, block=32,
                                  logits_at=[5, 69])
    assert np.array_equal(picks, picks_b) and picks.shape == (2, 70, 3)
    np.testing.assert_allclose(np.asarray(blocks),
                               np.asarray(whole)[[5, 69]], atol=1e-5)


# -- the model's forward against the reference ------------------------------

def test_float32_forward_picks_identical_logits_tight(f32_model,
                                                      f32_outputs):
    ids, logits, picks = f32_outputs
    r = ref.compare(logits, picks, params_of(f32_model),
                    as_dict(f32_model.config), ids)
    assert r["picks_agree"] == 1.0
    assert r["logits_err"] < 1e-5


def test_bf16_forward_passes_the_two_part_comparison():
    model = build("bfloat16")
    ids = ids_of(64, seed=1)
    logits, picks = ref.model_outputs(model, ids)
    r = ref.compare(logits, picks, params_of(model), as_dict(model.config),
                    ids)
    assert r["picks_agree"] >= ref.PICKS_TOLERANCE
    assert 1e-4 < r["logits_err"] <= ref.LOGITS_TOLERANCE


@pytest.mark.parametrize("broken", ref.MUTATIONS + ("float8",))
def test_a_dropped_term_or_a_coarser_precision_fails(broken, f32_model,
                                                     f32_outputs):
    """Each published term taken out of the reference (so the float32
    model now DIFFERS from it by exactly that term), and every product
    rounded through an 8-bit float, fails one of the two limits."""
    ids, logits, picks = f32_outputs
    kw = {"matmul_dtype": jnp.float8_e4m3fn} if broken == "float8" \
        else {"mutate": (broken,)}
    r = ref.compare(logits, picks, params_of(f32_model),
                    as_dict(f32_model.config), ids, **kw)
    assert r["logits_err"] > ref.LOGITS_TOLERANCE \
        or r["picks_agree"] < ref.PICKS_TOLERANCE, r
    if broken == "bias":           # only the CHOICE moves: (a) catches it
        assert r["logits_err"] < 1e-5


# -- the latent arena: chunked prefill, decode, absorbed vs expanded --------

def paged_logits(model, ids, chunk, table, num_blocks=9, block_size=8):
    """``ids`` through the cache path as the engine drives it: chunks of
    ``chunk`` tokens written through ``table`` into a fresh latent arena,
    then one token at a time; returns the logits of every position."""
    cache = model.init_paged_kv_cache(num_blocks, block_size)
    tbl = paddle.to_tensor(np.asarray(table, np.int32)[None])
    out, done, n_prefill = [], 0, len(ids) - 6
    while done < len(ids):
        n = min(chunk, n_prefill - done) if done < n_prefill else 1
        lg, cache = model(paddle.to_tensor(ids[None, done:done + n]),
                          cache=cache, block_table=tbl,
                          pos=paddle.to_tensor(np.asarray([done], np.int32)))
        out.append(np.asarray(lg._value[0]))
        done += n
    return np.concatenate(out), cache


def test_chunked_paged_prefill_then_decode_matches_the_reference(f32_model):
    ids = ids_of(45, seed=2)
    got, cache = paged_logits(f32_model, ids, chunk=16,
                              table=[3, 1, 7, 5, 2, 8, 0, 0])
    want, _ = ref.forward(params_of(f32_model), as_dict(f32_model.config),
                          ids)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-4)
    # what the programs counted: 2 expert layers x 3 picks a token, the
    # six s = 1 calls in row 0 and the chunks in row 1
    counters = np.asarray(cache["moe_counters"]._value)
    assert counters[:, 0].tolist() == [6 * 2 * 3, 39 * 2 * 3]
    assert 1 <= counters[0, 2] <= 3 and counters[0, 1] >= 2


def test_absorbed_cached_read_matches_the_expanded_forward(f32_model):
    """The cache path absorbs ``W_kvb`` into q and the output; the
    forward without a cache expands k_nope and v for every token. Same
    logits, prefill rows (s > 1) and decode rows (s = 1) alike."""
    ids = ids_of(40, seed=4)
    absorbed, _ = paged_logits(f32_model, ids, chunk=32,
                               table=[1, 2, 3, 4, 5, 6, 0, 0])
    expanded = np.asarray(f32_model(paddle.to_tensor(ids[None]))._value[0])
    np.testing.assert_allclose(absorbed, expanded, rtol=2e-5, atol=2e-5)


def latent_inputs(dtype=jnp.float32, w=128, rank=96):
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    arena = jax.random.normal(k[0], (12, 8, w), dtype)
    q = jax.random.normal(k[1], (3, 4, w), dtype)
    tbl = jnp.asarray([[1, 2, 3, 4, 5], [6, 7, 0, 0, 0], [8, 9, 10, 11, 0]],
                      jnp.int32)
    lens = jnp.asarray([40, 11, 27], jnp.int32)
    return q, arena, tbl, lens, rank


# the second geometry: a sliding layer's of ``models/dots3_note.py``, whose
# row is wider than its value by the same 64 (1,152 = 1,024 + 64 + pad there)
@pytest.mark.parametrize("w,rank", [(128, 96), (384, 320)],
                         ids=["row128", "row384"])
@pytest.mark.parametrize("window", [None, 13], ids=["full", "window13"])
@pytest.mark.parametrize("chunk_rows", [8, 16, 2048])
def test_latent_kernel_in_interpret_mode_matches_the_gathered_read(
        chunk_rows, window, w, rank, monkeypatch):
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "_CHUNK_ROWS", chunk_rows)
    q, arena, tbl, lens, rank = latent_inputs(w=w, rank=rank)
    assert pa._kernel_ok(arena)
    if window is None:
        out = pa.mla_paged_attention_decode(q, arena, tbl, lens, scale=0.1,
                                            rank=rank)
        kw = {}
    else:
        out = pa.swa_mla_paged_attention_decode(
            q, arena, tbl, lens, scale=0.1, rank=rank, window=window)
        kw = {"window": window}
    want = pa.mla_paged_attention_reference(q[:, None], arena, tbl, lens,
                                            scale=0.1, rank=rank, **kw)[:, 0]
    assert out.shape == (3, 4, rank)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_another_slots_nan_never_leaks_through_the_latent_chunk_buffer(
        monkeypatch):
    """As ``test_another_slots_nan_never_leaks_through_a_chunk_buffer``:
    slot 0's five pages are NaN, and slot 1's two live pages land in the
    buffer that last held them. Here K and V are ONE buffer, so its dead
    places are zeroed whole."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "_CHUNK_ROWS", 32)          # 4 pages a chunk
    q, arena, tbl, _, rank = latent_inputs()
    lens = jnp.asarray([40, 11, 27], jnp.int32)
    arena = arena.at[1:6].set(jnp.nan)
    out = np.asarray(pa.mla_paged_attention_decode(q, arena, tbl, lens,
                                                   scale=0.1, rank=rank))
    want = np.asarray(pa.mla_paged_attention_reference(
        q[:, None], arena, tbl, lens, scale=0.1, rank=rank)[:, 0])
    assert np.isnan(out[0]).all() and np.isfinite(out[1:]).all()
    np.testing.assert_allclose(out[1:], want[1:], atol=1e-5)


def test_latent_arena_routes_by_what_tiles(monkeypatch):
    monkeypatch.setattr(fused, "_on_tpu", lambda: True)
    ok = lambda shape, dt=jnp.bfloat16: pa._kernel_ok(      # noqa: E731
        jax.ShapeDtypeStruct(shape, dt))
    assert ok((16385, 16, 640))                # the served latent arena
    assert ok((64, 8, 640), jnp.float32)
    assert not ok((64, 16, 576))               # 4.5 lane tiles a row
    assert not ok((64, 8, 640))                # half a bf16 sublane group
    cfg = deepseek_v3_tiny_config(kv_lora_rank=512, qk_rope_head_dim=64)
    assert (cfg.latent_width, cfg.latent_row) == (576, 640)


# -- the expert layer --------------------------------------------------------

def expert_weights(e=8, h=16, ff=12, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (e, h, ff)) * 0.3,
            jax.random.normal(k[1], (e, h, ff)) * 0.3,
            jax.random.normal(k[2], (e, ff, h)) * 0.3,
            jax.random.normal(k[3], (10, h)))


def dense_mix(x, idx, w, wg, wu, wd):
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j, e in enumerate(np.asarray(idx[t])):
            out[t] += float(w[t, j]) * np.asarray(
                (jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wu[e])) @ wd[e])
    return out


def test_expert_layer_is_dropless_when_every_token_picks_one_expert():
    wg, wu, wd, x = expert_weights()
    idx = jnp.full((10, 3), 5, jnp.int32)       # all 30 picks on expert 5
    w = jnp.full((10, 3), 0.4)
    y, stats = dropless_expert_mix(x, idx, w, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), dense_mix(x, idx, w, wg, wu,
                                                        wd), atol=1e-5)
    assert stats.tolist() == [30, 1, 30]        # nothing dropped


@pytest.mark.parametrize("experts,per_share,shared", [(8, 4, True),
                                                      (16, 1, False)])
def test_shares_of_the_experts_add_up_to_the_uncut_layer(experts, per_share,
                                                         shared):
    """Layers holding experts 0-3 and 4-7 each compute their own experts'
    part over the full router; with the shared experts counted once the
    parts equal the uncut reference layer. And with NO shared expert (a
    model that has none): the 16 shares of one expert each add up to it."""
    model = build(num_hidden_layers=2, n_routed_experts=experts)
    c = as_dict(model.config)
    w = ref.layer_weights(params_of(model), 1)
    y = jax.random.normal(jax.random.PRNGKey(1), (24, c["hidden_size"]))
    whole, picks = ref.experts(y, w, c, () if shared else ("shared",))
    routed = 0
    for first in range(0, experts, per_share):
        layer = DroplessMoE(c["hidden_size"], c["moe_intermediate_size"],
                            experts, 3, experts=(first, per_share),
                            scaling=2.448)
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(layer, name)._value = w[name][first:first + per_share]
        layer.gate.weight._value = w["gate"]
        layer.e_score_correction_bias._value = w["e_score_correction_bias"]
        part, idx, stats = layer(paddle.to_tensor(y))
        assert np.array_equal(np.asarray(idx._value), np.asarray(picks))
        held = (np.asarray(picks) >= first) \
            & (np.asarray(picks) < first + per_share)
        assert int(stats._value[0]) == held.sum()
        # the reference, given the same share, computes the same part
        mine = {**w, **{n: w[n][first:first + per_share] for n in (
            "gate_proj", "up_proj", "down_proj")}}
        same, _ = ref.experts(y, mine, c, ("shared",),
                              held=(first, per_share))
        np.testing.assert_allclose(np.asarray(part._value),
                                   np.asarray(same), atol=1e-5)
        routed = routed + part._value
    if shared:
        routed = routed + ref.swiglu(
            ref._Ops(), y, w["shared_experts.gate_proj"],
            w["shared_experts.up_proj"], w["shared_experts.down_proj"])
    np.testing.assert_allclose(np.asarray(routed), np.asarray(whole),
                               atol=1e-5)


def test_router_matches_the_reference_with_groups():
    c = dict(num_experts_per_tok=3, n_group=4, topk_group=2,
             routed_scaling_factor=2.5, norm_topk_prob=True)
    logits = jax.random.normal(jax.random.PRNGKey(2), (50, 16))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    idx, w = route_topk(logits, bias, top_k=3, n_group=4, topk_group=2,
                        scaling=2.5)
    want_idx, want_w = ref.route(logits, bias, c, ())
    assert np.array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(want_w, -1),
                               rtol=1e-6)


# -- through the engine -------------------------------------------------------

def serve(model, prompts, new=6, **kw):
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    eng = ContinuousBatchingEngine(model, paged=True, num_slots=2,
                                   max_len=96, block_size=8,
                                   prefill_chunk=16, decode_block=4, **kw)
    srv = Server(eng, Scheduler())
    out = []
    for p in prompts:                 # one at a time: the second may hit
        rid = srv.submit(p, max_new_tokens=new)
        srv.run_until_idle()
        out.append(np.asarray(srv.results[rid]))
    return eng, out


def test_engine_stream_is_the_references_argmax_and_counts_its_picks(
        f32_model):
    prompt = ids_of(37, seed=5)
    eng, (row,) = serve(f32_model, [prompt], new=7)
    logits, _ = ref.forward(params_of(f32_model), as_dict(f32_model.config),
                            row[:-1])
    assert np.array_equal(row[len(prompt):],
                          np.argmax(np.asarray(logits), -1)[len(prompt) - 1:])
    assert eng.decode_compile_count() == eng.prefill_compile_count() == 1
    # counters: 2 slots x 2 expert layers x 3 picks a decode step; the
    # chunk program's three 16-token windows apart
    assert eng.moe_picks == eng.steps * 2 * 2 * 3
    assert eng.prefill_moe_picks == 3 * 16 * 2 * 3
    assert 0 < eng.moe_expert_hits <= eng.moe_picks
    assert 1 <= eng.moe_max_load <= 2
    from paddle_tpu.observability import tracing
    spans = [s for s in tracing.since(0) if s.name == "serving.decode_block"]
    assert {"kv_pages_live", "kv_pages_copied", "moe_picks",
            "moe_expert_hits", "moe_max_load"} <= set(spans[-1].ids)
    chunk = [s for s in tracing.since(0)
             if s.name == "serving.prefill_chunk"][-1]
    assert chunk.ids["moe_picks"] == 3 * 16 * 2 * 3 and chunk.ids["chunks"] == 3


def test_prefix_hit_on_the_latent_arena_streams_as_a_cold_prefill(
        f32_model):
    shared = ids_of(40, seed=6)
    first = np.concatenate([shared, ids_of(9, seed=7)])
    second = np.concatenate([shared, ids_of(13, seed=8)])
    warm, (_, hit) = serve(f32_model, [first, second])
    assert warm.shared_tokens == 40         # five whole blocks of eight
    cold, (alone,) = serve(f32_model, [second])
    assert cold.shared_tokens == 0
    assert np.array_equal(hit, alone)
    logits, _ = ref.forward(params_of(f32_model), as_dict(f32_model.config),
                            hit[:-1])
    assert np.array_equal(
        hit[len(second):],
        np.argmax(np.asarray(logits), -1)[len(second) - 1:])
