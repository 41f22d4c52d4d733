"""MiMo-V2-Flash-class model (sliding-window layers with a sink mixed with
full layers, K wider than V, routed experts with a share held) against its
plain reference, at toy size on the CPU.

The reference's attention (``benchmark/reference/mimo_v2.py``) is itself
pinned against ``transformers``' ``gpt_oss`` eager attention (a sink column
and a sliding window, code nobody here wrote). Then: the model's forward
(float32: identical picks, tight logits; bf16: the two-part comparison the
benchmark's ``correct`` uses; each broken variant fails it), chunked paged
prefill and decode through BOTH groups' caches with the ring wrapped, the
packed kernels in interpret mode, the hybrid engine (a prefix hit in both
groups, bounded window arenas, both pools consistent after a churn), and
what the hybrid cache refuses by name.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import mimo_v2 as ref
from paddle_tpu.models.mimo_v2 import (MiMoV2ForCausalLM,
                                       mimo_v2_tiny_config)
from paddle_tpu.ops.pallas import fused
from paddle_tpu.ops.pallas import paged_attention as pa

VOCAB = 512


def as_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)


def build(dtype="float32", seed=0, **kw):
    """A seeded toy model over all three forms of layer; the sinks and the
    selection bias are drawn non-zero, as the benchmark's builder draws
    them, so that dropping either shows."""
    paddle.seed(seed)
    paddle.set_default_dtype(dtype)
    try:
        model = MiMoV2ForCausalLM(mimo_v2_tiny_config(dtype=dtype, **kw))
    finally:
        paddle.set_default_dtype("float32")
    for i, (name, p) in enumerate(model.named_parameters()):
        if name.endswith("attention_sink_bias"):
            p._value = jax.random.normal(jax.random.PRNGKey(i),
                                         p._value.shape)
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
    ids = ids_of(70)
    return (ids,) + ref.model_outputs(f32_model, ids)


# -- the reference's attention, against code nobody here wrote ---------------

def test_reference_attention_agrees_with_transformers_gpt_oss():
    """``gpt_oss``'s eager attention has the same two mechanisms: a learned
    per-head sink that joins the softmax as one more column and is then
    dropped, and a sliding-window mask. Same q, k, v, sinks and band: the
    reference's window attention (RoPE on no dims, value scale 1) is that
    function. An oracle for one function, not a configuration."""
    torch = pytest.importorskip("torch")
    oss = pytest.importorskip("transformers.models.gpt_oss.modeling_gpt_oss")
    s, heads, kvh, dk, window = 40, 8, 4, 16, 9
    c = dict(num_attention_heads=heads, head_dim=dk, v_head_dim=dk,
             swa_num_key_value_heads=kvh, num_key_value_heads=kvh,
             swa_rope_theta=1e4, rope_theta=1e4, partial_rotary_factor=0.0,
             sliding_window=window, attention_value_scale=1.0)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    y = jax.random.normal(k[0], (s, 32))
    w = {"q_proj": jax.random.normal(k[1], (32, heads * dk)) * 0.3,
         "k_proj": jax.random.normal(k[2], (32, kvh * dk)) * 0.3,
         "v_proj": jax.random.normal(k[3], (32, kvh * dk)) * 0.3,
         "o_proj": jnp.eye(heads * dk),
         "attention_sink_bias": jax.random.normal(k[4], (heads,))}
    got = ref.attention(ref._Ops(), y, w, c, ref.WINDOW, (), block=512)

    def t(a, n):                         # (s, n * dk) -> (1, n, s, dk)
        return torch.tensor(np.asarray(a)).reshape(s, n, dk) \
            .permute(1, 0, 2)[None]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    band = np.where((j <= i) & (i - j < window), 0.0, -np.inf)
    module = torch.nn.Module()
    module.num_key_value_groups = heads // kvh
    module.sinks = torch.tensor(np.asarray(w["attention_sink_bias"]))
    module.training = False
    want, _ = oss.eager_attention_forward(
        module, t(y @ w["q_proj"], heads), t(y @ w["k_proj"], kvh),
        t(y @ w["v_proj"], kvh),
        torch.tensor(band, dtype=torch.float32)[None, None],
        scaling=dk ** -0.5, dropout=0.0)
    np.testing.assert_allclose(np.asarray(got),
                               want[0].reshape(s, heads * dk).numpy(),
                               rtol=2e-5, atol=2e-5)


# -- the model's forward against the reference ------------------------------

def test_float32_forward_picks_identical_logits_tight(f32_model,
                                                      f32_outputs):
    """Seven layers: dense + full, experts + window (x 5), experts + full."""
    cfg = f32_model.config
    assert (cfg.hybrid_layer_pattern, cfg.moe_layer_freq) == (
        (0, 1, 1, 1, 1, 0, 1), (0, 1, 1, 1, 1, 1, 1))
    ids, logits, picks = f32_outputs
    r = ref.compare(logits, picks, params_of(f32_model), as_dict(cfg), ids)
    assert r["picks_agree"] == 1.0
    assert r["logits_err"] < 1e-5


def test_reference_in_blocks_is_the_reference(f32_model, f32_outputs):
    ids = f32_outputs[0]
    params, c = params_of(f32_model), as_dict(f32_model.config)
    whole, picks = ref.forward(params, c, ids)
    blocks, picks_b = ref.forward(params, c, ids, block=32,
                                  logits_at=[5, 69])
    assert np.array_equal(picks, picks_b) and picks.shape == (6, 70, 3)
    np.testing.assert_allclose(np.asarray(blocks),
                               np.asarray(whole)[[5, 69]], atol=1e-5)


def test_bf16_forward_passes_the_two_part_comparison():
    model = build("bfloat16")
    ids = ids_of(64, seed=1)
    logits, picks = ref.model_outputs(model, ids)
    r = ref.compare(logits, picks, params_of(model), as_dict(model.config),
                    ids)
    # the limits are the chip's readings at the published widths (0.0054-
    # 0.0059); a 64-wide bf16 model rounds coarser (0.009), still a ninth
    # of the least a broken variant reads at this size
    assert r["picks_agree"] >= ref.PICKS_TOLERANCE
    assert ref.LOGITS_TOLERANCE < 0.0108 and 1e-4 < r["logits_err"] < 0.02


@pytest.mark.parametrize("broken", ref.MUTATIONS + ("float8",))
def test_a_broken_variant_fails_the_comparison(broken, f32_model,
                                               f32_outputs):
    """Each published term changed in the reference (no sink, a window of
    127 or 129 — here 15 or 17 —, the other kind's RoPE base, rotary on
    every dim, no value scale, no selection bias), so that the float32
    model now DIFFERS from it by exactly that term, and every product
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


def test_a_share_of_the_experts_is_the_references_share(f32_model):
    """The model told it holds experts 2-4 of 8 computes that part over
    the full router, as the reference given the same share does."""
    model = build(experts_held=(2, 3))
    ids = ids_of(40, seed=3)
    logits, picks = ref.model_outputs(model, ids)
    c = as_dict(model.config)
    r = ref.compare(logits, picks, params_of(model), c, ids)
    assert r["picks_agree"] == 1.0 and r["logits_err"] < 1e-5
    whole = ref.compare(logits, picks, params_of(model),
                        dict(c, experts_held=None), ids,
                        experts_held=(2, 3))
    assert whole["logits_err"] < 1e-5
    assert model.model.layers[1].mlp.gate.weight.shape[-1] == 8
    assert model.model.layers[1].mlp.gate_proj.shape[0] == 3


@pytest.mark.parametrize("few_rows", [0, 128],
                         ids=["grouped", "every_row_on_every_expert"])
def test_rows_of_experts_not_held_are_selected_out_not_multiplied(
        monkeypatch, few_rows):
    """A grouped matmul defines only the rows inside its groups. On the
    CPU the rows past the last group come out zero; on a TPU they hold
    whatever the buffer held, now and then a NaN, and a pick of an expert
    not held lies there. With a share held the mix SELECTS those rows out
    (0 x NaN is NaN: found on the chip, where one stream in a few hundred
    was poisoned). Both forms of a share's mix, the grouped one (more than
    ``FEW_ROWS`` tokens) and every row on every expert held (a decode
    step), give the plain sum and the same counters."""
    from paddle_tpu.incubate.distributed.models import moe
    monkeypatch.setattr(moe, "FEW_ROWS", few_rows)
    real = jax.lax.ragged_dot

    def undefined_past_the_groups(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes)
        inside = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
        return jnp.where(inside[:, None], out, jnp.nan)
    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_past_the_groups)
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(k[0], (10, 16))
    wg, wu = (jax.random.normal(k[i], (3, 16, 12)) * 0.3 for i in (1, 2))
    wd = jax.random.normal(k[3], (3, 12, 16)) * 0.3
    idx = jax.random.randint(k[4], (10, 3), 0, 8)       # of 8; 2-4 held
    w = jax.random.uniform(k[5], (10, 3))
    y, stats = moe.dropless_expert_mix(x, idx, w, wg, wu, wd, first=2,
                                       partial=True)
    want = np.zeros((10, 16), np.float32)
    for t in range(10):
        for j, e in enumerate(np.asarray(idx[t])):
            if 2 <= e < 5:
                want[t] += float(w[t, j]) * np.asarray(
                    (jax.nn.silu(x[t] @ wg[e - 2]) * (x[t] @ wu[e - 2]))
                    @ wd[e - 2])
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    on_held = np.asarray((idx >= 2) & (idx < 5))
    loads = [int((np.asarray(idx) == e).sum()) for e in (2, 3, 4)]
    assert [int(v) for v in stats] == [int(on_held.sum()),
                                       sum(n > 0 for n in loads), max(loads)]
    leaky, _ = moe.dropless_expert_mix(x, idx, w, wg, wu, wd, first=2)
    assert np.isnan(np.asarray(leaky)).any()     # what all-held never meets
    layer = moe.DroplessMoE(16, 12, 8, 3, experts=(2, 3))
    assert layer.partial and not moe.DroplessMoE(16, 12, 8, 3).partial


# -- the cache with two groups of layers -------------------------------------

def test_chunked_paged_prefill_then_decode_matches_the_reference(f32_model):
    """150 tokens = nine windows of 16, through a ring of cdiv(15 + 16, 8)
    + 1 = 5 blocks of 8 that wraps three times: every chunk's first and
    last token and every decode step agree with the reference's full
    forward."""
    ids = ids_of(150, seed=2)
    rows, got, picks = ref.cached_outputs(f32_model, ids, chunk=16,
                                          decode=12, block=8)
    assert len(rows) == 2 * 9 + 12 and rows[-1] == 149
    assert rows[:4].tolist() == [0, 15, 16, 31]
    table, _, window_blocks = ref.ring_tables(150 + 16, 8, 16, 16)
    assert window_blocks == 6 and table[len(table) // 2:].max() == 5
    r = ref.compare(got, picks, params_of(f32_model),
                    as_dict(f32_model.config), ids, logits_at=rows)
    assert r["picks_agree"] == 1.0 and r["logits_err"] < 1e-5


def test_a_ring_too_short_fails_the_cached_comparison(f32_model, monkeypatch):
    """A ring two blocks short of ``ring_tables``' own: a chunk's last block
    lands on the block that holds the start of its first token's window.
    The comparison sees it at the chunks' first tokens."""
    real = ref.ring_tables

    def short(*a):
        row, fb, wb = real(*a)
        cols = len(row) // 2
        row[cols:] = 1 + np.arange(cols) % (wb - 1 - 2)
        return row, fb, wb
    monkeypatch.setattr(ref, "ring_tables", short)
    ids = ids_of(150, seed=2)
    rows, got, picks = ref.cached_outputs(f32_model, ids, chunk=16,
                                          decode=12, block=8)
    r = ref.compare(got, picks, params_of(f32_model),
                    as_dict(f32_model.config), ids, logits_at=rows)
    assert r["logits_err"] > 1e-2


def test_cached_read_matches_the_forward_without_a_cache(f32_model):
    ids = ids_of(60, seed=4)
    rows, got, _ = ref.cached_outputs(f32_model, ids, chunk=32, decode=20,
                                      block=8)
    plain = np.asarray(f32_model(paddle.to_tensor(ids[None]))._value[0])
    np.testing.assert_allclose(got, plain[rows], rtol=2e-5, atol=2e-5)


def test_generate_reads_both_groups_through_one_block_a_row(f32_model):
    ids = ids_of(30, seed=5)
    out = np.asarray(f32_model.generate(paddle.to_tensor(ids[None]),
                                        max_new_tokens=6)._value[0])
    logits, _ = ref.forward(params_of(f32_model),
                            as_dict(f32_model.config), out[:-1])
    assert np.array_equal(out[30:], np.argmax(np.asarray(logits), -1)[29:])


# -- the packed kernels, interpret mode ---------------------------------------

def packed_inputs(kvh, lens, dtype=jnp.float32, nb=40, mb=20, h=16):
    """K 192 / V 128 in one 384-wide row; random tables into 39 blocks."""
    bs, dv, dk, w = 16, 128, 192, 384
    k = jax.random.split(jax.random.PRNGKey(kvh), 3)
    arena = jax.random.normal(k[0], (nb, bs * kvh, w), dtype)
    arena = arena.at[..., dv + dk:].set(0)
    q = jnp.pad(jax.random.normal(k[1], (len(lens), h, dk), dtype),
                ((0, 0), (0, 0), (dv, w - dv - dk)))
    tbl = jnp.asarray(np.random.default_rng(0).integers(
        1, nb, (len(lens), mb)), jnp.int32)
    return q, arena, tbl, jnp.asarray(lens, jnp.int32), \
        jax.random.normal(k[2], (h,))


@pytest.mark.parametrize("kvh", [4, 8])
@pytest.mark.parametrize("chunk_rows", [128, 2048])
def test_packed_kernels_in_interpret_mode_match_the_gathered_read(
        kvh, chunk_rows, monkeypatch):
    """Lengths below, at and above the window of 128 and across page edges
    (127, 128, 129, 144, 145), one token, and several chunks of pages."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "_CHUNK_ROWS", chunk_rows)
    lens = [40, 127, 128, 129, 144, 145, 300, 1]
    q, arena, tbl, lengths, sinks = packed_inputs(kvh, lens)
    assert pa._kernel_ok(arena)
    kw = dict(scale=192 ** -0.5, kvh=kvh, dv=128)
    full = pa.packed_paged_attention_decode(q, arena, tbl, lengths, **kw)
    want = pa.packed_paged_attention_reference(q[:, None], arena, tbl,
                                               lengths, **kw)[:, 0]
    assert full.shape == (len(lens), 16, 128)
    np.testing.assert_allclose(np.asarray(full), np.asarray(want),
                               atol=2e-6)
    for s in (sinks, None):
        swa = pa.swa_paged_attention_decode(q, arena, tbl, lengths, s,
                                            window=128, **kw)
        want = pa.packed_paged_attention_reference(
            q[:, None], arena, tbl, lengths, window=128, sinks=s, **kw)[:, 0]
        np.testing.assert_allclose(np.asarray(swa), np.asarray(want),
                                   atol=2e-6)


def test_packed_gathered_read_is_the_dense_softmax_with_a_sink_column():
    """The gathered read itself against a softmax written out: the sink is
    one more column; under the window only the last 128 keys are seen, and
    only the columns a window spans are gathered (a table whose other
    columns name block 0 gives the same answer)."""
    kvh, lens = 4, [300, 129, 17]
    q, arena, tbl, lengths, sinks = packed_inputs(kvh, lens)
    got = np.asarray(pa.packed_paged_attention_reference(
        q[:, None], arena, tbl, lengths, scale=0.07, kvh=kvh, dv=128,
        window=128, sinks=sinks)[:, 0])
    rows = np.asarray(arena)[np.asarray(tbl)].reshape(len(lens), -1, kvh,
                                                      384)
    for b, n in enumerate(lens):
        lo = max(0, n - 128)
        for h in range(16):
            kh = h // 4
            a = rows[b, lo:n, kh, 128:320] @ np.asarray(q)[b, h, 128:320] \
                * 0.07
            a = np.concatenate([a, [float(sinks[h])]])
            p = np.exp(a - a.max())
            p /= p.sum()
            np.testing.assert_allclose(
                got[b, h], p[:-1] @ rows[b, lo:n, kh, :128], atol=2e-5)
    first = (np.asarray(lens) - 128).clip(0) // 16
    holed = np.asarray(tbl).copy()
    for b, f in enumerate(first):
        holed[b, :f] = 0
    again = pa.packed_paged_attention_reference(
        q[:, None], arena, jnp.asarray(holed), lengths, scale=0.07, kvh=kvh,
        dv=128, window=128, sinks=sinks)[:, 0]
    assert np.array_equal(np.asarray(again), got)


def test_another_slots_nan_never_leaks_through_the_packed_chunk_buffer(
        monkeypatch):
    """Slot 0's pages are NaN and its read is NaN; slots 1 and 2 land in
    the chunk buffers that last held them, and read finite values, in the
    full walk and in the window walk (whose first page lies mid-table)."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "_CHUNK_ROWS", 256)          # 4 pages a chunk
    q, arena, _, _, sinks = packed_inputs(4, [0] * 3)
    tbl = jnp.asarray([list(range(1, 21)), list(range(21, 39)) + [0, 0],
                       [39] + [0] * 19], jnp.int32)
    lengths = jnp.asarray([310, 200, 11], jnp.int32)
    arena = arena.at[1:21].set(jnp.nan)
    kw = dict(scale=0.07, kvh=4, dv=128)
    for got, ref_kw in (
            (pa.packed_paged_attention_decode(q, arena, tbl, lengths, **kw),
             {}),
            (pa.swa_paged_attention_decode(q, arena, tbl, lengths, sinks,
                                           window=128, **kw),
             dict(window=128, sinks=sinks))):
        got = np.asarray(got)
        want = np.asarray(pa.packed_paged_attention_reference(
            q[:, None], arena, tbl, lengths, **kw, **ref_kw)[:, 0])
        assert np.isnan(got[0]).all() and np.isfinite(got[1:]).all()
        np.testing.assert_allclose(got[1:], want[1:], atol=2e-6)


def test_walk_counts_mirror_the_window():
    """Under a window a slot's walk covers the pages from the one holding
    its oldest visible token to its last: at most cdiv(127, 16) + 1 = 9."""
    lens = [0, 1, 16, 127, 128, 129, 144, 145, 7000, 9000]
    live, copied, chunks = pa.walk_counts(lens, 512, 16, ppc=16, window=128)
    each = [pa.walk_counts([n], 512, 16, window=128)[0] for n in lens]
    assert each == [0, 1, 1, 8, 8, 9, 8, 9, 9, 8]
    assert (live, copied, chunks) == (sum(each), sum(each) + 1, len(lens))
    assert pa.walk_counts(lens, 512, 16)[0] == sum(
        -(-min(n, 8192) // 16) for n in lens)
    # the window starts where the length is clamped, as the kernel's does
    assert pa.walk_counts([9000], 512, 16, window=128)[0] == 8


def test_packed_arena_routes_by_what_tiles(monkeypatch):
    monkeypatch.setattr(fused, "_on_tpu", lambda: True)
    ok = lambda shape, dt=jnp.bfloat16: pa._kernel_ok(      # noqa: E731
        jax.ShapeDtypeStruct(shape, dt))
    assert ok((28673, 16 * 4, 384))            # the served full arena
    assert ok((3201, 16 * 8, 384))             # the served window arena
    assert not ok((64, 16 * 4, 320))           # 2.5 lane tiles a row
    from paddle_tpu.models.mimo_v2 import MiMoV2Config
    cfg = MiMoV2Config(num_hidden_layers=7)
    assert (cfg.kv_row, cfg.rotary_dim) == (384, 64)
    assert cfg.hybrid_layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert MiMoV2Config().hybrid_layer_pattern.count(1) == 39


# -- through the hybrid engine -------------------------------------------------

def engine_of(model, **kw):
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    args = dict(paged=True, num_slots=3, max_len=192, block_size=8,
                prefill_chunk=16, decode_block=4)
    args.update(kw)
    eng = ContinuousBatchingEngine(model, **args)
    return eng, Server(eng, Scheduler())


def is_the_references_argmax(model, prompt, row):
    logits, _ = ref.forward(params_of(model), as_dict(model.config), row[:-1])
    return np.array_equal(row[len(prompt):],
                          np.argmax(np.asarray(logits), -1)[len(prompt) - 1:])


def test_engine_serves_slots_at_different_depths(f32_model):
    """Three requests of different lengths decode side by side (each slot
    its own depth in both tables, every ring wrapped): each stream is the
    reference's argmax; the programs compiled once; the spans carry each
    group's walk."""
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving.hybrid import HybridPagedEngine
    eng, srv = engine_of(f32_model)
    assert isinstance(eng, HybridPagedEngine)
    assert (eng.tail_blocks, eng.ring_blocks) == (2, 5)
    prompts = [ids_of(n, seed=20 + n) for n in (21, 77, 50)]
    rids = [srv.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, (60, 30, 45))]
    srv.run_until_idle()
    for p, rid in zip(prompts, rids):
        assert is_the_references_argmax(f32_model, p,
                                        np.asarray(srv.results[rid]))
    assert eng.decode_compile_count() == eng.prefill_compile_count() == 1
    span = [s for s in tracing.since(0)
            if s.name == "serving.decode_block"][-1]
    assert {"kv_pages_live", "kv_pages_copied", "window_kv_pages_live",
            "window_kv_pages_copied", "kv_rows_live", "window_kv_rows_live",
            "moe_picks", "moe_expert_hits"} <= set(span.ids)
    assert 0 < eng.window_kv_rows_live < eng.kv_rows_live
    assert eng.window_kv_pages_live < eng.kv_pages_live
    assert eng.moe_picks == eng.steps * 3 * 6 * 3       # all experts held
    eng.manager.assert_consistent()
    eng.window_manager.assert_consistent()
    assert not eng.manager._ref and not eng.window_manager._ref


def test_prefix_hit_streams_as_a_cold_prefill_and_leaves_only_the_tail(
        f32_model):
    """The first request of a prefix is cold; the second finds the full
    group's chain but no tail in the window group: cold too, and it sets
    the tail's two blocks aside; the third and fourth HIT in both groups
    (64 tokens = 8 blocks), prefill only the rest, and stream what a cold
    engine streams. What the window pool retains of the prefix is its
    tail, not its eight blocks."""
    from paddle_tpu.observability import tracing
    shared = ids_of(64, seed=6)
    prompts = [np.concatenate([shared, ids_of(n, seed=30 + n)])
               for n in (9, 13, 11, 7)]
    eng, srv = engine_of(f32_model)
    rows, hits = [], []
    for p in prompts:                  # one at a time: a later one may hit
        rid = srv.submit(p, max_new_tokens=24)
        before = eng.shared_tokens
        srv.run_until_idle()
        rows.append(np.asarray(srv.results[rid]))
        hits.append(eng.shared_tokens - before)
    assert hits == [0, 0, 64, 64]
    admits = [s.ids for s in tracing.since(0)
              if s.name == "serving.admit"][-4:]
    assert [a["shared_window_blocks"] for a in admits] == [0, 0, 2, 2]
    # a cold ring of 5; + the 2 blocks set aside; after a hit only the
    # columns past the cut cycle over the ring
    assert [a["window_blocks"] for a in admits] == [5, 7, 5, 4]
    assert admits[2]["fresh_blocks"] == -(-(75 + 23) // 8) - 8
    cold, cold_srv = engine_of(f32_model)
    rid = cold_srv.submit(prompts[2], max_new_tokens=24)
    cold_srv.run_until_idle()
    assert cold.shared_tokens == 0
    assert np.array_equal(rows[2], np.asarray(cold_srv.results[rid]))
    for p, row in zip(prompts, rows):
        assert is_the_references_argmax(f32_model, p, row)
    # the prefix's tail: two registered blocks with a hit tally, retained;
    # the other retained blocks are the four streams' last windows
    wm = eng.window_manager
    chain = eng.manager.chain(shared, 8)
    tail = [wm.block_of(d, c) for d, c in chain]
    assert tail[:6] == [None] * 6 and None not in tail[6:]
    assert [wm._hits.get(b) for b in tail[6:]] == [2, 2]
    assert len(wm._cached) == 2 + 4 * 2 and not wm._ref
    assert len(eng.manager._cached) > 8 + 4       # every prefix block
    eng.manager.assert_consistent()
    wm.assert_consistent()


@pytest.fixture(scope="module", params=["mimo_v2", "dots3_note"])
def family(request, f32_model):
    """The two model families the hybrid engine serves: packed
    grouped-query arenas (this file's), and latent arenas whose full
    layers' leaf is a pair under one block id (``models/dots3_note.py``).
    ``(model, is the reference's argmax?)``."""
    if request.param == "mimo_v2":
        return f32_model, is_the_references_argmax
    import test_dots3_note as other
    return other.build(), other.is_the_references_argmax


def test_a_continued_conversation_hits_on_the_retired_rings_tail(family):
    """A stream's last window is still in its ring when it retires: the
    tail before its last full block is registered as it is, so a request
    that continues the conversation hits in both groups."""
    f32_model, is_the_references_argmax = family
    eng, srv = engine_of(f32_model)
    first = ids_of(45, seed=8)
    rid = srv.submit(first, max_new_tokens=28)
    srv.run_until_idle()
    said = np.asarray(srv.results[rid])
    follow = np.concatenate([said, ids_of(10, seed=9)])
    rid = srv.submit(follow, max_new_tokens=12)
    srv.run_until_idle()
    assert eng.shared_tokens == (45 + 27) // 8 * 8
    assert is_the_references_argmax(f32_model, follow,
                                    np.asarray(srv.results[rid]))


def test_window_arenas_do_not_grow_with_max_len(f32_model):
    """No arena leaf of a window layer depends on ``max_len``: a slot's
    ring is cdiv(window - 1 + longest write, block) + 1 blocks."""
    from paddle_tpu.serving.hybrid import HybridPagedStepBackend
    shapes = {}
    for max_len in (64, 512):
        eng, _ = engine_of(f32_model, max_len=max_len)
        be = eng.backend
        assert isinstance(be, HybridPagedStepBackend)
        shapes[max_len] = [spec for spec, group in
                           zip(be.pool_specs, be.leaf_group) if group == 1]
        full = [spec[0][0] for spec, group in
                zip(be.pool_specs, be.leaf_group) if group == 0]
        assert full == [1 + 3 * (max_len // 8)] * 2
        assert be.init_state()["table"].shape == (3, 2 * (max_len // 8))
    assert shapes[64] == shapes[512]
    assert len(shapes[64]) == 5
    assert all(shape == (1 + 3 * (5 + 2 * 2), 8 * 4, 128)
               for shape, _ in shapes[64])


@pytest.fixture(scope="module")
def hybrid_lives(f32_model):
    """Four requests behind one 64-token prefix, one at a time (cold; the
    one that sets the tail aside; two hits), then one that continues the
    last stream: what each life hashed and what it left in each index."""
    from paddle_tpu.observability import tracing
    shared = ids_of(64, seed=6)
    prompts = [np.concatenate([shared, ids_of(n, seed=30 + n)])
               for n in (9, 13, 11, 7)]
    eng, srv = engine_of(f32_model)
    fm, wm = eng.manager, eng.window_manager
    lives = []
    for i in range(5):
        p = prompts[i] if i < 4 else np.concatenate(
            [lives[3]["row"], ids_of(6, seed=77)])
        hashed, matched = fm.hashed_blocks, eng.shared_tokens
        rid = srv.submit(p, max_new_tokens=24)
        srv.run_until_idle()
        admit = [s.ids for s in tracing.since(0)
                 if s.name == "serving.admit"][-1]
        lives.append(dict(
            prompt=p, row=np.asarray(srv.results[rid]),
            hashed=fm.hashed_blocks - hashed, admit=admit,
            matched=(eng.shared_tokens - matched) // 8,
            stats=srv.stats()["hashed_blocks"], total=fm.hashed_blocks,
            by_window=wm.hashed_blocks, index=dict(fm._index),
            window_index=dict(wm._index)))
    fm.assert_consistent(), wm.assert_consistent()
    return lives


@pytest.mark.parametrize("i,matched,hashed_at_admission", [
    (0, 0, 1), (1, 0, 9), (2, 8, 9), (3, 8, 8), (4, 11, 12)],
    ids=["cold", "sets_the_tail_aside", "hit", "hit_to_the_last_block",
         "continues"])
def test_hybrid_engine_hashes_a_block_once_for_both_groups(
        hybrid_lives, i, matched, hashed_at_admission):
    """``hashed_blocks`` rises by the blocks a request wrote or matched,
    once each, and the window group registers (the tail set aside at the
    end of prefill, the ring's tail at retirement) from the SAME list:
    its manager hashes nothing, and every digest it holds is the full
    group's digest of that block."""
    from paddle_tpu.serving.paging import _sha1_chain
    life = hybrid_lives[i]
    row = life["row"]
    assert life["matched"] == matched
    assert life["hashed"] == (len(row) - 1) // 8
    assert life["admit"]["hashed_blocks"] == hashed_at_admission
    assert life["stats"] == life["total"] and life["by_window"] == 0
    # the per-token fold of the written sequence, a reference kept here
    want, parent = [], b""
    for j in range((len(row) - 1) // 8):
        chunk = tuple(int(t) for t in row[j * 8:(j + 1) * 8])
        parent = _sha1_chain(parent, chunk)
        want.append((parent, chunk))
    index, window_index = life["index"], life["window_index"]
    assert all(index[d][1] == chunk for d, chunk in want)
    held = [j for j, (d, chunk) in enumerate(want)
            if d in window_index and window_index[d][1] == chunk]
    # the ring's tail before the last written block; from the request that
    # set it aside on, the prefix's tail (blocks 6 and 7 of 8); and under
    # the continuation, the tail the stream it continues left
    tails = [8 if i else 0, len(want)]
    if i == 4:
        tails.insert(1, (len(hybrid_lives[3]["row"]) - 1) // 8)
    assert held == [j for n in tails for j in range(max(n - 2, 0), n)]
    assert set(window_index) <= set(index)


def test_both_pools_are_consistent_after_a_churn(family):
    """Admissions, retirements and evictions in BOTH pools: small pools,
    two prefixes, requests that hit, miss, set a tail aside and wait for
    blocks; after every tick both managers' accounting holds, every
    stream is the reference's argmax, and nothing is left held."""
    f32_model, is_the_references_argmax = family
    eng, srv = engine_of(f32_model, num_blocks=40, window_blocks=20,
                         max_len=128)
    prefixes = [ids_of(32, seed=40), ids_of(32, seed=41)]
    rng = np.random.default_rng(0)
    prompts = [np.concatenate([prefixes[i % 2],
                               ids_of(int(rng.integers(3, 20)), seed=50 + i)])
               for i in range(14)]
    rids = [srv.submit(p, max_new_tokens=int(rng.integers(4, 30)))
            for p in prompts]
    ticks = 0
    while srv.scheduler.pending() or eng.has_live():
        srv.run_until_idle(max_ticks=1)
        eng.manager.assert_consistent()
        eng.window_manager.assert_consistent()
        blocks, tokens = eng.window_kv_resident()
        assert blocks <= 3 * (eng.ring_blocks + eng.tail_blocks) + 2 * 2
        ticks += 1
        assert ticks < 400
    for p, rid in zip(prompts, rids):
        assert is_the_references_argmax(f32_model, p,
                                        np.asarray(srv.results[rid]))
    stats = srv.stats()
    assert stats["window_block_evictions"] == eng.window_manager.evictions \
        > 0
    assert stats["block_evictions"] > 0 and eng.shared_tokens >= 32 * 6
    assert not eng.manager._ref and not eng.window_manager._ref


def test_what_the_hybrid_cache_cannot_do_refuses_by_name(f32_model,
                                                         tmp_path):
    from paddle_tpu.serving import ContinuousBatchingEngine
    from paddle_tpu.serving.fleet import DecodeWorker, PrefillPagedEngine
    from paddle_tpu.serving.paging import PagedEngine
    from paddle_tpu.serving.spec import SpecConfig
    from paddle_tpu.serving.tp import TPConfig
    kw = dict(paged=True, num_slots=2, max_len=64, block_size=8)
    for extra, word in ((dict(kv_int8=True), "kv_int8"),
                        (dict(spec=SpecConfig(k=2)), "speculative"),
                        (dict(tp=TPConfig(mode="exact", mesh=object())),
                         "tensor-parallel")):
        with pytest.raises(NotImplementedError, match=word):
            ContinuousBatchingEngine(f32_model, **extra, **kw)
    # a backend that knows one pool: the model itself says where to go
    for build in (lambda: PagedEngine(f32_model, 2, 64, block_size=8),
                  lambda: PrefillPagedEngine(f32_model, 2, 64,
                                             block_size=8)):
        with pytest.raises(NotImplementedError, match="HybridPagedEngine"):
            build()
    eng, srv = engine_of(f32_model)
    with pytest.raises(NotImplementedError, match="hand-off"):
        DecodeWorker(eng)
    with pytest.raises(NotImplementedError, match="snapshot"):
        eng.snapshot(str(tmp_path / "s.npz"))
    with pytest.raises(NotImplementedError, match="preemption"):
        eng.preempt_slot(0)
    rid = srv.submit(ids_of(20), max_new_tokens=12)
    srv.run_until_idle(max_ticks=1)
    assert not eng.can_resume(eng.live_runs()[0][1])
    srv.run_until_idle()
    assert len(srv.results[rid]) == 32
    small = ContinuousBatchingEngine(f32_model, window_blocks=4, **kw)
    with pytest.raises(ValueError, match="window-group"):
        small.validate_request(20, 4)
