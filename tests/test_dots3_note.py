"""dots3-note-class model (latent attention of two geometries, a learned
indexer that picks the full layers' visible set, a ring of latent rows under
the sliding layers, a headwise gate, routed experts with a share held + one
shared expert) against its plain reference, at toy size on the CPU.

The model's forward without a cache, ``generate()`` and chunked paged
prefill + decode through BOTH groups' caches against
``benchmark/reference/dots3_note.py``; each broken variant of the reference
fails the same comparison; the selected set is the reference's in float32;
each new read (selected rows, windowed latent, index scores) against its
gathered reference, the kernels in interpret mode; the share test of the
experts; the hybrid engine over a leaf of two arenas (streams, a prefix hit
in both groups, where the index keys land); and what it refuses by name.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import dots3_note as ref
from paddle_tpu.models.dots3_note import (Dots3NoteForCausalLM,
                                          dots3_note_tiny_config)
from paddle_tpu.ops.pallas import fused
from paddle_tpu.ops.pallas import paged_attention as pa

VOCAB = 512
TOPK, WINDOW = 16, 9
TOY_LIMIT = 0.08      # between bf16 and the least broken variant, below


def as_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)


def build(dtype="float32", seed=0, **kw):
    """A seeded toy model, dense + full then F S S S; the selection bias
    and the index keys' LayerNorm bias are drawn non-zero, so that dropping
    either shows."""
    paddle.seed(seed)
    paddle.set_default_dtype(dtype)
    try:
        model = Dots3NoteForCausalLM(dots3_note_tiny_config(dtype=dtype, **kw))
    finally:
        paddle.set_default_dtype("float32")
    for i, (name, p) in enumerate(model.named_parameters()):
        if name.endswith("e_score_correction_bias"):
            p._value = 0.2 * jax.random.normal(jax.random.PRNGKey(i),
                                               p._value.shape)
        if name.endswith("idx_k_norm.bias"):
            p._value = 0.1 * jax.random.normal(
                jax.random.PRNGKey(i), p._value.shape).astype(p._value.dtype)
    model.eval()
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


def compare(model, outputs, **kw):
    ids, logits, picks, sel = outputs
    return ref.compare(logits, picks, sel, params_of(model),
                       as_dict(model.config), ids, **kw)


# -- the forward without a cache ----------------------------------------------

def test_float32_forward_is_the_reference(f32_model, f32_outputs):
    """float32 on both sides: the same expert picks, the same selected set
    at every row of both full layers, logits to rounding."""
    r = compare(f32_model, f32_outputs)
    assert r["picks_agree"] == 1.0
    assert r["selected_share"] == 1.0
    assert r["logits_err"] < 1e-5


def test_reference_in_blocks_and_head_groups_is_the_reference(
        f32_model, f32_outputs, monkeypatch):
    ids = f32_outputs[0][:64]
    p, c = params_of(f32_model), as_dict(f32_model.config)
    whole, picks = ref.forward(p, c, ids)
    parts, picks2 = ref.forward(p, c, ids, block=16, head_group=2)
    assert np.array_equal(picks, picks2)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    # the feed-forward half in row blocks (the last one short), the head in
    # column blocks, the reference held to given picks
    monkeypatch.setattr(ref, "FFN_ROWS", 24)
    monkeypatch.setattr(ref, "HEAD_COLS", 200)
    parts, picks3 = ref.forward(p, c, ids, block=16, head_group=2,
                                forced_picks=picks)
    assert np.array_equal(picks, picks3)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)


def test_the_selected_set_is_the_references(f32_model, f32_outputs):
    """Row t of a full layer selects min(t + 1, index_topk) tokens, all of
    them causal, and exactly the reference's."""
    ids, _, _, sel = f32_outputs
    want = []
    ref.forward(params_of(f32_model), as_dict(f32_model.config), ids,
                logits_at=[0], selections=want)
    assert len(sel) == len(want) == 2
    for (gi, gn), (wi, wn) in zip(sel, want):
        assert np.array_equal(gn, np.minimum(np.arange(70) + 1, TOPK))
        assert np.array_equal(gn, wn)
        for t in range(70):
            got = set(gi[t, :gn[t]].tolist())
            assert got == set(wi[t, :wn[t]].tolist())
            assert max(got) <= t and len(got) == gn[t]


def test_a_context_under_index_topk_selects_every_token(f32_model):
    """While t + 1 <= index_topk the selection is everything: the model
    with the indexer is the reference without one."""
    ids = ids_of(TOPK, seed=3)
    logits, picks, sel = ref.model_outputs(f32_model, ids)
    for gi, gn in sel:
        for t in range(TOPK):
            assert set(gi[t, :gn[t]].tolist()) == set(range(t + 1))
    want, _ = ref.forward(params_of(f32_model), as_dict(f32_model.config),
                          ids, forced_picks=picks, mutate=("indexer",))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_bf16_forward_passes_the_comparison():
    model = build("bfloat16")
    ids = ids_of(70)
    r = compare(model, (ids,) + ref.model_outputs(model, ids))
    assert r["logits_err"] < TOY_LIMIT and r["picks_agree"] > 0.9
    assert r["selected_share"] > 0.9


@pytest.mark.parametrize("broken", ref.MUTATIONS + ("float8",))
def test_a_broken_variant_fails_the_comparison(broken, f32_model,
                                               f32_outputs):
    """One published term at a time: each moves the logits by more than
    bf16 rounding does at this size (0.037-0.043 over three seeds; the
    least a broken variant reads is 0.13, the sliding layers' RoPE base)."""
    kw = {"matmul_dtype": jnp.float8_e4m3fn} if broken == "float8" \
        else {"mutate": (broken,)}
    assert compare(f32_model, f32_outputs, **kw)["logits_err"] > TOY_LIMIT


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(f32_model):
    """The guide's share test: the parts of an expert layer's result that
    all 4 shares of 2 experts give, the shared expert counted ONCE, add up
    to what the uncut reference gives for the whole layer."""
    from benchmark.reference.deepseek_v3 import experts, layer_weights
    from paddle_tpu.incubate.distributed.models.moe import DroplessMoE
    c = as_dict(f32_model.config)
    w = layer_weights(params_of(f32_model), 2)
    y = jax.random.normal(jax.random.PRNGKey(5), (24, c["hidden_size"]))
    route_c = ref.router_config(c)
    whole, idx = experts(y, w, route_c, held=(0, 8))
    shared, _ = experts(y, w, route_c, held=(0, 1), forced=np.full(
        (24, c["num_experts_per_tok"]), 7, np.int32))   # no pick lands
    total = np.asarray(shared)
    for first in range(0, 8, 2):
        layer = DroplessMoE(
            c["hidden_size"], c["moe_intermediate_size"], 8,
            c["num_experts_per_tok"], experts=(first, 2),
            norm_topk_prob=True, scaling=1.0)
        layer.gate.weight._value = w["gate"]
        layer.e_score_correction_bias._value = w["e_score_correction_bias"]
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(layer, name)._value = w[name][first:first + 2]
        part, picks, _ = layer(paddle.to_tensor(np.asarray(y)))
        assert np.array_equal(np.asarray(picks._value), np.asarray(idx))
        total = total + np.asarray(part._value)
    np.testing.assert_allclose(total, np.asarray(whole), rtol=2e-4,
                               atol=2e-5)


# -- the cached path -----------------------------------------------------------

@pytest.mark.parametrize("interpret", [False, True], ids=["gathered",
                                                          "kernels"])
def test_chunked_paged_prefill_then_decode_matches_the_reference(
        interpret, f32_model, monkeypatch):
    """70 tokens in chunks of 16 through a fresh two-group cache (the ring
    of cdiv(8 + 16, 8) + 1 = 4 blocks wraps, the selection drops most of
    the context), then 6 decode steps, against the reference's full
    forward: on the CPU lane, and with the kernels in interpret mode (the
    selection ``dsa_select_topk`` in the chunks and the steps, the three
    reads in the steps). Either way each row's ids come in ascending
    position order."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", interpret)
    ids = ids_of(70, seed=2)
    rows, got, picks, sel = ref.cached_outputs(f32_model, ids, chunk=16,
                                               decode=6, block=8)
    r = ref.compare(got, picks, sel, params_of(f32_model),
                    as_dict(f32_model.config), ids, logits_at=rows,
                    sel_rows=rows)
    assert r["picks_agree"] == 1.0 and r["selected_share"] == 1.0
    assert r["logits_err"] < 1e-5
    assert len(rows) == 2 * 4 + 6
    for ids_, _ in sel:
        assert (np.diff(ids_[:, :TOPK], axis=-1) > 0).all()


def test_a_ring_too_short_fails_the_cached_comparison(f32_model, monkeypatch):
    """The SYSTEM broken: a ring two blocks short lets a chunk's last block
    land on the start of its first token's window."""
    full = ref.ring_tables

    def short(n, block, window, chunk):
        row, fb, wb = full(n, block, window, chunk)
        cols = len(row) // 2
        ring = -(-(window - 1 + chunk) // block) - 1
        row[cols:] = 1 + np.arange(cols) % ring
        return row, fb, wb
    monkeypatch.setattr(ref, "ring_tables", short)
    ids = ids_of(70, seed=2)
    rows, got, picks, sel = ref.cached_outputs(f32_model, ids, chunk=16,
                                               decode=6, block=8)
    r = ref.compare(got, picks, sel, params_of(f32_model),
                    as_dict(f32_model.config), ids, logits_at=rows,
                    sel_rows=rows)
    assert r["logits_err"] > TOY_LIMIT


@pytest.mark.parametrize("broken", [None, "indexer", "relu", "topk",
                                    "float8"])
def test_the_timed_contexts_check_holds_the_selection_to_the_reference(
        broken, f32_model):
    """``reference.timed_context`` (what the cell adds to ``correct`` at
    the context it is timed at): a 96-token prompt (a 64-token "document"
    + a question) and the 12 tokens the model generates after it, teacher-
    forced through the cache path under a table WIDER than the sequence
    (the deployment's), against one forward of the reference. As it is, the
    logits and the selected set at the rows past the document are the
    reference's and every emitted token is its argmax; a reference with no
    indexer, no ReLU, half the ``index_topk`` or 8-bit products fails the
    logits, and one that selects another set fails the selected share."""
    prompt = ids_of(96, seed=5)
    row = np.asarray(f32_model.generate(paddle.to_tensor(prompt[None]),
                                        max_new_tokens=12)._value)[0]
    kw = {} if broken is None else {"matmul_dtype": jnp.float8_e4m3fn} \
        if broken == "float8" else {"mutate": (broken,)}
    r = ref.timed_context(f32_model, params_of(f32_model),
                          as_dict(f32_model.config), prompt, row[96:],
                          chunk=32, table_len=256, past=64, decode=8,
                          say=lambda m: None, block=16, head_group=2, **kw)
    # the rows past the document: two chunks' ends and the 8 decode steps
    assert r["tokens"] == 108 and r["rows"] == 2 * 2 + 8
    if broken is None:
        assert r["logits_err"] < 1e-5 and r["selected_share"] == 1.0
        assert r["below_max"] == 0.0 and r["same_argmax"] == 1.0
    else:
        assert r["logits_err"] > TOY_LIMIT
        # no indexer: no set to compare; half the index_topk: layer 0's
        # set lies inside the system's, so only the logits fail that one
        assert np.isnan(r["selected_share"]) if broken == "indexer" \
            else broken == "topk" or r["selected_share"] < 0.95


def test_generate_is_the_references_argmax(f32_model):
    prompt = ids_of(30, seed=4)
    out = f32_model.generate(paddle.to_tensor(prompt[None]),
                             max_new_tokens=12)
    row = np.asarray(out._value)[0]
    assert is_the_references_argmax(f32_model, prompt, row)


def is_the_references_argmax(model, prompt, row):
    logits, _ = ref.forward(params_of(model), as_dict(model.config), row[:-1])
    return np.array_equal(row[len(prompt):],
                          np.argmax(np.asarray(logits), -1)[len(prompt) - 1:])


# -- the three new reads against their gathered references ---------------------

def latent_inputs(lens, w=128, nb=60, mb=12, h=4, bs=8, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(len(lens) + w), 4)
    b = len(lens)
    arena = jax.random.normal(k[0], (nb, bs, w), dtype)
    table = jax.random.permutation(k[1], jnp.arange(1, nb))[:b * mb] \
        .reshape(b, mb).astype(jnp.int32)
    q = jax.random.normal(k[2], (b, h, w), dtype)
    return q, arena, table, jnp.asarray(lens, jnp.int32), k[3]


@pytest.mark.parametrize("chunk_rows", [16, 2048])
def test_window_latent_kernel_in_interpret_mode_matches_the_gathered_read(
        chunk_rows, monkeypatch):
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "_CHUNK_ROWS", chunk_rows)
    q, arena, table, lens, _ = latent_inputs([1, 9, 37, 96])
    kw = dict(scale=0.3, rank=96, window=13)
    got = pa.swa_mla_paged_attention_decode(q, arena, table, lens, **kw)
    want = pa.mla_paged_attention_reference(q[:, None], arena, table, lens,
                                            **kw)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_window_latent_gathered_read_is_the_dense_softmax():
    """The gathered windowed read (only the columns a window spans, in
    query blocks) against a dense softmax over the whole table."""
    q, arena, table, lens, key = latent_inputs([40, 96])
    s, window = 8, 13
    qs = jax.random.normal(key, (2, s, 4, 128))
    got = pa.mla_paged_attention_reference(qs, arena, table, lens, scale=0.3,
                                           rank=96, window=window, q_block=4)
    lat = arena[table].reshape(2, -1, 128)
    scores = jnp.einsum("bshw,btw->bhst", qs, lat) * 0.3
    i = (lens - s)[:, None] + jnp.arange(s)[None]
    j = jnp.arange(lat.shape[1])
    seen = (j[None, None] <= i[:, :, None]) & \
        (i[:, :, None] - j[None, None] < window)
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -jnp.inf), -1)
    want = jnp.einsum("bhst,btr->bshr", probs, lat[..., :96])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("chunk_rows", [16, 2048])
def test_index_scores_kernel_in_interpret_mode_matches_the_gathered_read(
        chunk_rows, monkeypatch):
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "_CHUNK_ROWS", chunk_rows)
    q, arena, table, lens, key = latent_inputs([1, 9, 37, 96], w=16, h=4)
    wts = jax.random.normal(key, (4, 4))
    got = pa.dsa_index_scores_decode(q, wts, arena, table, lens)
    want = pa.dsa_index_scores_reference(q[:, None], wts[:, None], arena,
                                         table)[:, 0]
    assert got.shape == want.shape == (4, 96)
    for r, n in enumerate([1, 9, 37, 96]):
        np.testing.assert_allclose(np.asarray(got)[r, :n],
                                   np.asarray(want)[r, :n], rtol=2e-5,
                                   atol=2e-5)


def test_index_scores_kernel_moves_runs_of_consecutive_blocks_whole(
        monkeypatch):
    """A table that names blocks in a row, rising or falling (a prefix
    prefilled into a fresh pool: the block manager pops its free list from
    the end), beside scattered ones, runs that start mid-group, and lengths
    that end inside a run: the same scores as the gathered read."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "_CHUNK_ROWS", 256)          # 32 pages a chunk
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    arena = jax.random.normal(k[0], (200, 8, 16))
    table = np.zeros((3, 40), np.int32)
    table[0] = 1 + np.arange(40)                         # one rising run
    table[1] = np.r_[50 + np.arange(13), 150, 120 + np.arange(26)]
    table[2] = 1 + np.random.RandomState(0).permutation(190)[:40]
    table = np.concatenate([table, table[:1]])
    table[3] = 199 - np.arange(40)       # falling: a free list popped
    lens = jnp.asarray([320, 203, 77, 300], jnp.int32)
    q = jax.random.normal(k[1], (4, 4, 16))
    wts = jax.random.normal(k[2], (4, 4))
    # the ONE predicate the kernel and the un-reversal both read: row 1's
    # runs start mid-group, row 2 is scattered, a group that is not wholly
    # live (rows 0 and 3 end inside their last) moves page by page
    assert np.asarray(pa._run_directions(jnp.asarray(table), lens, 8,
                                         8)).tolist() == [
        [1] * 5, [1, 0, 1, 0, 0], [0] * 5, [-1] * 4 + [0]]
    got = pa.dsa_index_scores_decode(q, wts, arena, jnp.asarray(table), lens)
    want = pa.dsa_index_scores_reference(q[:, None], wts[:, None], arena,
                                         jnp.asarray(table))[:, 0]
    for r, n in enumerate([320, 203, 77, 300]):
        np.testing.assert_allclose(np.asarray(got)[r, :n],
                                   np.asarray(want)[r, :n], rtol=2e-5,
                                   atol=2e-5)


def test_index_scores_in_key_blocks_are_the_definition():
    q, arena, table, _, key = latent_inputs([96, 96], w=16)
    s = 5
    qs = jax.random.normal(key, (2, s, 4, 16))
    wts = jax.random.normal(jax.random.PRNGKey(9), (2, s, 4))
    keys = arena[table].reshape(2, -1, 16)
    got = pa._index_scores(qs, wts, keys, key_block=32)
    want = jnp.einsum("bsht,bsh->bst", jnp.maximum(
        jnp.einsum("bshd,btd->bsht", qs, keys), 0), wts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("interpret", [False, True], ids=["gathered",
                                                          "kernel"])
def test_selected_read_attends_to_the_selected_rows_only(interpret,
                                                         monkeypatch):
    """The selected read (gathered; the decode kernel in interpret mode)
    against a dense softmax masked to the selected ids; a NaN in a row that
    no id names never reaches the output."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", interpret)
    q, arena, table, lens, key = latent_inputs([20, 96, 50])
    k = 16
    ids = jnp.stack([jax.random.permutation(jax.random.fold_in(key, r),
                                            int(n))[:k] if n >= k else
                     jnp.arange(k) for r, n in enumerate([20, 96, 50])])
    n_valid = jnp.asarray([k, k, 7], jnp.int32)
    ids = jnp.where(jnp.arange(k)[None] < n_valid[:, None], ids, 0)
    named = np.zeros((3, 96), bool)
    for r in range(3):
        named[r, np.asarray(ids[r, :n_valid[r]])] = True
    # poison every row of every slot's timeline that no id names
    tl = np.asarray(arena[table].reshape(3, 96, 128)).copy()
    blk, off = np.asarray(table)[:, np.arange(96) // 8], np.arange(96) % 8
    poisoned = np.asarray(arena).copy()
    for r in range(3):
        for t in np.nonzero(~named[r])[0]:
            if t != 0:
                poisoned[blk[r, t], off[t]] = np.nan
    poisoned = jnp.asarray(poisoned)
    kw = dict(scale=0.3, rank=96)
    if interpret:
        got = pa.dsa_sparse_mla_decode(q, poisoned, table, ids, n_valid, **kw)
    else:
        got = pa.dsa_sparse_mla_reference(
            q[:, None], poisoned, table, ids[:, None], n_valid[:, None],
            **kw)[:, 0]
    scores = jnp.einsum("bhw,btw->bht", q, tl) * 0.3
    probs = jax.nn.softmax(jnp.where(named[:, None], scores, -jnp.inf), -1)
    want = jnp.einsum("bht,btr->bhr", probs, tl[..., :96])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_selected_read_in_query_blocks_is_the_whole_read():
    q, arena, table, lens, key = latent_inputs([96, 96])
    s, k = 256, 16
    qs = jax.random.normal(key, (2, s, 4, 128))
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, s, k), 0, 96)
    n_valid = jax.random.randint(jax.random.PRNGKey(4), (2, s), 1, k + 1)
    kw = dict(scale=0.3, rank=96)
    whole = pa.dsa_sparse_mla_reference(qs, arena, table, ids, n_valid, **kw)
    parts = pa.dsa_sparse_mla_reference(qs, arena, table, ids, n_valid,
                                        q_block=128, **kw)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)


def test_the_cached_step_through_the_kernels_is_the_gathered_step(
        f32_model, monkeypatch):
    """A decode step of the whole model with all three kernels in interpret
    mode gives the gathered lane's logits and selections."""
    ids = ids_of(41, seed=11)
    outs = []
    for interpret in (False, True):
        monkeypatch.setattr(fused, "_FORCE_INTERPRET", interpret)
        rows, got, _, sel = ref.cached_outputs(f32_model, ids, chunk=16,
                                               decode=3, block=8)
        outs.append((got, sel))
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=2e-4, atol=2e-4)
    for (a, an), (b, bn) in zip(outs[0][1], outs[1][1]):
        assert np.array_equal(an, bn)
        assert np.array_equal(a, b)       # one order on both lanes


@pytest.mark.parametrize("interpret", [False, True], ids=["gathered",
                                                          "kernels"])
def test_a_decode_step_counts_its_live_rows_selected(interpret, f32_model,
                                                     monkeypatch):
    """A decode step of three slots, one dead (its table row all trash):
    the step counts the 2 live rows in each of the 2 full layers as
    ``dsa_rows_selected``, and as ``dsa_rows_kernel_selected`` where the
    kernel made the selection."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", interpret)
    row, fb, wb = ref.ring_tables(64, 8, WINDOW, 8)
    rows = np.stack([row, row, np.zeros_like(row)])
    rows[1, :len(row) // 2] += len(row) // 2        # another slot's blocks
    cache = f32_model.init_paged_kv_cache(2 * fb, 8, window_blocks=3 * wb)
    with paddle.no_grad():
        _, cache = f32_model(
            paddle.to_tensor(ids_of(3, seed=1)[:, None]), cache=cache,
            pos=paddle.to_tensor(np.asarray([30, 21, 0], np.int32)),
            block_table=paddle.to_tensor(rows))
    counts = np.asarray(cache["moe_counters"]._value)
    assert counts[0, 5:].tolist() == [4, 4 if interpret else 0]
    assert counts[1, 3:].tolist() == [0, 0, 0, 0]


# -- the hybrid engine over a leaf of two arenas -------------------------------

def engine_of(model, **kw):
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    args = dict(paged=True, num_slots=3, max_len=192, block_size=8,
                prefill_chunk=16, decode_block=4)
    args.update(kw)
    eng = ContinuousBatchingEngine(model, **args)
    return eng, Server(eng, Scheduler())


def test_engine_streams_are_generates_token_for_token(f32_model):
    """Three slots at different depths, contexts past the window and
    ``index_topk``: every stream is ``generate()``'s, the programs compile
    once, the pools are consistent, and the dsa counters count the live
    rows' contexts."""
    from paddle_tpu.serving.hybrid import HybridPagedEngine
    eng, srv = engine_of(f32_model)
    assert type(eng) is HybridPagedEngine
    assert eng.tail_blocks == 1 and eng.ring_blocks == 4
    be = eng.backend
    assert be.leaf_group == (0, 0, 0, 0, 1, 1, 1, None)
    prompts = [ids_of(n, seed=20 + n) for n in (21, 40, 33, 27)]
    news = [14, 9, 17, 11]
    rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    srv.run_until_idle()
    for p, n, rid in zip(prompts, news, rids):
        want = f32_model.generate(paddle.to_tensor(p[None]),
                                  max_new_tokens=n)
        assert np.array_equal(np.asarray(srv.results[rid]),
                              np.asarray(want._value)[0])
    assert eng.decode_compile_count() == 1
    assert eng.prefill_compile_count() == 1
    # a decode step of a live row at position p scores p + 1 tokens and
    # reads min(p + 1, 16), in each of the 2 full layers
    scored = sum(2 * (len(p) + j + 1) for p, n in zip(prompts, news)
                 for j in range(n - 1))
    assert eng.dsa_tokens_scored == scored
    assert eng.dsa_tokens_selected == 2 * TOPK * sum(n - 1 for n in news)
    stats = srv.stats()
    assert stats["dsa_tokens_scored"] == scored
    assert stats["dsa_tokens_selected"] == eng.dsa_tokens_selected
    assert eng.prefill_dsa_tokens_scored > 0
    eng.manager.assert_consistent()
    eng.window_manager.assert_consistent()
    assert not eng.manager._ref and not eng.window_manager._ref


def test_prefix_hit_in_both_groups_streams_as_a_cold_prefill(f32_model):
    """A hit needs every block of the full group (latent rows AND index
    keys lie under it) and the window group's tail: the third request of a
    prefix hits 64 tokens, prefills the rest and streams what a cold engine
    streams, which is the reference's argmax."""
    shared = ids_of(64, seed=6)
    prompts = [np.concatenate([shared, ids_of(n, seed=30 + n)])
               for n in (9, 13, 11)]
    eng, srv = engine_of(f32_model)
    rows, hits = [], []
    for p in prompts:
        rid = srv.submit(p, max_new_tokens=20)
        before = eng.shared_tokens
        srv.run_until_idle()
        rows.append(np.asarray(srv.results[rid]))
        hits.append(eng.shared_tokens - before)
    assert hits == [0, 0, 64]
    cold, cold_srv = engine_of(f32_model)
    rid = cold_srv.submit(prompts[2], max_new_tokens=20)
    cold_srv.run_until_idle()
    assert cold.shared_tokens == 0
    assert np.array_equal(rows[2], np.asarray(cold_srv.results[rid]))
    for p, row in zip(prompts, rows):
        assert is_the_references_argmax(f32_model, p, row)
    eng.manager.assert_consistent()
    eng.window_manager.assert_consistent()


def test_index_keys_land_under_the_latent_rows_block_and_block_0_is_trash(
        f32_model):
    """Mid-stream: in each full layer a written position holds a latent row
    and an index key at the SAME (block, offset), and nowhere else; no live
    run holds block 0 in either pool, so what dead slots and pad columns
    write there is never read."""
    eng, srv = engine_of(f32_model)
    prompt = ids_of(37, seed=12)
    srv.submit(prompt, max_new_tokens=30)
    for _ in range(4):
        srv.run_until_idle(max_ticks=1)
    (slot, run), = eng.live_runs()
    written = len(prompt) + len(run.tokens) - 1
    assert 0 not in run.block_ids and 0 not in run.window.block_ids
    groups = eng.backend.leaf_group
    full = [np.asarray(a) for a, g in zip(eng._cache, groups) if g == 0]
    assert len(full) == 4                  # 2 layers x (rows, keys)
    held = np.zeros(full[0].shape[:2], bool)
    for t in range(written):
        held[run.block_ids[t // 8], t % 8] = True
    for rows_, keys in (full[:2], full[2:]):
        assert rows_.shape[:2] == keys.shape[:2]
        has_row = np.abs(rows_).sum(-1) > 0
        has_key = np.abs(keys).sum(-1) > 0
        assert np.array_equal(has_row[1:], held[1:])
        assert np.array_equal(has_key[1:], held[1:])
    window = [np.asarray(a) for a, g in zip(eng._cache, groups) if g == 1]
    assert all(a.shape[0] == eng.num_window_blocks for a in window)
    srv.run_until_idle()


def test_what_the_sparse_latent_cache_cannot_do_refuses_by_name(f32_model,
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
    for build_ in (lambda: PagedEngine(f32_model, 2, 64, block_size=8),
                   lambda: PrefillPagedEngine(f32_model, 2, 64,
                                              block_size=8)):
        with pytest.raises(NotImplementedError, match="HybridPagedEngine"):
            build_()
    with pytest.raises(NotImplementedError, match="int8"):
        f32_model.init_paged_kv_cache(4, 8, kv_int8=True, window_blocks=4)
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
    with pytest.raises(ValueError, match="q_lora_rank"):
        from paddle_tpu.models.deepseek_v3 import (DeepseekV3Attention,
                                                   deepseek_v3_tiny_config)
        DeepseekV3Attention(deepseek_v3_tiny_config(), indexer={
            "n_heads": 2, "head_dim": 8, "topk": 4})


@pytest.mark.parametrize("interpret", [False, True], ids=["gathered",
                                                          "kernels"])
def test_a_chunks_pad_blocks_are_skipped_and_its_real_rows_unchanged(
        interpret, f32_model, monkeypatch):
    """``valid_len``: of a right-padded chunk's rows, the blocks wholly past
    the real columns skip the selected read (blocks of 128) and, in the
    kernel, the selection (blocks of 8: zeros there); the real rows'
    logits, the cache they leave and the selections are what they are
    without it, and the chunk's dsa counters count the real columns only:
    each one's row selected in each full layer, by the kernel where it
    runs."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", interpret)
    ids = np.zeros((1, 256), np.int32)
    ids[0, :100] = ids_of(100, seed=13)
    row, fb, wb = ref.ring_tables(256 + 64, 8, WINDOW, 256)
    table = paddle.to_tensor(row[None])
    pos = paddle.to_tensor(np.zeros(1, np.int32))
    outs = []
    with paddle.no_grad():
        for valid in (None, paddle.to_tensor(np.int32(100))):
            cache = f32_model.init_paged_kv_cache(fb, 8, window_blocks=wb)
            logits, cache, sel = f32_model(
                paddle.to_tensor(ids), cache=cache, pos=pos,
                block_table=table, output_selections=True, valid_len=valid)
            outs.append((np.asarray(logits._value)[0],
                         [np.asarray(i._value)[0] for i, _ in sel],
                         np.asarray(cache["moe_counters"]._value)))
    (whole, sel_w, count_w), (cut, sel_c, count_c) = outs
    np.testing.assert_allclose(cut[:100], whole[:100], rtol=1e-5, atol=1e-5)
    for a, c_ in zip(sel_w, sel_c):
        assert np.array_equal(a[:100], c_[:100])      # the real rows
        if interpret:                                 # blocks of 8 skipped
            assert not c_[104:].any() and a[104:].any()

    # row 1 (chunks): scored = sum of contexts, selected = sum of min(., 16),
    # rows selected, of them by the kernel; over 256 columns without
    # valid_len and over the 100 real ones with it
    def want(n):
        return [2 * sum(range(1, n + 1)),
                2 * sum(min(t, TOPK) for t in range(1, n + 1)),
                2 * n, 2 * n if interpret else 0]
    assert count_w[1, 3:].tolist() == want(256)
    assert count_c[1, 3:].tolist() == want(100)
