"""The routed experts of a decode step on a model that holds all of them:
the Pallas kernel ``moe_hit_experts_decode`` (interpret mode on the CPU)
against the grouped form it replaces there and against a float32 plain
sum; which form ``dropless_expert_mix`` takes; and a toy latent-MoE
model's greedy stream through the engine with the kernel on the path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models import moe
from paddle_tpu.ops.pallas import fused
from paddle_tpu.ops.pallas import moe_experts as me

E, H, K = 8, 16, 3


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)


def weights(e=E, h=H, ff=12, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple((jax.random.normal(k[i], s) * 0.3).astype(dtype)
                 for i, s in enumerate(((e, h, ff), (e, h, ff),
                                        (e, ff, h))))


def plain_sum(x, idx, w, wg, wu, wd):
    """float32, token by token, pick by pick."""
    x, wg, wu, wd = (np.asarray(a, np.float32) for a in (x, wg, wu, wd))
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j, e in enumerate(np.asarray(idx[t])):
            g, u = x[t] @ wg[e], x[t] @ wu[e]
            out[t] += float(w[t, j]) * ((g / (1 + np.exp(-g)) * u) @ wd[e])
    return out


def distinct_picks(t, e=E, k=K, seed=1, experts=None):
    """``k`` distinct experts a token, drawn from ``experts`` (all by
    default), as a router picks them."""
    rs = np.random.RandomState(seed)
    pool = np.arange(e) if experts is None else np.asarray(experts)
    return jnp.asarray(np.stack([rs.permutation(pool)[:k]
                                 for _ in range(t)]), jnp.int32)


ROUTINGS = {
    "one_row": lambda: distinct_picks(1),
    "seven_rows": lambda: distinct_picks(7),
    "few_rows": lambda: distinct_picks(moe.FEW_ROWS),
    # experts 3 .. 7 never hit: not read, and nothing of theirs is added
    "experts_never_hit": lambda: distinct_picks(10, experts=[0, 1, 2]),
    "every_pick_on_one_expert": lambda: jnp.full((10, K), 5, jnp.int32),
}


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_kernel_is_the_grouped_form_and_the_plain_sum(routing, interpret):
    idx = ROUTINGS[routing]()
    t = idx.shape[0]
    wg, wu, wd = weights()
    x = jax.random.normal(jax.random.PRNGKey(2), (t, H))
    w = jax.random.uniform(jax.random.PRNGKey(3), (t, K))
    y, stats = me.moe_hit_experts_decode(x, idx, w, wg, wu, wd)
    grouped, grouped_stats = moe._grouped_expert_mix(x, idx, w, wg, wu, wd,
                                                     0, False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(grouped),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), plain_sum(x, idx, w, wg, wu,
                                                        wd), atol=1e-5)
    assert stats.tolist() == grouped_stats.tolist()
    loads = np.bincount(np.asarray(idx).ravel(), minlength=E)
    assert stats.tolist() == [t * K, int((loads > 0).sum()),
                              int(loads.max())]


def test_an_ff_axis_in_several_tiles_adds_up(monkeypatch, interpret):
    """ff 384 in three tiles of 128 when a whole expert's blocks do not
    fit the weight budget: the same sum."""
    monkeypatch.setattr(me, "_WEIGHT_VMEM", 6 * H * 128 * 4)
    assert me._ff_tile(H, 384, 4) == 128 and me._ff_tile(H, 256, 4) == 128
    wg, wu, wd = weights(ff=384)
    idx = distinct_picks(9)
    x = jax.random.normal(jax.random.PRNGKey(2), (9, H))
    w = jax.random.uniform(jax.random.PRNGKey(3), (9, K))
    y, _ = me.moe_hit_experts_decode(x, idx, w, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), plain_sum(x, idx, w, wg, wu,
                                                        wd), atol=1e-5)


def test_whole_experts_fit_the_budget_at_the_served_width():
    """2048 x 768 bf16 experts stream whole (three blocks, two buffers,
    18.9 MB); an expert twice as wide is cut in 128-lane tiles."""
    assert me._ff_tile(2048, 768, 2) == 768
    assert me._ff_tile(4096, 2048, 2) == 512
    assert me._ff_tile(64, 32, 4) == 32


def test_a_nan_row_stays_in_its_own_row(interpret):
    """A poisoned slot's row reaches every expert the step reads; the rows
    that did not pick an expert are selected out of its output, never
    multiplied by a zero weight, so the NaN stays where it was."""
    wg, wu, wd = weights()
    idx = distinct_picks(6)
    x = jax.random.normal(jax.random.PRNGKey(2), (6, H)).at[3].set(jnp.nan)
    w = jax.random.uniform(jax.random.PRNGKey(3), (6, K))
    y = np.asarray(me.moe_hit_experts_decode(x, idx, w, wg, wu, wd)[0])
    assert np.isnan(y[3]).all()
    rest = np.array([0, 1, 2, 4, 5])
    np.testing.assert_allclose(y[rest], plain_sum(x[rest], idx[rest], w[rest],
                                                  wg, wu, wd), atol=1e-5)


def test_bf16_is_no_further_from_float32_than_the_grouped_form(interpret):
    """In bf16 the kernel accumulates over the experts in float32 where the
    grouped form rounds each pick's row: no further from the plain sum."""
    wg, wu, wd = weights(ff=128, h=128, e=16, dtype=jnp.bfloat16)
    idx = distinct_picks(64, e=16, k=6)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 128), jnp.bfloat16)
    w = jax.random.uniform(jax.random.PRNGKey(3), (64, 6))
    want = plain_sum(x, idx, w, wg, wu, wd)
    y, _ = me.moe_hit_experts_decode(x, idx, w, wg, wu, wd)
    grouped, _ = moe._grouped_expert_mix(x, idx, w, wg, wu, wd, 0, False)
    assert y.dtype == jnp.bfloat16

    def rms(a):
        return float(np.sqrt(np.mean((np.asarray(a, np.float32) - want)
                                     ** 2)))
    assert rms(y) <= rms(grouped)


@pytest.mark.parametrize("partial,rows,forced,form", [
    (True, moe.FEW_ROWS, True, "every_row_on_every_expert_held"),
    (False, moe.FEW_ROWS, True, "kernel"),
    (False, moe.FEW_ROWS + 1, True, "prefill_kernel"),
    (True, moe.FEW_ROWS + 1, True, "prefill_kernel"),
    (False, moe.FEW_ROWS, False, "grouped"),
    (False, moe.FEW_ROWS + 1, False, "grouped"),
    (True, moe.FEW_ROWS + 1, False, "grouped"),
], ids=["share_decode", "whole_decode", "whole_chunk", "share_chunk",
        "whole_decode_cpu", "whole_chunk_cpu", "share_chunk_cpu"])
def test_the_form_follows_the_share_the_rows_and_the_backend(
        monkeypatch, partial, rows, forced, form):
    """A share at a decode step's rows: every row on every expert held;
    all the experts at those rows, on a TPU (here: forced interpret): the
    kernel; more rows (a prefill chunk) on a TPU: the prefill kernel,
    whole hold or share; the CPU lane: the grouped form."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", forced)
    taken = []
    for name, attr, owner in (
            ("every_row_on_every_expert_held",
             "_every_row_on_every_expert_held", moe),
            ("grouped", "_grouped_expert_mix", moe),
            ("kernel", "moe_hit_experts_decode", me),
            ("prefill_kernel", "moe_grouped_experts_prefill", me)):
        real = getattr(owner, attr)
        monkeypatch.setattr(owner, attr, lambda *a, _n=name, _r=real: (
            taken.append(_n), _r(*a))[1])
    wg, wu, wd = weights(e=4)
    idx = distinct_picks(rows, e=8)
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, H))
    w = jax.random.uniform(jax.random.PRNGKey(3), (rows, K))
    jax.eval_shape(lambda *a: moe.dropless_expert_mix(*a, first=2,
                                                      partial=partial),
                   x, idx, w, wg, wu, wd)
    assert taken == [form]


def test_toy_latent_moe_greedy_stream_is_the_same_with_the_kernel(
        monkeypatch):
    """A toy latent-MoE model (all 8 experts held) served greedily: the
    CPU lane's stream (grouped form) and the stream with the kernels on
    the path in interpret mode (the expert kernel in both programs: the
    decode block's 2 rows and the chunk's 16) are the same tokens, with
    the same expert counters."""
    from test_deepseek_v3 import build, ids_of, serve
    model = build()
    prompt = ids_of(37, seed=5)
    cpu, (want,) = serve(model, [prompt], new=7)
    calls = []
    real = me.moe_hit_experts_decode
    monkeypatch.setattr(me, "moe_hit_experts_decode",
                        lambda *a: (calls.append(a[0].shape), real(*a))[1])
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    eng, (got,) = serve(model, [prompt], new=7)
    assert sorted(calls) == [(2, 64)] * 2 + [(16, 64)] * 2
    assert np.array_equal(got, want)
    for name in ("moe_picks", "moe_expert_hits", "moe_max_load",
                 "prefill_moe_picks"):
        assert getattr(eng, name) == getattr(cpu, name), name
