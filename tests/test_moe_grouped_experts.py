"""The routed experts of a prefill chunk: the Pallas kernel
``moe_grouped_experts_prefill`` (interpret mode on the CPU) against XLA's
grouped form it replaces there, on whole holds and shares, at chunk rows
past ``moe.FEW_ROWS``; its visit list; and a toy latent-MoE model's greedy
stream with the kernel in its chunk program (which form
``dropless_expert_mix`` takes: ``tests/test_moe_hit_experts.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models import moe
from paddle_tpu.ops.pallas import fused
from paddle_tpu.ops.pallas import moe_experts as me

H, FF = 16, 12


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)


def weights(e, h=H, ff=FF, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple((jax.random.normal(k[i], s) * 0.3).astype(dtype)
                 for i, s in enumerate(((e, h, ff), (e, h, ff),
                                        (e, ff, h))))


def distinct_picks(t, routed, k, seed=1):
    """``k`` distinct experts of the router's ``routed`` a token."""
    rs = np.random.RandomState(seed)
    return jnp.asarray(np.stack([rs.permutation(routed)[:k]
                                 for _ in range(t)]), jnp.int32)


def inputs(t, k, h=H, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(2), (t, h), dtype)
    w = jax.random.uniform(jax.random.PRNGKey(3), (t, k))
    return x, w


def kernel_and_grouped(x, idx, w, ws, first, partial):
    y, stats = me.moe_grouped_experts_prefill(x, idx - first, w, *ws)
    want, want_stats = moe._grouped_expert_mix(x, idx, w, *ws, first,
                                               partial)
    return np.asarray(y), stats.tolist(), np.asarray(want), \
        want_stats.tolist()


# (experts held, first held, the router's experts, top-k)
HOLDS = {
    "whole_top4_of_64": (64, 0, 64, 4),
    "whole_top6_of_128": (128, 0, 128, 6),
    "share_first_2": (4, 2, 8, 3),
    "share_16_of_256_top8": (16, 0, 256, 8),
}


@pytest.mark.parametrize("rows", [129, 512, 777])
@pytest.mark.parametrize("hold", sorted(HOLDS))
def test_kernel_is_the_grouped_form(hold, rows, interpret):
    """Whole holds and shares, at a chunk's rows and past them: the sum
    and the counters of the grouped form."""
    e, first, routed, k = HOLDS[hold]
    idx = distinct_picks(rows, routed, k)
    x, w = inputs(rows, k)
    y, stats, want, want_stats = kernel_and_grouped(
        x, idx, w, weights(e), first, e < routed)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert stats == want_stats
    local = np.asarray(idx).ravel() - first
    loads = np.bincount(local[(local >= 0) & (local < e)], minlength=e)
    assert stats == [int(loads.sum()), int((loads > 0).sum()),
                     int(loads.max())]


def test_an_empty_expert_and_a_group_of_several_row_tiles(interpret):
    """Expert 0 holds 448 rows (four row tiles, the first and last shared
    with their neighbours), expert 3 none: the sum is the grouped form's,
    and the empty expert is counted as not hit."""
    t, k, e = 448, 2, 8
    rs = np.random.RandomState(4)
    second = rs.choice([1, 2, 4, 5, 6, 7], size=t)
    idx = jnp.asarray(np.stack([np.zeros(t, int), second], 1), jnp.int32)
    x, w = inputs(t, k)
    y, stats, want, want_stats = kernel_and_grouped(x, idx, w, weights(e),
                                                    0, False)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert stats == want_stats and stats[1] == 7 and stats[2] == 448


def test_ff_tiles_and_a_group_longer_than_the_scratch(monkeypatch,
                                                      interpret):
    """ff 384 in three 128-lane tiles (a group's rows add up over them in
    the scratch) and a scratch of two row tiles, so that a 448-row group
    is visited in two pieces: the same sum."""
    monkeypatch.setattr(me, "_WEIGHT_VMEM", 6 * H * 128 * 4)
    monkeypatch.setattr(me, "_ACC_VMEM", 2 * me._ROWS * H * 4)
    assert me._ff_tile(H, 384, 4) == 128
    assert me._acc_tiles(448 * 2, H, 3) == 2
    t, k, e = 448, 2, 8
    rs = np.random.RandomState(4)
    idx = jnp.asarray(np.stack([np.zeros(t, int),
                                rs.choice(np.arange(1, e), size=t)], 1),
                      jnp.int32)
    x, w = inputs(t, k)
    y, stats, want, want_stats = kernel_and_grouped(
        x, idx, w, weights(e, ff=384), 0, False)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert stats == want_stats


def test_a_nan_row_stays_in_its_own_row(interpret):
    """A poisoned token's rows share their row tiles with other tokens'
    rows and with other experts' groups; the rows outside a group are
    selected out of each visit's output, never multiplied by a zero
    weight, so the NaN stays in its own token's row."""
    t, k, e = 200, 4, 16
    idx = distinct_picks(t, e, k)
    x, w = inputs(t, k)
    x = x.at[37].set(jnp.nan)
    ws = weights(e)
    y = np.asarray(me.moe_grouped_experts_prefill(x, idx, w, *ws)[0])
    assert np.isnan(y[37]).all()
    rest = np.arange(t) != 37
    want = np.asarray(moe._grouped_expert_mix(x[rest], idx[rest], w[rest],
                                              *ws, 0, False)[0])
    np.testing.assert_allclose(y[rest], want, atol=1e-5)


def test_bf16_is_no_further_from_float32_than_the_grouped_form(interpret):
    """In bf16 the kernel's rows stay float32 to the sum over a token's
    picks, where the grouped form rounds each pick's row: no further from
    the float32 sum."""
    t, k, e = 160, 6, 16
    ws32 = weights(e, h=128, ff=128)
    ws = tuple(a.astype(jnp.bfloat16) for a in ws32)
    idx = distinct_picks(t, e, k)
    x, w = inputs(t, k, h=128, dtype=jnp.bfloat16)
    want = np.asarray(moe._grouped_expert_mix(
        x.astype(jnp.float32), idx, w,
        *(a.astype(jnp.float32) for a in ws), 0, False)[0])
    y, _ = me.moe_grouped_experts_prefill(x, idx, w, *ws)
    grouped, _ = moe._grouped_expert_mix(x, idx, w, *ws, 0, False)
    assert y.dtype == jnp.bfloat16

    def rms(a):
        return float(np.sqrt(np.mean((np.asarray(a, np.float32) - want)
                                     ** 2)))
    assert rms(y) <= rms(grouped)


@pytest.mark.parametrize("sizes,nf,acc", [
    ([0, 130, 0, 1, 300, 0, 5, 200], 1, 5),
    ([0, 130, 0, 1, 300, 0, 5, 200], 3, 2),
    ([0, 0, 0, 0], 2, 1),
], ids=["one_ff_tile", "ff_tiles_and_pieces", "nothing_held"])
def test_the_visits_read_each_block_once_and_every_row_once(sizes, nf, acc):
    """Every (expert hit, ff tile, row tile of its group) visited once,
    the row tiles innermost; a weight block is entered once per piece of
    a group (a group longer than the scratch comes in pieces), an output
    tile's index only moves at a last ff tile and never goes back; past
    the count the last real visit again."""
    rows = 768
    tiles = rows // me._ROWS
    (tile, ex, j, r, lo, hi, out), n = me._visits(
        jnp.asarray(sizes, jnp.int32), tiles, nf, acc)
    tile, ex, j, r, lo, hi, out = (np.asarray(a) for a in (tile, ex, j, r,
                                                           lo, hi, out))
    n = int(n[0])
    assert len(tile) == nf * (tiles + len(sizes) - 1)
    offs = np.cumsum(sizes) - sizes
    want = set()
    for g, size in enumerate(sizes):
        for tl in range(offs[g] // me._ROWS,
                        (offs[g] + size - 1) // me._ROWS + 1 if size else 0):
            want |= {(g, f, tl) for f in range(nf)}
    got = list(zip(ex[:n], j[:n], tile[:n]))
    assert len(got) == len(set(got)) and set(got) == want
    spans = [len({tl for g2, _, tl in want if g2 == g})
             for g in range(len(sizes))]
    pieces = sum(-(-s // acc) for s in spans)
    entered = sum(1 for v in range(n) if v == 0
                  or (ex[v], j[v]) != (ex[v - 1], j[v - 1]))
    assert entered == nf * pieces
    assert all(lo[v] == offs[ex[v]] and hi[v] == offs[ex[v]] + sizes[ex[v]]
               and 0 <= r[v] < acc for v in range(n))
    assert all(out[v] == out[v - 1] for v in range(1, len(out))
               if not (v < n and j[v] == nf - 1))
    assert list(out) == sorted(out)
    for a in (tile, ex, j, r, lo, hi, out):
        assert (a[n:] == a[max(n - 1, 0)]).all()


def test_toy_latent_moe_greedy_stream_is_the_same_with_the_prefill_kernel(
        monkeypatch):
    """A toy latent-MoE model (all 8 experts held) served greedily with a
    144-token chunk: the CPU lane's stream (grouped form) and the stream
    with the kernels in interpret mode (the prefill kernel in the chunk
    program, which a 200-token prompt runs twice) are the same tokens,
    with the same expert counters."""
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    from test_deepseek_v3 import build, ids_of
    model = build()
    prompt = ids_of(200, seed=5)

    def serve():
        eng = ContinuousBatchingEngine(model, paged=True, num_slots=2,
                                       max_len=256, block_size=8,
                                       prefill_chunk=144, decode_block=4)
        srv = Server(eng, Scheduler())
        rid = srv.submit(prompt, max_new_tokens=5)
        srv.run_until_idle()
        return eng, np.asarray(srv.results[rid])
    cpu, want = serve()
    calls = []
    real = me.moe_grouped_experts_prefill
    monkeypatch.setattr(me, "moe_grouped_experts_prefill",
                        lambda *a: (calls.append(a[0].shape), real(*a))[1])
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    eng, got = serve()
    assert calls and set(calls) == {(144, 64)}
    assert np.array_equal(got, want)
    for name in ("moe_picks", "moe_expert_hits", "moe_max_load",
                 "prefill_moe_picks", "prefill_moe_expert_hits"):
        assert getattr(eng, name) == getattr(cpu, name), name
