"""The indexer's exact top-k without a sort (``ops/pallas/dsa_select.py``):
the kernel in interpret mode and the CPU lane (``lax.top_k``, its ids
sorted) against ``lax.top_k`` row by row: the same set, in ascending
position order, ties to the lower position, XLA's total order on signed
zeros and ``-inf``; a chunk's padding blocks skipped."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import dsa_select as ds
from paddle_tpu.ops.pallas import fused

N, K = 3000, 100        # n not a whole number of the kernel's row tiles


def _random(rs):
    return rs.standard_normal((8, N))


def _plateau(rs):
    """Plateaus of equal scores across the k-th place: rounded scores, and
    one row whose k-th place lies inside a run of 300 equal scores."""
    s = np.round(rs.standard_normal((8, N)) * 2)
    s[0] = 0.0
    s[0, rs.choice(N, 40, replace=False)] = 1.0
    return s


def _signed_zero(rs):
    """+0.0 above -0.0 (XLA's total order), then the lower position."""
    s = np.zeros((8, N))
    s[:, ::3] = -0.0
    s[1] = -0.0
    s[2, rs.choice(N, 10, replace=False)] = -1.0
    return s


def _short(n_valid):
    def rows(rs):
        """A row's finite scores before its -inf ones, as a slot's
        context lies before the positions past it."""
        s = rs.standard_normal((8, N))
        s[:, n_valid] = -np.inf
        return s
    return rows


def _all_neg_inf(rs):
    s = np.full((8, N), -np.inf)
    s[3, :5] = np.inf
    return s


CASES = {
    "random": (_random, K),
    "plateau": (_plateau, K),
    "signed_zero": (_signed_zero, K),
    "fewer_than_k": (_short(slice(K // 2, None)), K),
    "exactly_k": (_short(slice(K, None)), K),
    "more_than_k": (_short(slice(K + 1, None)), K),
    "k_is_n": (_random, N),
    "all_neg_inf": (_all_neg_inf, K),
}


@pytest.mark.parametrize("lane", ["kernel", "cpu"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_selection_is_top_ks_set_in_position_order(case, lane,
                                                       monkeypatch):
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", lane == "kernel")
    make, k = CASES[case]
    score = jnp.asarray(make(np.random.default_rng(len(case))), jnp.float32)
    got = np.asarray(ds.dsa_select_topk(score, k))
    want = np.asarray(jax.lax.top_k(score, k)[1])
    assert got.shape == want.shape == (8, k) and got.dtype == np.int32
    # the same set, ties to the lower position: top_k's ids, sorted
    assert np.array_equal(got, np.sort(want, axis=-1))
    assert (np.diff(got, axis=-1) > 0).all()


@pytest.mark.parametrize("lane", ["kernel", "cpu"])
def test_a_chunks_padding_blocks_are_skipped(lane, monkeypatch):
    """A chunk of 256 rows with 100 real ones: the kernel skips the blocks
    of 8 rows that hold no real row and writes zeros there (the CPU lane
    selects every row); the real rows are what they are without it."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", lane == "kernel")
    rs = np.random.default_rng(7)
    score = jnp.asarray(rs.standard_normal((256, 1024)), jnp.float32)
    live = jnp.arange(256) < 100
    got = np.asarray(ds.dsa_select_topk(score, 64, live))
    whole = np.asarray(ds.dsa_select_topk(score, 64))
    assert np.array_equal(got[:100], whole[:100])
    assert np.array_equal(whole, np.sort(np.asarray(
        jax.lax.top_k(score, 64)[1]), axis=-1))
    if lane == "kernel":
        assert not got[104:].any() and whole[104:].any()
        assert np.array_equal(got[:104], whole[:104])
    else:
        assert np.array_equal(got, whole)


def test_a_selection_wider_than_the_row_is_refused():
    with pytest.raises(ValueError, match="top-9 of 8"):
        ds.dsa_select_topk(jnp.zeros((2, 8)), 9)
