"""The indexer's exact top-k without a sort: ``dsa_select_topk``.

Learned sparse attention (``models/deepseek_v3.py``, ``indexer=``) keeps,
for every query row, the ``k`` positions of largest index score among the
``n`` of its slot's table (2,048 of 36,864 in ``dots3-longdoc-decode``).
``lax.top_k`` gives that set, and XLA lowers it on the TPU to a full sort of
every row's ``n`` scores with their indices: work a selection does not
need, since only the set is read (the selected read is a softmax over it).

The kernel selects the same set, exactly, and sorts nothing. A grid step is
a block of rows, each row viewed as the ``(n / 128, 128)`` matrix it is in
memory, read from HBM once; every pass after that runs over VMEM:

1. every float32 score becomes an int32 key of the same order, XLA's total
   order (``-0.0`` below ``+0.0``, ``-inf`` below every finite score): the
   bits where the sign is clear, the bits with the 31 low ones flipped where
   it is set (the comparator XLA's ``TopK`` itself applies);
2. the row's k-th largest key ``T`` is found bit by bit from the top: 32
   passes, each counting the keys ``>=`` a candidate;
3. the chosen positions are every key ``> T`` and, of the keys ``== T``,
   the first ``k - count(> T)`` by position (``lax.top_k``'s rule: a tie
   goes to the lower position). A rank along a row is a prefix count,
   which the MXU makes of 0/1 masks exactly (within a 128-lane row by an
   upper-triangular matrix of ones, across rows by a lower-triangular one);
4. each chosen position moves left by ``d``, the unchosen positions before
   it, in ``ceil(log2(n - k + 1))`` static shifts, one bit of ``d`` at a
   time from the lowest: lane rolls for bits 0-6 (a lane that wraps moves
   one row up), row rolls for bits 7 and on. ``d`` never decreases along a
   row, so two chosen positions never meet, and the first k places end up
   holding the chosen positions in ASCENDING order. Only ``d`` moves: a
   place ``p`` that ends holding ``d`` held position ``p + d``.

So the ids come in position order, not score order; every consumer reads a
set (the selected read's softmax, the reference's ``selected_share``), and
where a row has fewer than k finite scores its finite ones, which lie
before its ``-inf`` ones, come first.

Off the TPU (the CPU lane) :func:`dsa_select_topk` takes ``lax.top_k`` and
sorts its k ids, so both lanes give one order; a test's forced interpret
mode runs the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import fused as _fused

_LANES = 128
# a row is padded to whole (16, 128) tiles of the bfloat16 masks the MXU
# ranks, with -inf: a pad lies after every real position, so it loses
# every tie and is never among a row's k (k <= n)
_ROW_ALIGN = 16 * _LANES
# rows a grid step, selected one after another (a row's keys are 36 vregs
# at 36,864 scores): the unit in which a chunk's padding rows are skipped
_BLOCK_ROWS = 8
_INT_MIN = -2 ** 31


def _keys(score):
    """float32 -> int32 of the same total order (XLA's ``TopK``)."""
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _select_kernel(live_ref, s_ref, o_ref, *, k, n_bits):
    """One block of rows ``s_ref (R, G, 128)``, a row at a time: the row's
    k chosen positions, ascending, into ``o_ref (R, out_rows, 128)``
    (places past k undefined). A block that ``live_ref`` marks 0 writes
    zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, g, lanes = s_ref.shape
    out_rows = o_ref.shape[1]
    i32 = jnp.int32

    @pl.when(live_ref[pl.program_id(0)] == 0)
    def _skip():
        o_ref[...] = jnp.zeros(o_ref.shape, i32)

    def count(mask):                                    # -> (1, 1)
        return jnp.sum(jnp.sum(mask.astype(i32), axis=0, keepdims=True),
                       axis=1, keepdims=True)

    # prefix counts along a row on the MXU (exact: 0/1 operands, integer
    # sums under 2^24 in float32)
    r_i = jax.lax.broadcasted_iota(i32, (lanes, lanes), 0)
    c_i = jax.lax.broadcasted_iota(i32, (lanes, lanes), 1)
    g_r = jax.lax.broadcasted_iota(i32, (g, g), 0)
    g_c = jax.lax.broadcasted_iota(i32, (g, g), 1)

    def before(mask):
        """How many of ``mask (G, 128)`` lie before each position of the
        row (an exclusive prefix count), int32."""
        m = mask.astype(jnp.bfloat16)
        inside = jnp.dot(m, (r_i <= c_i).astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        totals = jnp.dot(m, jnp.ones((lanes, lanes), jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        above = jnp.dot((g_c < g_r).astype(jnp.bfloat16),
                        totals.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        return (inside + above).astype(i32) - mask.astype(i32)

    pos = (jax.lax.broadcasted_iota(i32, (g, lanes), 0) * lanes
           + jax.lax.broadcasted_iota(i32, (g, lanes), 1))
    lane = jax.lax.broadcasted_iota(i32, (g, lanes), 1)

    def one_row(r, carry):
        key = _keys(s_ref[r])
        # the k-th largest key: the sign first, then bits 30 .. 0
        lo = jnp.where(count(key >= 0) >= k, i32(0), i32(_INT_MIN))

        def bisect(j, lo):
            cand = lo + jnp.right_shift(i32(1 << 30), j)
            return jnp.where(count(key >= cand) >= k, cand, lo)
        t = jax.lax.fori_loop(0, 31, bisect, lo)
        greater, tied = key > t, key == t
        chosen = greater | (tied & (before(tied) < k - count(greater)))
        # move each chosen position left by d, a bit of d a shift
        d = jnp.where(chosen, pos - before(chosen), -1)
        for b in range(n_bits):
            moving = (d & (1 << b)) != 0           # -1 (no one) moves -1
            src = jnp.where(moving, d, -1)
            stay = jnp.where(moving, -1, d)
            if (1 << b) < lanes:
                s = 1 << b
                moved = pltpu.roll(src, lanes - s, 1)
                moved = jnp.where(lane >= lanes - s,
                                  pltpu.roll(moved, g - 1, 0), moved)
            else:
                moved = pltpu.roll(src, g - (1 << b) // lanes, 0)
            d = jnp.maximum(moved, stay)
        o_ref[r] = d[:out_rows] + pos[:out_rows]
        return carry

    @pl.when(live_ref[pl.program_id(0)] != 0)
    def _select():
        jax.lax.fori_loop(0, rows, one_row, 0)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _select_call(score, live, *, k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, n = score.shape
    g = n // _LANES
    out_rows = min(g, -(-k // (8 * _LANES)) * 8)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, g, _LANES),
                               lambda i, live: (i, 0, 0))],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, out_rows, _LANES),
                               lambda i, live: (i, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k,
                          n_bits=max(1, (n - k).bit_length())),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, out_rows, _LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="dsa_select_topk",
    )(live, score.reshape(rows, g, _LANES))
    return out.reshape(rows, out_rows * _LANES)[:, :k]


def kernel_ok() -> bool:
    """The selection runs the kernel on a TPU (or in a test's forced
    interpret mode); elsewhere ``lax.top_k``, its ids sorted."""
    return _fused._pallas_ok()


def dsa_select_topk(score, k: int, live_rows=None):
    """The ``k`` positions of largest ``score (rows, n)`` float32 of every
    row, int32 ``(rows, k)`` in ASCENDING position order: exactly
    ``lax.top_k``'s set (XLA's total order, a tie to the lower position).

    ``live_rows (rows,)`` bool, optional: a row that is False may be
    skipped; the kernel skips a block of rows none of which is live and
    writes zeros there (a chunk's padding rows). Rows that are live come
    out the same either way."""
    rows, n = score.shape
    if not 0 < k <= n:
        raise ValueError(f"top-{k} of {n} scores")
    if not kernel_ok():
        return jnp.sort(jax.lax.top_k(score, k)[1], axis=-1)
    pad_n = -n % _ROW_ALIGN
    pad_r = -rows % _BLOCK_ROWS
    score = score.astype(jnp.float32)
    if pad_n or pad_r:
        score = jnp.pad(score, ((0, pad_r), (0, pad_n)),
                        constant_values=-jnp.inf)
    if live_rows is None:
        live = jnp.ones((score.shape[0] // _BLOCK_ROWS,), jnp.int32)
    else:
        live = jnp.any(jnp.pad(live_rows, (0, pad_r)).reshape(
            -1, _BLOCK_ROWS), axis=1).astype(jnp.int32)
    return _select_call(score, live, k=k,
                        interpret=_fused._FORCE_INTERPRET)[:rows]
