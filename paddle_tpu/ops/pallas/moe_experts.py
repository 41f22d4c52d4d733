"""The routed experts held, each streamed from HBM once where a token picked
it: ``moe_hit_experts_decode`` for a decode step's few rows,
``moe_grouped_experts_prefill`` for a prefill chunk's many.

A decode step of a model that holds ALL its routed experts (no expert
parallelism: the only rows that reach them are its own) has few rows (64
slots, top-6 of 128: 3 picks an expert), and every matmul of an expert is
one pass of the matrix unit whatever its rows: the step pays for the bytes
of the experts its tokens picked. XLA's grouped form (``ragged-dot``) reads
those bytes at about half the memory bandwidth at 1-4 rows a group, and at
about a third at a chunk's 16-32.

``moe_hit_experts_decode`` reads them once, at a plain matmul's bandwidth.
The experts that received a pick are listed in expert order ahead of the
call (a scalar prefetch, with their count); the grid runs over (slot of
that list, tile of the ff axis), and a weight block's ``index_map`` reads
its expert from the list. Past the count a slot repeats the last real
block, so nothing is copied, and its body is skipped. A grid step runs
every row through its expert, ``silu(x @ Wg) * (x @ Wu)`` accumulated in
float32 and cast to the activations' dtype, then ``@ Wd``, and adds that
into a resident float32 ``(T, h)`` accumulator weighted by the expert's
column of the mix; rows that did not pick the expert are SELECTED out,
never multiplied by a zero weight (0 x NaN is NaN, and a row's output
depends on its own picks only). The output is written once, in the
activations' dtype.

``moe_grouped_experts_prefill`` takes any number of rows. The ``T * k``
picks are sorted by expert and their rows gathered in that order ahead of
the call, as XLA's grouped form has them; an expert's rows (its GROUP, 1 to
hundreds of rows) then lie in one or more 128-row tiles, a tile at a
group's edge shared with the next group. The grid is one list of visits
(hit expert, ff tile, row tile of its group), the row tile innermost, so a
weight block stays put while the group's row tiles pass under it and each
hit expert's blocks are copied once; the list, the row ranges and the
count are scalar prefetches, and past the count a visit repeats the last
real one (nothing copied, body skipped). Over the ff tiles a group's rows
add up in a float32 scratch of row tiles; at the last ff tile they are
written into the float32 output tile, SELECTING the group's rows and
keeping the rest, so a shared tile leaves its output buffer (the output's
block index only moves at a last ff tile) with both groups' rows. A group
longer than the scratch is visited in pieces, each reading the expert's
blocks once. The caller turns the rows back to token order and sums each
token's picks, weighted, in float32, casting once.

Off the TPU (the CPU lane) ``incubate/.../moe.py`` keeps the grouped form;
a test's forced interpret mode runs the kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import fused as _fused

_LANES = 128
# a grid step's three weight blocks, double-buffered: whole experts where
# they fit (a 2048 x 768 bf16 expert is 9.4 MB, 18.9 MB buffered)
_WEIGHT_VMEM = 24 * 1024 * 1024
# beside them: Mosaic's internal scratch and rounding
_VMEM_HEADROOM = 4 * 1024 * 1024


def _ff_tile(h: int, ff: int, itemsize: int) -> int:
    """The widest slice of the ff axis whose gate, up and down blocks fit
    ``_WEIGHT_VMEM`` double-buffered: ff whole, else the widest multiple of
    128 lanes that divides it (the narrowest such where none fits)."""
    tiles = [ff] + [tf for tf in range(ff // _LANES * _LANES, 0, -_LANES)
                    if tf < ff and ff % tf == 0]
    for tf in tiles:
        if 6 * h * tf * itemsize <= _WEIGHT_VMEM:
            return tf
    return tiles[-1]


def _kernel(ids_ref, n_ref, x_ref, idx_ref, w_ref, wg_ref, wu_ref, wd_ref,
            o_ref, acc_ref):
    from jax.experimental import pallas as pl
    s, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when((s == 0) & (j == 0))
    def _zero():
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    @pl.when(s < n_ref[0])
    def _expert():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=f32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=f32)
        act = (jax.nn.silu(gate) * up).astype(x.dtype)
        y = jnp.dot(act, wd_ref[...], preferred_element_type=f32)
        picked = idx_ref[...] == ids_ref[s]                   # (T, lanes)
        mix = jnp.sum(jnp.where(picked, w_ref[...], 0.0), axis=1,
                      keepdims=True)
        mine = jnp.sum(picked.astype(jnp.int32), axis=1, keepdims=True) > 0
        acc_ref[...] += jnp.where(mine, y * mix, 0.0)

    @pl.when((s == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tf", "interpret"))
def _hit_experts_call(x, idx, weights, w_gate, w_up, w_down, *, tf,
                      interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    t, h = x.shape
    e, _, ff = w_gate.shape
    held = (idx >= 0) & (idx < e)
    loads = jnp.zeros((e + 1,), jnp.int32).at[
        jnp.where(held, idx, e).reshape(-1)].add(1)[:e]
    hit = loads > 0
    n = jnp.sum(hit).astype(jnp.int32)
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)  # hit first
    ids = jnp.where(jnp.arange(e) < n, order,
                    order[jnp.maximum(n - 1, 0)])
    # the picks lane-dense: (T, 128), a pad picks no expert
    pad = ((0, 0), (0, -idx.shape[1] % _LANES))
    picks = jnp.pad(jnp.where(held, idx, -1), pad, constant_values=-1)
    mix = jnp.pad(weights.astype(jnp.float32), pad)
    kp = picks.shape[1]
    nf = ff // tf

    def tile(s, j, n):
        # past the last expert hit, the last block again: no copy
        return jnp.where(s < n[0], j, nf - 1)

    def resident(s, j, ids, n):
        return 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(e, nf),
        in_specs=[
            pl.BlockSpec((t, h), resident),
            pl.BlockSpec((t, kp), resident),
            pl.BlockSpec((t, kp), resident),
            pl.BlockSpec((None, h, tf),
                         lambda s, j, ids, n: (ids[s], 0, tile(s, j, n))),
            pl.BlockSpec((None, h, tf),
                         lambda s, j, ids, n: (ids[s], 0, tile(s, j, n))),
            pl.BlockSpec((None, tf, h),
                         lambda s, j, ids, n: (ids[s], tile(s, j, n), 0)),
        ],
        out_specs=pl.BlockSpec((t, h), resident),
        scratch_shapes=[pltpu.VMEM((t, h), jnp.float32)])
    # the weight blocks, x and the output double-buffered, the picks, the
    # accumulator, and a step's float32 temporaries (gate, up, act; y)
    vmem = (6 * h * tf * w_gate.dtype.itemsize + 4 * t * h * x.dtype.itemsize
            + 4 * t * kp * 4 + t * h * 4 + 3 * t * tf * 4 + t * h * 4
            + _VMEM_HEADROOM)
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret, name="moe_hit_experts_decode",
    )(ids, n.reshape(1), x, picks, mix, w_gate, w_up, w_down)
    stats = jnp.stack([jnp.sum(loads), n, jnp.max(loads)]).astype(jnp.int32)
    return out, stats


def kernel_ok() -> bool:
    """The expert mix runs the kernel on a TPU (or in a test's forced
    interpret mode); elsewhere the caller keeps its grouped form."""
    return _fused._pallas_ok()


def moe_hit_experts_decode(x, idx, weights, w_gate, w_up, w_down):
    """``sum_k weights[t, k] * SwiGLU_{idx[t, k]}(x[t])`` over the experts
    held, ``w_gate`` / ``w_up (E, h, ff)`` and ``w_down (E, ff, h)``, each
    read from HBM once if a token picked it and not at all otherwise; a
    pick outside ``0 .. E - 1`` adds nothing. ``x (T, h)``, ``idx (T, k)``
    int32, ``weights (T, k)``. Returns ``(y (T, h)`` in ``x``'s dtype,
    ``stats)``, ``stats`` int32 ``[picks on held experts, held experts with
    at least one pick, largest count on one expert]``: what
    ``dropless_expert_mix`` returns."""
    _, h, ff = w_gate.shape
    # the tile is a static argument: worked out at each call, not cached
    # with the traced program
    return _hit_experts_call(x, idx, weights, w_gate, w_up, w_down,
                             tf=_ff_tile(h, ff, w_gate.dtype.itemsize),
                             interpret=_fused._FORCE_INTERPRET)


# a prefill visit's rows: one pass of the 128-row matrix unit
_ROWS = 128
# the float32 row tiles a group's sums wait in over the ff tiles (where the
# ff axis is tiled): 12 row tiles at a hidden width of 2048, 4 at 5120
_ACC_VMEM = 12 * 1024 * 1024


def _acc_tiles(rows: int, h: int, nf: int) -> int:
    """Row tiles of the scratch a group's sums wait in: all of them where
    the ff axis is one tile (nothing waits), else what ``_ACC_VMEM``
    holds, at least one."""
    tiles = -(-rows // _ROWS)
    if nf == 1:
        return tiles
    return max(1, min(tiles, _ACC_VMEM // (_ROWS * h * 4)))


def _prefill_kernel(nf, tile_ref, expert_ref, j_ref, r_ref, lo_ref, hi_ref,
                    out_ref, n_ref, xs_ref, wg_ref, wu_ref, wd_ref, o_ref,
                    *acc):
    from jax.experimental import pallas as pl
    v = pl.program_id(0)
    f32 = jnp.float32

    @pl.when(v < n_ref[0])
    def _visit():
        x = xs_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=f32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=f32)
        act = (jax.nn.silu(gate) * up).astype(x.dtype)
        y = jnp.dot(act, wd_ref[...], preferred_element_type=f32)
        j, r = j_ref[v], r_ref[v]
        if nf > 1:
            @pl.when(j == 0)
            def _first():
                acc[0][r] = y

            @pl.when((j > 0) & (j < nf - 1))
            def _more():
                acc[0][r] += y

        @pl.when(j == nf - 1)
        def _last():
            total = y if nf == 1 else acc[0][r] + y
            row = tile_ref[v] * _ROWS + jax.lax.broadcasted_iota(
                jnp.int32, (_ROWS, 1), 0)
            mine = (row >= lo_ref[v]) & (row < hi_ref[v])
            o_ref[...] = jnp.where(mine, total, o_ref[...])


def _visits(sizes, tiles, nf, acc_tiles):
    """The grid's visit list over the groups ``sizes (E,)`` of ``tiles``
    row tiles: per visit its row tile, expert, ff tile, scratch tile, the
    group's rows ``[lo, hi)`` and the output's row tile; and the count.
    Visits run expert by expert (a group longer than ``acc_tiles`` tiles in
    pieces), then ff tile, then row tile. Past the count, the last real
    visit again."""
    e = sizes.shape[0]
    n_max = nf * (tiles + e - 1)
    offs = jnp.cumsum(sizes) - sizes
    hit = sizes > 0
    first = offs // _ROWS
    spans = jnp.where(hit, (offs + sizes - 1) // _ROWS - first + 1, 0)
    ends = jnp.cumsum(nf * spans)
    n = ends[-1]
    v = jnp.minimum(jnp.arange(n_max, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    ex = jnp.minimum(jnp.searchsorted(ends, v, side="right"), e - 1)
    w = v - (ends[ex] - nf * spans[ex])          # the visit within its expert
    piece = w // (nf * acc_tiles)
    width = jnp.maximum(jnp.minimum(acc_tiles, spans[ex] - piece * acc_tiles),
                        1)
    w = w - piece * nf * acc_tiles
    j, r = w // width, w % width
    tile = first[ex] + piece * acc_tiles + r
    last = (j == nf - 1) & (jnp.arange(n_max) < n)
    seen = jax.lax.cummax(jnp.where(last, jnp.arange(n_max), -1))
    out = tile[jnp.where(seen >= 0, seen, jnp.argmax(last))]
    i32 = jnp.int32
    return ([a.astype(i32) for a in (tile, ex, j, r, offs[ex],
                                     offs[ex] + sizes[ex], out)],
            n.astype(i32).reshape(1))


@functools.partial(jax.jit, static_argnames=("tf", "acc_tiles", "interpret"))
def _grouped_experts_call(x, idx, weights, w_gate, w_up, w_down, *, tf,
                          acc_tiles, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    t, h = x.shape
    k = idx.shape[1]
    e, _, ff = w_gate.shape
    nf = ff // tf
    rows = t * k
    tiles = -(-rows // _ROWS)
    pick = idx.reshape(-1)
    held = (pick >= 0) & (pick < e)
    key = jnp.where(held, pick, e)               # not held: sorted last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
    # expert-major rows, the last tile filled with token 0 (no group's)
    xs = x[jnp.pad(order // k, (0, tiles * _ROWS - rows))]
    meta, n = _visits(sizes, tiles, nf, acc_tiles)
    acc = [pltpu.VMEM((acc_tiles, _ROWS, h), jnp.float32)] if nf > 1 else []

    def expert_block(v, tile, ex, j, *_):
        return ex[v], 0, j[v]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(meta[0].shape[0],),
        in_specs=[
            pl.BlockSpec((_ROWS, h), lambda v, tile, *_: (tile[v], 0)),
            pl.BlockSpec((None, h, tf), expert_block),
            pl.BlockSpec((None, h, tf), expert_block),
            pl.BlockSpec((None, tf, h),
                         lambda v, tile, ex, j, *_: (ex[v], j[v], 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, h),
                               lambda v, *m: (m[6][v], 0)),
        scratch_shapes=acc)
    # the weight blocks, the row tile and the output tile double-buffered,
    # the scratch, and a visit's float32 temporaries (gate, up, act; y and
    # the selected sum)
    vmem = (6 * h * tf * w_gate.dtype.itemsize
            + 2 * _ROWS * h * x.dtype.itemsize + 2 * _ROWS * h * 4
            + len(acc) * acc_tiles * _ROWS * h * 4 + 3 * _ROWS * tf * 4
            + 2 * _ROWS * h * 4 + _VMEM_HEADROOM)
    ys = pl.pallas_call(
        functools.partial(_prefill_kernel, nf),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles * _ROWS, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret, name="moe_grouped_experts_prefill",
    )(*meta, n, xs, w_gate, w_up, w_down)
    # token order again; rows of experts not held (never written) SELECTED
    # out, and each token's picks summed in float32, cast once
    rows_back = ys[jnp.argsort(order)] * weights.reshape(-1, 1).astype(
        jnp.float32)
    rows_back = jnp.where(held[:, None], rows_back, 0.0)
    y = jnp.sum(rows_back.reshape(t, k, h), axis=1).astype(x.dtype)
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                       jnp.max(sizes)]).astype(jnp.int32)
    return y, stats


def moe_grouped_experts_prefill(x, idx, weights, w_gate, w_up, w_down):
    """``moe_hit_experts_decode``'s sum for any number of rows ``T`` (a
    prefill chunk's): each expert a token picked is read from HBM once
    and runs only the rows that picked it, in 128-row tiles; a pick
    outside ``0 .. E - 1`` adds nothing. Same arguments and returns."""
    t, h = x.shape
    _, _, ff = w_gate.shape
    tf = _ff_tile(h, ff, w_gate.dtype.itemsize)
    # both tiles are static arguments: worked out at each call, not cached
    # with the traced program
    return _grouped_experts_call(
        x, idx, weights, w_gate, w_up, w_down, tf=tf,
        acc_tiles=_acc_tiles(t * idx.shape[1], h, ff // tf),
        interpret=_fused._FORCE_INTERPRET)
