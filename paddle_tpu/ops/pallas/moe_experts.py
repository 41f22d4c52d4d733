"""The routed experts of a decode step, each streamed once where a token
picked it: ``moe_hit_experts_decode``.

A decode step of a model that holds ALL its routed experts (no expert
parallelism: the only rows that reach them are its own) has few rows (64
slots, top-6 of 128: 3 picks an expert), and every matmul of an expert is
one pass of the matrix unit whatever its rows: the step pays for the bytes
of the experts its tokens picked. XLA's grouped form (``ragged-dot``) reads
those bytes at about half the memory bandwidth at 1-4 rows a group.

The kernel reads them once, at a plain matmul's bandwidth. The experts that
received a pick are listed in expert order ahead of the call (a scalar
prefetch, with their count); the grid runs over (slot of that list, tile of
the ff axis), and a weight block's ``index_map`` reads its expert from the
list. Past the count a slot repeats the last real block, so nothing is
copied, and its body is skipped. A grid step runs every row through its
expert, ``silu(x @ Wg) * (x @ Wu)`` accumulated in float32 and cast to the
activations' dtype, then ``@ Wd``, and adds that into a resident float32
``(T, h)`` accumulator weighted by the expert's column of the mix; rows that
did not pick the expert are SELECTED out, never multiplied by a zero weight
(0 x NaN is NaN, and a row's output depends on its own picks only). The
output is written once, in the activations' dtype.

Off the TPU (the CPU lane) ``incubate/.../moe.py`` keeps the grouped form;
a test's forced interpret mode runs the kernel.
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
