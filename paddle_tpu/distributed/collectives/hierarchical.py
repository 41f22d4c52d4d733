"""HiCCL-style hierarchical collective primitives over mesh axes.

A reduction spanning two mesh levels — e.g. ("dp", "mp") where "mp"
rides intra-slice ICI and "dp" crosses slices/DCN — decomposes
(arXiv:2408.05962):

    all-reduce      = reduce-scatter(inner) ; all-reduce(outer)
                      ; all-gather(inner)
    reduce-scatter  = reduce-scatter(outer) ; reduce-scatter(inner)
    all-gather      = all-gather(inner) ; all-gather(outer)

The inner (fastest-ICI, innermost in mesh.AXIS_ORDER) level carries the
full payload; the outer level only moves 1/inner_size of it. Chunk
ordering is chosen so every composition is **bit-identical** to the
flat single-call collective over the same axes whenever the sums are
exactly representable (always for the data-movement collectives; for
fp32 sums whenever addition does not round, e.g. integer-valued
gradients — otherwise within normal fp32 reassociation noise).

These primitives are IN-GRAPH: call them inside ``shard_map`` where the
axis names are bound. The module-level :func:`all_reduce` /
:func:`all_gather` / :func:`reduce_scatter` wrappers at the bottom run
them over a mesh from host level (stacked per-device contributions in,
global result out) — the form the tests use.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..mesh import get_current_mesh

Axes = Union[str, Sequence[str]]


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _norm_axes(axes: Axes) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


@dataclasses.dataclass(frozen=True)
class HierarchyPlan:
    """How one logical collective maps onto mesh levels.

    ``axes`` is the full reduction scope (mesh order, outer->inner).
    ``inner`` is the fastest level (one axis name) and ``outer`` the
    remaining axes, or both None for a flat plan. ``inner_size`` /
    ``total_size`` are static device counts used for padding/chunking.
    """
    axes: Tuple[str, ...]
    outer: Optional[Tuple[str, ...]]
    inner: Optional[str]
    inner_size: int
    total_size: int

    @property
    def flat(self) -> bool:
        return self.inner is None

    @property
    def mode(self) -> str:
        return "flat" if self.flat else "hierarchical"


def plan_hierarchy(axes: Axes, mesh: Optional[Mesh] = None,
                   hierarchy: Optional[str] = None) -> HierarchyPlan:
    """Pick the decomposition for a reduction over ``axes``.

    Axes are re-ordered to mesh order (outermost first — matching what
    a flat multi-axis collective does with that tuple). When >= 2 of
    them have degree > 1 and ``hierarchy`` resolves to "auto", the
    innermost becomes the fast level; otherwise the plan is flat.
    Degree-1 axes are dropped (they contribute nothing but would still
    force XLA to emit a wider replica-group table)."""
    from . import collective_config
    if hierarchy is None:
        hierarchy = collective_config().hierarchy
    mesh = mesh if mesh is not None else get_current_mesh()
    names = _norm_axes(axes)
    if mesh is None:                      # no topology known: flat as-is
        return HierarchyPlan(names, None, None, 1, 1)
    sizes = _axis_sizes(mesh)
    for a in names:
        if a not in sizes:
            raise ValueError(
                f"axis {a!r} not in mesh axes {tuple(sizes)}")
    order = {a: i for i, a in enumerate(mesh.axis_names)}
    names = tuple(sorted(dict.fromkeys(names), key=order.__getitem__))
    live = tuple(a for a in names if sizes[a] > 1)
    total = int(np.prod([sizes[a] for a in live])) if live else 1
    if hierarchy != "auto" or len(live) < 2:
        return HierarchyPlan(live or names[-1:], None, None, 1, total)
    return HierarchyPlan(live, live[:-1], live[-1], sizes[live[-1]],
                         total)


# --------------------------------------------------------------------------
# in-graph primitives (call inside shard_map)
# --------------------------------------------------------------------------

def pad_to_multiple(flat, multiple):
    """Zero-pad a 1-D array so ``multiple`` divides it; returns
    (padded, pad). Shared by the hierarchical chunking here and the
    quantization bucketing in :mod:`.quantized`."""
    pad = (-flat.size) % multiple
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


def hier_all_reduce(x, plan: HierarchyPlan):
    """All-reduce (sum) over ``plan.axes``; hierarchical plans run
    reduce-scatter(inner) -> all-reduce(outer) -> all-gather(inner),
    padding the flattened payload so the inner level divides it."""
    if plan.flat:
        with jax.named_scope("collectives.all_reduce[flat]"):
            return jax.lax.psum(x, plan.axes)
    with jax.named_scope("collectives.all_reduce[hier]"):
        shape, dtype = x.shape, x.dtype
        flat, pad = pad_to_multiple(x.reshape(-1), plan.inner_size)
        part = jax.lax.psum_scatter(flat, plan.inner,
                                    scatter_dimension=0, tiled=True)
        part = jax.lax.psum(part, plan.outer)
        out = jax.lax.all_gather(part, plan.inner, axis=0, tiled=True)
        if pad:
            out = out[:flat.size - pad]
        return out.reshape(shape)


def hier_reduce_scatter(x, plan: HierarchyPlan):
    """Reduce-scatter (sum) over ``plan.axes`` along dim 0 (tiled):
    in (N, ...) per device -> out (N/total, ...), the chunk for this
    device's linear index over ``plan.axes`` (outer-major — identical
    chunk assignment to the flat collective). Hierarchical plans
    scatter outer-first so chunk order is preserved."""
    n = plan.total_size
    if x.shape[0] % max(n, 1):
        raise ValueError(
            f"reduce_scatter dim 0 ({x.shape[0]}) not divisible by "
            f"device count {n} over axes {plan.axes}")
    if plan.flat:
        with jax.named_scope("collectives.reduce_scatter[flat]"):
            return jax.lax.psum_scatter(x, plan.axes,
                                        scatter_dimension=0, tiled=True)
    with jax.named_scope("collectives.reduce_scatter[hier]"):
        out = jax.lax.psum_scatter(x, plan.outer, scatter_dimension=0,
                                   tiled=True)
        return jax.lax.psum_scatter(out, plan.inner,
                                    scatter_dimension=0, tiled=True)


def hier_all_gather(x, plan: HierarchyPlan):
    """All-gather over ``plan.axes`` along dim 0 (tiled): in (M, ...)
    per device -> out (M*total, ...) with shards in linear-index order
    (outer-major). Hierarchical plans gather inner-first, which keeps
    that order while the outer level moves already-widened blocks."""
    if plan.flat:
        with jax.named_scope("collectives.all_gather[flat]"):
            return jax.lax.all_gather(x, plan.axes, axis=0, tiled=True)
    with jax.named_scope("collectives.all_gather[hier]"):
        out = jax.lax.all_gather(x, plan.inner, axis=0, tiled=True)
        return jax.lax.all_gather(out, plan.outer, axis=0, tiled=True)


# --------------------------------------------------------------------------
# host-level wrappers (tests / eager loops)
# --------------------------------------------------------------------------

def _unwrap(x):
    from ...tensor import Tensor
    if isinstance(x, Tensor):
        return x._value, True
    return jnp.asarray(x), False


def _wrap(v, was_tensor):
    if was_tensor:
        from ...tensor import Tensor
        return Tensor(v)
    return v


def _resolve(axes, mesh, hierarchy):
    mesh = mesh if mesh is not None else get_current_mesh()
    if mesh is None:
        raise ValueError(
            "collectives need a mesh: pass mesh= or build one via "
            "HybridCommunicateGroup / build_device_mesh")
    if axes is None:
        axes = tuple(a for a, s in _axis_sizes(mesh).items() if s > 1)
        if not axes:
            axes = (mesh.axis_names[-1],)
    plan = plan_hierarchy(axes, mesh, hierarchy)
    return mesh, plan


def _record(name):
    from ...profiler import RecordEvent
    return RecordEvent(name)


_om = None        # observability.metrics, imported on first dispatch
                  # (collectives load during package init, before
                  # ``paddle_tpu.utils`` exists, so no top-level import)


def _note_metrics(op: str, plan: HierarchyPlan, v, int8: bool = False):
    """Per-call collective metrics: calls + payload bytes (labelled by
    op and plan mode) and, for the quantized path, the runtime int8
    error bound on this payload. The module is imported once and
    cached; after that the disarmed path is one None test + one bool
    check, and the absmax host sync only happens armed."""
    global _om
    if _om is None:
        from ...observability import metrics as _om
    om = _om
    if not om.enabled():
        return
    mode = plan.mode + (",int8" if int8 else "")
    om.counter("pt_collectives_calls_total",
               "host-level collective dispatches",
               labels=("op", "mode")).inc(op=op, mode=mode)
    om.counter("pt_collectives_bytes_total",
               "payload bytes handed to collectives (stacked "
               "contributions, not algorithmic wire bytes)",
               labels=("op", "mode")).inc(v.nbytes, op=op, mode=mode)
    if int8:
        from .quantized import int8_error_bound
        absmax = float(jnp.max(jnp.abs(v)))
        om.gauge("pt_collectives_int8_error_bound",
                 "worst-case |dequant - fp32| of the most recent int8 "
                 "all-reduce payload").set(
            float(int8_error_bound(absmax, plan.total_size)))


@functools.lru_cache(maxsize=256)
def _compiled(op: str, mesh: Mesh, plan: HierarchyPlan,
              bucket_size: Optional[int]):
    """Jitted shard_map program per (op, mesh, plan) — host-level
    wrappers would otherwise re-trace on every call."""
    if op == "all_reduce":
        inner = lambda xl: hier_all_reduce(        # noqa: E731
            jnp.squeeze(xl, 0), plan)
        out_specs = P()
    elif op == "all_reduce_int8":
        from .quantized import quantized_all_reduce
        inner = lambda xl: quantized_all_reduce(   # noqa: E731
            jnp.squeeze(xl, 0), plan, bucket_size=bucket_size)
        out_specs = P()
    elif op == "reduce_scatter":
        def inner(xl):
            return hier_reduce_scatter(jnp.squeeze(xl, 0), plan)[None]
        out_specs = P(plan.axes)
    elif op == "all_gather":
        inner = lambda xl: hier_all_gather(        # noqa: E731
            jnp.squeeze(xl, 0), plan)
        out_specs = P()
    else:  # pragma: no cover
        raise ValueError(op)
    return jax.jit(jax.shard_map(inner, mesh=mesh,
                                 in_specs=(P(plan.axes),),
                                 out_specs=out_specs, check_vma=False))


def all_reduce(x, axes: Optional[Axes] = None, mesh: Optional[Mesh] = None,
               compress: Optional[str] = "__config__",
               hierarchy: Optional[str] = None):
    """Sum stacked per-device contributions.

    ``x``: (n_devices, *shape) — row d is device d's term (linear index
    over ``axes``, outer-major). Returns (*shape), the sum every device
    ends up holding. ``compress="int8"`` routes through the quantized
    wire format (see :mod:`.quantized`); default follows the global
    config."""
    from . import collective_config
    cfg = collective_config()
    if compress == "__config__":
        compress = cfg.compress
    v, wast = _unwrap(x)
    mesh, plan = _resolve(axes, mesh, hierarchy)
    if v.shape[0] != plan.total_size:
        raise ValueError(
            f"all_reduce expects stacked contributions with dim 0 == "
            f"{plan.total_size} (devices over {plan.axes}), got "
            f"{v.shape}")
    op = "all_reduce_int8" if compress == "int8" else "all_reduce"
    # bucket size only shapes the int8 program; keying the fp32 cache
    # on it would recompile identical programs on config churn
    bucket = cfg.quant_bucket_size if compress == "int8" else None
    _note_metrics("all_reduce", plan, v, int8=compress == "int8")
    with _record(f"collectives::all_reduce[{plan.mode}"
                 f"{',int8' if compress == 'int8' else ''}]"):
        out = _compiled(op, mesh, plan, bucket)(v)
        out.block_until_ready()
    return _wrap(out, wast)


def reduce_scatter(x, axes: Optional[Axes] = None,
                   mesh: Optional[Mesh] = None,
                   hierarchy: Optional[str] = None):
    """Reduce-scatter stacked per-device contributions.

    ``x``: (n_devices, N, ...) — row d is device d's full-length term.
    Returns (n_devices, N/n, ...): row d is the reduced chunk device d
    holds afterwards (so callers can check placement, not just values).
    """
    v, wast = _unwrap(x)
    mesh, plan = _resolve(axes, mesh, hierarchy)
    n = plan.total_size
    if v.shape[0] != n:
        raise ValueError(
            f"reduce_scatter expects dim 0 == {n}, got {v.shape}")
    _note_metrics("reduce_scatter", plan, v)
    with _record(f"collectives::reduce_scatter[{plan.mode}]"):
        out = _compiled("reduce_scatter", mesh, plan, None)(v)
        out.block_until_ready()
    return _wrap(out, wast)


def all_gather(x, axes: Optional[Axes] = None, mesh: Optional[Mesh] = None,
               hierarchy: Optional[str] = None):
    """All-gather stacked per-device shards.

    ``x``: (n_devices, M, ...) — row d is device d's shard. Returns
    (n_devices * M, ...), the concatenation (linear order over
    ``axes``) every device ends up holding."""
    v, wast = _unwrap(x)
    mesh, plan = _resolve(axes, mesh, hierarchy)
    if v.shape[0] != plan.total_size:
        raise ValueError(
            f"all_gather expects dim 0 == {plan.total_size}, got "
            f"{v.shape}")
    _note_metrics("all_gather", plan, v)
    with _record(f"collectives::all_gather[{plan.mode}]"):
        out = _compiled("all_gather", mesh, plan, None)(v)
        out.block_until_ready()
    return _wrap(out, wast)
