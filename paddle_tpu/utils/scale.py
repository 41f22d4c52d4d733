"""Abstract (weight-free) model construction for AOT scale checks.

Reference parity: the reference's auto-parallel cost model / memory
estimator (python/paddle/distributed/auto_parallel/static/cost/ —
verify) answers "does this config fit the cluster?" without running it.

TPU-native design: XLA's own compiler IS the cost model. Build the model
with every Parameter backed by a ``jax.ShapeDtypeStruct`` (no host
memory), attach NamedShardings for the target mesh, AOT-lower + compile
the full fused train step over a virtual device mesh, and read
``memory_analysis()`` / ``cost_analysis()`` — the compiler's per-device
peak-memory estimate for hardware we don't have attached (an
estimate: the chip has measured 1-2 GiB under it, PERF.md)."""
from __future__ import annotations

import contextlib

import jax
import numpy as np

__all__ = ["abstract_init", "attach_shardings", "abstract_state_specs"]


@contextlib.contextmanager
def abstract_init(dtype=None):
    """Inside this context, ``Layer.create_parameter`` yields Parameters
    whose ``_value`` is a ShapeDtypeStruct — model construction at any
    size without materializing weights. ``dtype`` overrides the param
    dtype (e.g. "bfloat16" for a bf16-weights scale check)."""
    from ..nn.layer import Layer
    from ..tensor import Parameter
    from ..framework import convert_dtype

    orig = Layer.create_parameter
    forced = convert_dtype(dtype) if dtype else None

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from .. import framework
        dt = forced or convert_dtype(dtype) or self._dtype or \
            framework.state().default_dtype
        p = Parameter(jax.ShapeDtypeStruct(
            tuple(int(s) for s in shape), np.dtype(dt)))
        if attr is not None:
            if getattr(attr, "learning_rate", None) is not None:
                p.optimize_attr["learning_rate"] = attr.learning_rate
            if getattr(attr, "trainable", True) is False:
                p.stop_gradient = True
                p.trainable = False
        return p

    Layer.create_parameter = create_parameter
    try:
        yield
    finally:
        Layer.create_parameter = orig


def attach_shardings(model, mesh):
    """Abstract analogue of sharding_utils.place_model: rewrap every
    param spec with its NamedSharding for ``mesh`` (replicated when the
    spec is absent or not divisible). Buffers stay concrete (they are
    small) — callers should pass them through device_put as usual."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..distributed.sharding_utils import filter_spec, _divisible

    for _, p in model.named_parameters():
        v = p._value
        if not isinstance(v, jax.ShapeDtypeStruct):
            continue
        spec = filter_spec(getattr(p, "_sharding_spec", None), mesh,
                           len(v.shape))
        if not _divisible(v.shape, spec, mesh):
            spec = P()
        p._value = jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=NamedSharding(mesh, spec))
    return model


def abstract_state_specs(opt_state, params):
    """Give optimizer-slot specs the sharding of their parameter (the
    shard_optimizer default) so AOT lowering sees the real placement."""
    slots = opt_state["slots"]
    out = {}
    for pname, s in slots.items():
        pspec = params.get(pname)
        psharding = getattr(pspec, "sharding", None) \
            if pspec is not None else None
        out[pname] = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=psharding)
            if isinstance(v, jax.ShapeDtypeStruct)
            and psharding is not None and v.shape == pspec.shape else v
            for k, v in s.items()}
    return {"slots": out, "step": opt_state["step"]}
