"""Hierarchical + quantized collective communication.

The gradient/activation sync path is the multi-chip hot path, and flat
``lax.p*`` collectives leave two kinds of performance on the table:

- **Topology**: a v5p pod is not a flat ring — the inner mesh axes ride
  3D-torus ICI while the outer axes may cross DCN. HiCCL
  (arXiv:2408.05962) composes big collectives from per-level primitives:
  reduce-scatter inside the fast level, a small all-reduce across the
  slow level, all-gather back. :mod:`.hierarchical` implements that
  decomposition over any two (groups of) mesh axes, chosen automatically
  from the current :mod:`..mesh` topology, with a flat fallback — and
  bit-identical results for exactly-representable sums.
- **Bytes**: gradients tolerate low-precision transport. EQuARX
  (arXiv:2506.17615) shows an in-XLA int8 all-reduce with per-block
  scales and full-precision accumulation at ~2x wire bandwidth.
  :mod:`.quantized` is the same scheme over shard_map: int8 payload,
  fp32 per-bucket scales, fp32 accumulate, documented error bound
  (exact for constant buckets).

On top sits a bucketing scheduler (:mod:`.bucketing`): gradient tensors
coalesce into size-targeted buckets so one collective moves many small
tensors — fewer dispatches, and XLA's latency-hiding scheduler can
overlap bucket k's collective with bucket k+1's math. Off by default;
enable via :func:`configure` or ``PT_COLLECTIVES_BUCKETED_SYNC=1``.

Everything here is **in-graph**: the ``*_collective`` primitives run
inside ``shard_map`` where mesh axis names are bound; the module-level
``all_reduce``/``all_gather``/``reduce_scatter`` wrap them over a mesh
for host-level use (tests, eager loops). The eager
control-plane API in :mod:`..communication` is unchanged and unrelated.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Optional

__all__ = [
    "CollectiveConfig", "collective_config", "set_collective_config",
    "configure",
    "HierarchyPlan", "plan_hierarchy",
    "hier_all_reduce", "hier_all_gather", "hier_reduce_scatter",
    "all_reduce", "all_gather", "reduce_scatter",
    "quantized_all_reduce", "int8_error_bound",
    "build_buckets", "BucketedGradSync", "bucketed_allreduce_gradients",
    "attach_grad_sync",
]


from ...utils.flags import env_flag as _env_flag  # noqa: E402
# (shared falsy spellings with PT_FUSION_PASSES — utils.flags.env_flag)


@dataclasses.dataclass
class CollectiveConfig:
    """Knobs for the collectives subsystem.

    - ``hierarchy``: "auto" decomposes over two mesh axes when the
      reduction spans >= 2 axes of degree > 1; "flat" always uses the
      single fused XLA collective.
    - ``compress``: None (fp32 wire) or "int8" (per-bucket-scaled int8
      payload, fp32 accumulate).
    - ``quant_bucket_size``: elements per int8 scale bucket. Smaller
      buckets -> tighter error bound, more scale overhead
      (4/bucket_size extra bytes per element).
    - ``error_bound``: optional max tolerable |quantized - fp32| per
      element. With ``compress="int8"``, the in-graph bucketed
      grad-sync computes the runtime bound per fused bucket and
      selects the fp32 reduction for any bucket that would exceed it
      (both reductions run for budgeted buckets — a hard guarantee,
      not a free one). Other in-graph callers fetch the bound via
      ``quantized_all_reduce(..., return_error_bound=True)``. The
      eager bucketed path always ships fp32 and never reads this.
    - ``bucket_bytes``: coalescing target for the gradient bucketer
      (reference DataParallel's comm_buffer_size is 25 MB).
    - ``bucketed_grad_sync``: master switch for wiring the bucketer
      into DataParallel / group_sharded_parallel / the optimizer's
      functional grad path. Defaults OFF — flipping it changes comm
      scheduling, never values.
    """
    hierarchy: str = "auto"                 # "auto" | "flat"
    compress: Optional[str] = None          # None | "int8"
    quant_bucket_size: int = 512
    error_bound: Optional[float] = None
    bucket_bytes: int = 25 << 20
    bucketed_grad_sync: bool = dataclasses.field(
        default_factory=lambda: _env_flag("PT_COLLECTIVES_BUCKETED_SYNC"))

    def __post_init__(self):
        if self.hierarchy not in ("auto", "flat"):
            raise ValueError(
                f"hierarchy must be 'auto' or 'flat', got "
                f"{self.hierarchy!r}")
        if self.compress not in (None, "int8"):
            raise ValueError(
                f"compress must be None or 'int8', got {self.compress!r}")
        if self.quant_bucket_size < 1:
            raise ValueError("quant_bucket_size must be >= 1")


_CONFIG = CollectiveConfig()


def collective_config() -> CollectiveConfig:
    return _CONFIG


def set_collective_config(cfg: CollectiveConfig) -> CollectiveConfig:
    global _CONFIG
    prev, _CONFIG = _CONFIG, cfg
    return prev


@contextlib.contextmanager
def configure(**kw):
    """Scoped config override: ``with collectives.configure(
    compress="int8", hierarchy="flat"): ...``"""
    prev = set_collective_config(dataclasses.replace(_CONFIG, **kw))
    try:
        yield _CONFIG
    finally:
        set_collective_config(prev)


from .hierarchical import (HierarchyPlan, plan_hierarchy,          # noqa: E402
                           hier_all_reduce, hier_all_gather,
                           hier_reduce_scatter,
                           all_reduce, all_gather, reduce_scatter)
from .quantized import quantized_all_reduce, int8_error_bound      # noqa: E402
from .bucketing import (build_buckets, BucketedGradSync,           # noqa: E402
                        bucketed_allreduce_gradients, attach_grad_sync)
