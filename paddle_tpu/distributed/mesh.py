"""Hybrid-parallel topology: the keystone of the distributed stack.

Reference parity: ``HybridCommunicateGroup``
(python/paddle/distributed/fleet/base/topology.py — verify): builds the
Cartesian dp×pp×sharding×sep×mp process topology and one comm group per
axis.

TPU-native design: ONE ``jax.sharding.Mesh`` whose named axes are the
parallelism dimensions, laid out with ``mesh_utils.create_device_mesh`` so
the innermost axes (mp/sep) ride the fastest ICI links of the v5p torus.
A "communication group" is just (mesh, axis-name); collectives inside
jitted programs reference axis names, never rank lists."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["HybridCommunicateGroup", "get_hybrid_communicate_group",
           "build_device_mesh", "CommunicateTopology"]

# axis order: outermost (slowest/DCN-adjacent) → innermost (fastest ICI).
# pp stages communicate least per step; mp/sep all-reduce constantly.
AXIS_ORDER = ("pp", "dp", "sharding", "sep", "mp")

_HCG: Optional["HybridCommunicateGroup"] = None
_CURRENT_MESH: Optional[Mesh] = None


def set_current_mesh(mesh: Optional[Mesh]):
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def get_current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH


def build_device_mesh(axis_dims: dict, devices=None,
                      allow_subset: bool = False) -> Mesh:
    """axis_dims: {"dp": 2, "mp": 4, ...}; missing axes get degree 1.
    With allow_subset, uses the first prod(dims) devices (driver dryruns);
    otherwise a size mismatch is an error — silently idling chips hides
    config typos."""
    devices = list(devices if devices is not None else jax.devices())
    dims = [int(axis_dims.get(a, 1)) for a in AXIS_ORDER]
    total = int(np.prod(dims))
    if total > len(devices) or (total < len(devices) and not allow_subset):
        raise ValueError(
            f"topology {dict(zip(AXIS_ORDER, dims))} needs {total} devices, "
            f"have {len(devices)} (pass allow_subset=True to use a prefix)")
    devices = devices[:total]
    # a topology mesh_utils cannot lay out raises here: a silently
    # reshaped device list would put mp/sep on whatever links came first
    from jax.experimental import mesh_utils
    arr = mesh_utils.create_device_mesh(dims, devices=devices)
    return Mesh(arr, AXIS_ORDER)


class CommunicateTopology:
    """Parity shim for fleet.base.topology.CommunicateTopology — verify."""

    def __init__(self, hybrid_group_names, dims):
        self._names = list(hybrid_group_names)
        self._dims = list(dims)

    def get_hybrid_group_names(self):
        return self._names

    def get_dim(self, name):
        return self._dims[self._names.index(name)]

    def world_size(self):
        return int(np.prod(self._dims))


class HybridCommunicateGroup:
    def __init__(self, dp_degree=1, mp_degree=1, pp_degree=1,
                 sharding_degree=1, sep_degree=1, order=None, devices=None,
                 allow_subset=False):
        self._dims = {"dp": dp_degree, "mp": mp_degree, "pp": pp_degree,
                      "sharding": sharding_degree, "sep": sep_degree}
        self.mesh = build_device_mesh(self._dims, devices,
                                      allow_subset=allow_subset)
        self._topo = CommunicateTopology(list(AXIS_ORDER),
                                         [self._dims.get(a, 1)
                                          for a in AXIS_ORDER])
        global _HCG
        _HCG = self
        set_current_mesh(self.mesh)

    # -- mesh-native accessors ---------------------------------------------
    @property
    def jax_mesh(self) -> Mesh:
        return self.mesh

    def axis_size(self, axis: str) -> int:
        return self._dims.get(axis, 1)

    def sharding_spec(self, *axes) -> PartitionSpec:
        return PartitionSpec(*axes)

    def named_sharding(self, *axes) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(*axes))

    # -- paddle fleet.topology API parity ----------------------------------
    def get_parallel_mode(self):
        if self._dims["pp"] > 1:
            return "pipeline_parallel"
        if self._dims["sharding"] > 1:
            return "sharding_parallel"
        if self._dims["mp"] > 1:
            return "tensor_parallel"
        return "data_parallel"

    def topology(self):
        return self._topo

    def get_global_rank(self):
        return jax.process_index()

    # world sizes
    def get_data_parallel_world_size(self):
        return self._dims["dp"]

    def get_model_parallel_world_size(self):
        return self._dims["mp"]

    def get_pipe_parallel_world_size(self):
        return self._dims["pp"]

    def get_sharding_parallel_world_size(self):
        return self._dims["sharding"]

    def get_sep_parallel_world_size(self):
        return self._dims["sep"]

    # ranks: under single-controller SPMD there is one logical program; the
    # per-axis "rank" is meaningful only inside shard_map — expose 0 host-side
    # (multi-host: derive from process index position in the mesh).
    def _axis_rank(self, axis):
        if jax.process_count() == 1:
            return 0
        # position of this process's first device along the axis
        coords = np.argwhere(
            np.vectorize(lambda d: d.process_index)(self.mesh.devices)
            == jax.process_index())
        if coords.size == 0:
            return 0
        return int(coords[0][list(AXIS_ORDER).index(axis)])

    def get_data_parallel_rank(self):
        return self._axis_rank("dp")

    def get_model_parallel_rank(self):
        return self._axis_rank("mp")

    def get_stage_id(self):
        return self._axis_rank("pp")

    def get_sharding_parallel_rank(self):
        return self._axis_rank("sharding")

    def get_sep_parallel_rank(self):
        return self._axis_rank("sep")

    # group objects (API parity; value = (mesh, axis))
    class _AxisGroup:
        def __init__(self, mesh, axis, size):
            self.mesh = mesh
            self.axis = axis
            self.nranks = size
            self.world_size = size
            self.rank = 0

        @property
        def ranks(self):
            return list(range(self.nranks))

    def _group(self, axis):
        return self._AxisGroup(self.mesh, axis, self._dims.get(axis, 1))

    def get_data_parallel_group(self):
        return self._group("dp")

    def get_model_parallel_group(self):
        return self._group("mp")

    def get_pipe_parallel_group(self):
        return self._group("pp")

    def get_sharding_parallel_group(self):
        return self._group("sharding")

    def get_sep_parallel_group(self):
        return self._group("sep")

    def get_check_parallel_group(self, *a, **k):
        return self._group("mp")

    def get_data_parallel_group_src_rank(self):
        return 0

    def get_model_parallel_group_src_rank(self):
        return 0

    # pipeline helpers
    def is_first_stage(self):
        return self.get_stage_id() == 0

    def is_last_stage(self):
        return self.get_stage_id() == self._dims["pp"] - 1


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _HCG
