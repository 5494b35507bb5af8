"""Global logical device mesh.

Reference analog: the N-D rank topology built by `CommunicateTopology`
(fleet/base/topology.py:65) and ProcessMesh (auto_parallel/process_mesh.py).
TPU-native: ONE `jax.sharding.Mesh` over all addressable devices; every
parallelism axis (dp/pp/sharding/sep/mp/ep) is a named mesh axis. Collectives
become XLA collectives over the axis (ICI within a slice, DCN across slices —
XLA picks the transport from device topology).
"""
from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["build_mesh", "get_mesh", "set_mesh", "mesh_axis_size", "PartitionSpec",
           "NamedSharding", "Mesh", "shard_map_compat"]


def shard_map_compat(body, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off — the repo-wide
    convention: our bodies use rank-dependent values (axis_index) by
    design."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_GLOBAL_MESH: Mesh | None = None

# canonical axis order mirrors the reference hybrid topology order
# (pp outermost -> dp innermost maps pp stages far apart / dp neighbors close,
# the standard ICI-friendly layout; reference order fleet/base/topology.py:68)
AXIS_ORDER = ("pp", "dp", "sharding", "sep", "mp")


def build_mesh(axes: Mapping[str, int] | None = None, devices: Sequence | None = None) -> Mesh:
    """Build + install the global mesh. axes: {"dp": 2, "mp": 4, ...}; axes of
    size 1 are kept (they make PartitionSpecs uniform across configs)."""
    devs = list(devices) if devices is not None else jax.devices()
    if axes is None:
        axes = {"dp": len(devs)}
    names = [a for a in AXIS_ORDER if a in axes] + [a for a in axes if a not in AXIS_ORDER]
    sizes = [int(axes[a]) for a in names]
    total = int(np.prod(sizes))
    if total > len(devs):
        raise ValueError(f"mesh axes {dict(axes)} require {total} devices, have {len(devs)}")
    arr = np.array(devs[:total]).reshape(sizes)
    mesh = Mesh(arr, tuple(names))
    set_mesh(mesh)
    return mesh


def set_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_mesh() -> Mesh | None:
    return _GLOBAL_MESH


def mesh_axis_size(axis: str) -> int:
    m = get_mesh()
    if m is None or axis not in m.shape:
        return 1
    return int(m.shape[axis])
