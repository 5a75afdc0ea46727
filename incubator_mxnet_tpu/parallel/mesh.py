"""Device mesh helpers.

TPU-native replacement for the reference's device-topology machinery
(ref: src/kvstore/gpu_topology.h link-weight trees; ps-lite node groups):
on TPU the topology is the ICI mesh and XLA owns collective routing —
the framework's job is just to pick mesh axes and shardings
(jax.sharding.Mesh / NamedSharding / PartitionSpec).
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as _np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "Mesh", "NamedSharding", "P", "replicated",
           "batch_sharded", "default_dp_mesh", "replica_contexts",
           "mesh_devices", "surviving_mesh"]


def make_mesh(shape: Sequence[int] = None,
              axis_names: Sequence[str] = ("data",),
              devices=None) -> Mesh:
    """Build a Mesh over available devices.

    make_mesh() → 1-d 'data' mesh over all devices;
    make_mesh((4, 2), ('data', 'model')) → dp×tp grid.
    """
    devices = devices if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devices),)
    arr = _np.asarray(devices[:int(_np.prod(shape))]).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def default_dp_mesh() -> Mesh:
    return make_mesh()


def mesh_devices(mesh: Mesh):
    """The mesh's devices as a flat list (replica order: the order
    `make_mesh` laid them out in)."""
    return list(mesh.devices.flat)


def surviving_mesh(devices, lost=(), axis_names=("data",)) -> Mesh:
    """Re-form a 1-d data mesh from `devices` minus the replicas in
    `lost` (indices into `devices`) — the elastic shrink/grow path.
    Delegates to `make_mesh` so mesh construction stays in one place;
    survivor ORDER is preserved, which is what keeps a re-formed mesh
    deterministic: the same survivor set always yields the same device
    layout (and therefore the same shardings and the same compiled
    step)."""
    lost = set(int(i) for i in lost)
    keep = [d for i, d in enumerate(devices) if i not in lost]
    if not keep:
        raise ValueError("no surviving devices (lost=%s of %d)"
                         % (sorted(lost), len(list(devices))))
    return make_mesh((len(keep),), axis_names, devices=keep)


def replica_contexts(mesh: Optional[Mesh] = None):
    """This process's mesh devices as framework Contexts — the replica
    set a `serving.InferenceEngine` round-robins inference buckets
    across (each replica holds a full parameter copy; data-parallel
    serving, the inference-side mirror of the DP training mesh).
    Non-addressable devices (other processes' chips in a
    multi-controller mesh) are skipped: each host serves its own."""
    from ..context import Context
    devs = (list(mesh.devices.flat) if mesh is not None
            else jax.local_devices())
    local_index = {d.id: i for i, d in enumerate(jax.local_devices())}
    out = []
    for d in devs:
        i = local_index.get(d.id)
        if i is None:       # not addressable from this process
            continue
        out.append(Context("cpu" if d.platform == "cpu" else "tpu", i))
    return out


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = "data",
                  batch_dim: int = 0) -> NamedSharding:
    spec = [None] * (batch_dim + 1)
    spec[batch_dim] = axis
    return NamedSharding(mesh, P(*spec))


def squeeze_stage_axis(tree):
    """Strip the leading size-1 axis a P('<axis>')-sharded stacked tree
    carries inside a shard_map body (each device sees its own slice)."""
    import jax as _jax

    def _squeeze(leaf):
        return leaf[0] if getattr(leaf, "ndim", 0) and             leaf.shape[0] == 1 else leaf
    return _jax.tree_util.tree_map(_squeeze, tree)


def mark_varying(x, axis_name):
    """Tag an unvarying value as device-varying for shard_map's vma
    type system (scan carries that become per-device)."""
    return jax.lax.pcast(x, (axis_name,), to="varying")
