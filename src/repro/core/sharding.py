"""Sharding helpers: logical axes -> PartitionSpec, mesh utilities."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Data-parallel axes: every mesh axis that is not the model axis.
    On the multi-pod mesh this is ('pod', 'data')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_size(mesh: Mesh) -> int:
    sizes = mesh_axis_sizes(mesh)
    out = 1
    for a in dp_axes(mesh):
        out *= sizes[a]
    return out


def model_size(mesh: Mesh) -> int:
    return mesh_axis_sizes(mesh).get("model", 1)


def batch_spec(mesh: Mesh, extra_dims: int = 1) -> P:
    """[B, ...] with B over all data axes."""
    return P(dp_axes(mesh), *([None] * extra_dims))


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_batch_dim(mesh: Mesh, ndim: int, dim: int = 0) -> P:
    parts: list = [None] * ndim
    parts[dim] = dp_axes(mesh)
    return P(*parts)


def row_axes(mesh: Mesh, batch: int):
    """Data axes for sharding a batch dim, or None when the batch does not
    divide them (e.g. the batch-1 long-context cells)."""
    dpx = dp_axes(mesh)
    n = dp_size(mesh)
    if n > 1 and batch % n == 0 and batch >= n:
        return dpx
    return None


def constrain(x, mesh: Mesh, spec: P):
    """with_sharding_constraint that is a no-op on 1-device meshes (keeps
    small CPU tests free of sharding noise)."""
    if mesh.devices.size == 1:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
