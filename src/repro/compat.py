"""The distribution APIs under the names the rest of the codebase calls.

Every sharded path builds meshes, binds them and maps over them through
these three names, so the spelling lives in one place:
``jax.shard_map``, ``jax.make_mesh(..., axis_types=...)`` with explicit
``Auto`` axes, and ``jax.set_mesh``.
"""
from __future__ import annotations

import jax


def shard_map(f=None, *, mesh, in_specs, out_specs):
    """``jax.shard_map``, usable directly or as a partial
    ``shard_map(mesh=..., in_specs=..., out_specs=...)(f)``."""
    if f is None:
        return lambda g: shard_map(g, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis typed ``Auto``."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def use_mesh(mesh):
    """Context manager binding ``mesh`` for jitted sharded computations."""
    return jax.set_mesh(mesh)
