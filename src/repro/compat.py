"""The sharding helpers the distributed engine uses, in one place.

Every shard_map/mesh construction in the repo goes through these two
helpers, so the flags they set (replication checking off, Auto axis types)
live in exactly one place.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map", "make_mesh"]


def shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
