"""The two JAX calls every mesh call site shares, with this repo's
defaults: ``shard_map`` with the replication check off and meshes with
explicit ``Auto`` axis types.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with the replication check off by default (the
    MoE body mixes psum'd and per-shard outputs)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis typed ``Auto``."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
