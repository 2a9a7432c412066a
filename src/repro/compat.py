"""Mesh and shard_map helpers shared by the crawler, the serve path and the
model cells (single import point, so every caller builds meshes and
shard_maps the same way)."""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              **kw) -> jax.sharding.Mesh:
    """jax.make_mesh with every axis Auto."""
    kw.setdefault("axis_types", (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kw)


def shard_map(f, *, mesh, in_specs, out_specs):
    """jax.shard_map without the replication (vma) check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
