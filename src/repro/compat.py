"""Mesh and shard_map entry points on the installed JAX (0.9) API.

Every mesh / shard_map call site imports these names, so a move in
JAX's public API is one edit here.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types (every mesh here is Auto)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
