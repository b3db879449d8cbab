"""Mesh and ``shard_map`` construction for the installed JAX (0.9).

Everything that builds a mesh (launch/mesh.py, the multi-device test
subprocess) or maps a body over one (net/shard.py, optim/compress.py)
goes through these two helpers, so the axis types and the replication
check are chosen in one place.
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with every axis of the Auto type."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
