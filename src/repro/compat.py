"""One home for the mesh / shard_map conventions every caller shares.

Every mesh in src/, tests/ and benchmarks/ is Auto-typed, every
``shard_map`` that hosts a Pallas kernel turns the replication checker off,
and loop carries inside ``shard_map`` are marked device-varying. These
helpers spell those three conventions once, on the installed JAX API.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax import lax


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices=None,
):
    """``jax.make_mesh`` with every axis Auto-typed."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
        devices=devices,
    )


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``; ``check_vma=False`` for bodies that call Pallas
    (the replication checker has no rule for ``pallas_call``)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def pvary(x, axis_names):
    """Mark ``x`` device-varying over ``axis_names`` (VMA loop-carry typing)."""
    names = axis_names if isinstance(axis_names, tuple) else (axis_names,)
    return lax.pcast(x, names, to="varying")
