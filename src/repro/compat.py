"""Shared spellings of the JAX mesh APIs every mesh-using module calls.

``shard_map`` with replication checking off, ``make_mesh`` with Auto-mode
axes, and two small accessors; call sites go through these so the chosen
options live in one file.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def shard_map(f, mesh, in_specs, out_specs, auto=frozenset()):
    """jax.shard_map with replication checking off."""
    kw = {"check_vma": False}
    if auto:
        kw["auto"] = frozenset(auto)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def axis_size(axis_name) -> int:
    """STATIC size of a mapped axis, inside shard_map/pmap."""
    return jax.lax.axis_size(axis_name)


def cost_analysis(compiled) -> dict:
    """compiled.cost_analysis() as a dict (empty when XLA reports none)."""
    return compiled.cost_analysis() or {}


def make_mesh(shape, axes) -> Mesh:
    """Mesh over the first prod(shape) devices with Auto-mode axes."""
    shape = tuple(shape)
    return jax.make_mesh(shape, tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))
