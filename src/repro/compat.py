"""Mesh and shard_map helpers shared by library code.

Thin aliases over the jax API with the repo's defaults: every mesh axis
is GSPMD-``Auto`` unless a ``shard_map`` binds it, and ``shard_map`` is
partial-manual (only ``axis_names`` are manual).

    from repro import compat
    compat.make_mesh / compat.set_mesh / compat.shard_map
    compat.get_abstract_mesh / compat.auto_axis_names
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """All-axes-Auto mesh (GSPMD-managed unless shard_map binds an axis)."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_shapes))


set_mesh = jax.set_mesh
get_abstract_mesh = jax.sharding.get_abstract_mesh


def auto_axis_names(mesh_like) -> tuple:
    """Names of the GSPMD-Auto axes of a (possibly abstract) mesh; meshes
    without type info are treated as all-Auto."""
    names = tuple(getattr(mesh_like, "axis_names", ()) or ())
    types = getattr(mesh_like, "axis_types", None)
    if types is None:
        return names
    return tuple(n for n, t in zip(names, types) if t == AxisType.Auto)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = True):
    """Partial-manual shard_map: ``axis_names`` are bound manual, every
    other mesh axis stays GSPMD-auto."""
    manual = (set(axis_names) if axis_names is not None
              else set(mesh.axis_names))
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=check_vma)
