"""One spelling for the mesh and shard_map calls used across the repo.

``make_mesh`` and ``abstract_mesh`` give every axis the Auto type (jax's own
``make_mesh`` defaults to Explicit); ``shard_map`` defaults ``check_vma`` to
False and makes every mesh axis manual unless ``axis_names`` says otherwise.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(axis_shapes, axis_names, **kwargs):
    """jax.make_mesh with every axis Auto."""
    kwargs.setdefault("axis_types", (AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


def abstract_mesh(axis_shapes, axis_names):
    """jax.sharding.AbstractMesh with every axis Auto."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names),
                        axis_types=(AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, check_vma=False):
    """jax.shard_map; ``axis_names`` are the MANUAL axes (default: all of
    them), every other mesh axis stays auto."""
    manual = set(axis_names) if axis_names is not None else set(mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=manual, check_vma=check_vma)
