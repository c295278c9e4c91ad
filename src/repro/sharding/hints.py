"""In-model sharding constraints that degrade to no-ops without a mesh.

Model code calls ``constrain(x, *axes)`` with logical placements; if a global
mesh context is active (jax.sharding.set_mesh — done by the launchers), a
with_sharding_constraint is emitted using only the axes that exist on that
mesh; otherwise the call is a no-op so single-device tests and examples are
unaffected.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, PartitionSpec as P


def _mesh_axis_names():
    """Names of AUTO axes on the active abstract mesh (manual shard_map axes
    must not appear in sharding constraints); () without a mesh."""
    m = jax.sharding.get_abstract_mesh()
    return tuple(a for a, t in zip(m.axis_names, m.axis_types)
                 if t == AxisType.Auto)


def batch_axes():
    names = _mesh_axis_names()
    return tuple(a for a in ("pod", "data") if a in names)


def constrain(x, *placements):
    """placements: per-dim placement; each is None, an axis name, 'batch'
    (expands to the replica axes present), or a tuple of axis names. Axes not
    present on the active mesh are dropped; without a mesh this is identity.
    """
    names = _mesh_axis_names()
    if not names:
        return x
    parts = []
    for pl in placements:
        if pl is None:
            parts.append(None)
        elif pl == "batch":
            ba = batch_axes()
            parts.append(ba if ba else None)
        elif isinstance(pl, tuple):
            keep = tuple(a for a in pl if a in names)
            parts.append(keep if keep else None)
        else:
            parts.append(pl if pl in names else None)
    return jax.lax.with_sharding_constraint(x, P(*parts))
