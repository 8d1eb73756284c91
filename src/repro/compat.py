"""The mesh surface every mesh-aware call site shares. ``jax.make_mesh``
defaults to Explicit axes, while the sharding rules here rely on Auto
(GSPMD-propagated) axes; ``jax.sharding.get_abstract_mesh`` returns an
empty mesh, not None, outside any mesh scope. Imports only jax — safe from
any module without cycles.
"""
from __future__ import annotations

import jax


def ambient_mesh():
    """The mesh set by ``jax.set_mesh`` currently in scope, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def make_mesh(shape, axes):
    """A mesh over local devices whose axes are all Auto (GSPMD propagates
    shardings)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
