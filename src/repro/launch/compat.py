"""The ambient-mesh API the launchers and model-side sharding hints use.

  * :func:`mesh_context`  — context manager installing ``mesh`` as ambient
    (``jax.set_mesh``).
  * :func:`ambient_mesh`  — the ambient abstract mesh, or ``None`` when no
    mesh context is active.
"""
from __future__ import annotations

import jax

mesh_context = jax.set_mesh


def ambient_mesh():
    """The mesh installed by :func:`mesh_context`, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh
