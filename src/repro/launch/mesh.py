"""Device meshes for the sharded executor.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (device count is locked on first backend init,
and the main test session must see 1 CPU device while the sharded tests'
child processes see 8).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes.  JAX 0.9 makes Explicit axes the
    default, and an eager op on an array sharded over an Explicit mesh
    (the sharded executor's tombstone scatter, ``.at[].set``) then fails
    outside a ``jax.set_mesh`` context."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    return _auto_mesh((data, model), ("data", "model"))
