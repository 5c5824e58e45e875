"""Production mesh definitions.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (device count is locked on first backend init, and
smoke tests must see 1 CPU device while the dry-run sees 512 placeholders).

Topology (TPU v5e pods): 16×16 = 256 chips per pod.  Multi-pod runs add a
leading `pod` axis; sharding specs compose it with `data` for DP/FSDP, so
the same rules lower unchanged at 2, 8, or 64 pods — the scaling story for
1000+ nodes is purely additive on this axis (cross-pod traffic is one
gradient all-reduce per step; all per-layer collectives stay inside a pod).
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes.  JAX 0.9 makes Explicit axes the
    default, and an eager op on an array sharded over an Explicit mesh
    (the sharded executor's tombstone scatter, ``.at[].set``) then fails
    outside a ``jax.set_mesh`` context."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    return _auto_mesh((data, model), ("data", "model"))


def dp_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """The composite data-parallel axis group for this mesh."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def axis_size(mesh: jax.sharding.Mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    out = 1
    for n in names:
        out *= mesh.shape[n]
    return out
