"""Mesh construction — the one place a ``jax.sharding.Mesh`` is made.

Every mesh here has ``AxisType.Auto`` axes. Since jax 0.7,
``jax.make_mesh`` defaults to Explicit axes, under which the shard_map
fits (``parallel/runner.py``) and the LM sharding rules
(``parallel/sharding.py``) are rejected: those paths are written for
compiler-propagated (Auto) sharding.

These are FUNCTIONS (not module-level constants) so that importing this
module never touches jax device state — the dry-run sets XLA_FLAGS before
any jax initialization.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """Mesh of ``shape`` over named ``axes``, every axis Auto. ``devices``
    defaults to ``jax.devices()``; pass a topology's described devices to
    compile for a chip that is not attached."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def gp_machine_axes(mesh) -> tuple[str, ...]:
    """The paper's M machines = all DP axes of the mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
