"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure DP across pods; the "model" axis is the regionally
reconfigurable high-bandwidth domain (DESIGN.md §5).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any import).
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_test_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: shard_map regions and
    pjit sharding propagation mix on the same mesh (jax's own default is
    ``Explicit``)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for multi-device tests (8 forced host devices)."""
    return make_mesh(shape, axes)
