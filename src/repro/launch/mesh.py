"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state.  Production target: TPU v5e pods, 256 chips/pod
(16×16), optionally 2 pods (the "pod" axis is the DCN/elastic axis — the
Lambada analogue in DESIGN.md §2).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/benchmarks (host-device counts permitting)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
