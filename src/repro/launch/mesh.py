"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE any jax
import; smoke tests see the real (1-device) CPU.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests/examples (e.g. (2,2,2) on 8 fake devices).

    Axes are ``Auto``: the model code places activations with
    ``with_sharding_constraint``, which ``jax.make_mesh``'s default
    ``Explicit`` axes refuse.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
