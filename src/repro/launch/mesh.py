"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state - required by the dry-run, whose
``XLA_FLAGS`` must be set before any jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production meshes: 16x16 single pod (256 chips),
    2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """All local devices on a single 'data' axis (tests, examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
