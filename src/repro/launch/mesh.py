"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any
device query.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    The pod axis composes with data for batch DP — gradient all-reduce
    crosses the (slow, DCN-ish) pod axis exactly once per step.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over the actual local devices (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes: the sharding rules and
    ``sharding.constrain`` hand placement to GSPMD, which the default
    Explicit axes of current JAX refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
