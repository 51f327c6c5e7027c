"""Production meshes.

Functions (not module-level constants) so importing this module never
touches jax device state: jax locks the platform/device count on first use,
and only launch/dryrun.py is allowed to request 512 host devices.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: "pod" (cross-pod DCN/optical — data parallel only), "data"
    (in-pod DP/FSDP), "model" (TP/EP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    data = n // model_axis
    return jax.make_mesh(
        (data, model_axis), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
