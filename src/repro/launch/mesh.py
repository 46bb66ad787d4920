"""Production mesh definition (functions only — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips) mesh.

    Axes: ``data`` = batch/DP (+ZeRO), ``model`` = TP/EP, ``pod`` = DP
    across pods (gradient all-reduce crosses the inter-pod links only on
    this axis; TP stays inside a pod).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
