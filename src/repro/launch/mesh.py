"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16)."""
from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    # The logical-axis rules (distributed/sharding.py) place arrays with
    # with_sharding_constraint, which needs Auto axes; make_mesh
    # defaults to Explicit.
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh():
    """Whatever this host has (1 CPU device in tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), axis_types=_auto(2))
