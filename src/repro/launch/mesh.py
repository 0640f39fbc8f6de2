"""Production mesh construction.

Defined as a FUNCTION so importing this module never touches jax device
state.  Single pod: 16x16 = 256 chips, axes (data, model).  Multi-pod:
2x16x16 = 512 chips, axes (pod, data, model) — the ``pod`` axis composes
with ``data`` for batch/FSDP sharding; cross-pod traffic is the gradient/
FSDP all-reduce over DCI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (CPU) devices this host exposes —
    used by examples/tests; same axis names as production."""
    return jax.make_mesh((data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
