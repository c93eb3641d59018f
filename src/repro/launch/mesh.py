"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the wrapper-library discipline of
repro.runtime.backend — device count is locked at first query, and
dryrun.py needs to set XLA_FLAGS before that happens).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) (data, model).  Two pods: (2, 16, 16)
    (pod, data, model) — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Small mesh for subprocess tests (8 host devices)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh() -> Mesh:
    """Whatever devices exist, as a 1D data mesh (CPU smoke runs)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), (AxisType.Auto,))
