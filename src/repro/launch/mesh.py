"""Production mesh construction (deliverable e).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state."""
from __future__ import annotations

import jax

from repro.sharding.rules import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Single-process CPU mesh for tests/examples (1 device)."""
    n = len(jax.devices())
    if n >= 8:
        return auto_mesh((n // 4, 4), ("data", "model"))
    return auto_mesh((1, n), ("data", "model"))
