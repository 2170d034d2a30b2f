"""Production mesh construction (FUNCTION, not module constant: importing
this module never touches jax device state)."""
from __future__ import annotations

import jax

from ..parallel.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds the 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(max_devices: int | None = None):
    """Largest (data, model) mesh from the live device set (elastic path)."""
    n = len(jax.devices()) if max_devices is None else min(max_devices, len(jax.devices()))
    # squarest factorization with model <= data
    best = (n, 1)
    for m in range(1, int(n ** 0.5) + 1):
        if n % m == 0:
            best = (n // m, m)
    return make_mesh(best, ("data", "model"))
