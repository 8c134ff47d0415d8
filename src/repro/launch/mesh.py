"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state; launch/dryrun.py must set XLA_FLAGS *before* calling these.
"""
from __future__ import annotations

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small host-device mesh for tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count>=n_data*n_model)."""
    return compat.make_mesh((n_data, n_model), ("data", "model"))
