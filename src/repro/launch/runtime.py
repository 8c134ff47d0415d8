"""Process set-up shared by the launchers and ``chip_smoke.py``.

* :func:`enable_compile_cache` — JAX's persistent compilation cache.  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  here overrides it; otherwise the cache lives at one fixed path inside
  the checkout (:data:`DEFAULT_CACHE_DIR`, gitignored), so a second run of
  the same programs finds their executables again.
* :func:`host_platform` / :func:`force_host_devices` — the virtual-device
  count is a CPU-only knob; on an accelerator the device count is what
  ``jax.devices()`` reports.
* :func:`fit_mesh_shape` — a ``(data, model)`` mesh shape for the devices
  actually present when the requested one does not fit them.

Nothing here touches a JAX backend at import time.
"""
from __future__ import annotations

import math
import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory JAX's persistent compilation cache uses."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_CACHE_DIR))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Must run before the first compile of the process."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return compile_cache_dir()


def host_platform() -> str:
    """The platform ``JAX_PLATFORMS`` pins first, or "" when unpinned (JAX
    then picks the accelerator if one is present)."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()


def force_host_devices(n: int) -> bool:
    """Ask for `n` virtual CPU devices when the CPU platform is pinned and
    ``XLA_FLAGS`` does not already set the count; a no-op elsewhere.
    Must run before JAX initialises its backends.  Returns whether the
    count was forced."""
    if host_platform() != "cpu" or "xla_force_host_platform_device_count" \
            in os.environ.get("XLA_FLAGS", ""):
        return False
    import jax
    jax.config.update("jax_num_cpu_devices", int(n))
    return True


def fit_mesh_shape(requested: tuple[int, ...], n_devices: int
                   ) -> tuple[int, ...]:
    """`requested` when its product is `n_devices`; else a ``(data,
    model)`` shape over all `n_devices` that keeps as much of the
    requested model (last-axis) degree as divides the device count."""
    if math.prod(requested) == n_devices:
        return tuple(requested)
    model = math.gcd(int(requested[-1]), n_devices)
    return (n_devices // model, model)


class CompileMeter:
    """Counts this process's XLA compilations from JAX's monitoring
    events: programs compiled (or fetched from the persistent cache), the
    seconds spent in backend compilation including cache reads, and the
    persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.programs = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[int, float, int, int]:
        return self.programs, self.seconds, self.hits, self.misses
