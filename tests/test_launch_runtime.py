"""Process set-up helpers shared by the launchers and chip_smoke.py."""
import pathlib

import jax
import pytest

from repro.launch import runtime

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert runtime.compile_cache_dir() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = runtime.enable_compile_cache()
    assert d == runtime.compile_cache_dir() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == d
    assert pathlib.Path(d).resolve().is_relative_to(REPO)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_force_host_devices_only_on_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert not runtime.force_host_devices(8)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2")
    assert not runtime.force_host_devices(8)


@pytest.mark.parametrize("requested,n,want", [
    ((4, 2), 8, (4, 2)),
    ((4, 2), 1, (1, 1)),
    ((4, 2), 4, (2, 2)),
    ((1, 4), 4, (1, 4)),
    ((2, 16, 16), 4, (1, 4)),
    ((4, 3), 4, (4, 1)),
])
def test_fit_mesh_shape(requested, n, want):
    assert runtime.fit_mesh_shape(requested, n) == want
