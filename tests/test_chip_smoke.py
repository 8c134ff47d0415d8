"""chip_smoke.py on the CPU: it refuses to report a result without a TPU,
and its served-path checks pass for the real engine at reduced width but
stop a decode that drops the newest token."""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro import configs  # noqa: E402
from repro.kernels.paged_attention import ops as pa_ops  # noqa: E402
from repro.launch import runtime  # noqa: E402


def test_cpu_run_exits_without_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.fixture
def small_smoke(monkeypatch):
    """The smoke's serving phase at reduced width: short prompts, a pool
    of fixed size, no device memory statistics (the CPU has none)."""
    monkeypatch.setattr(chip_smoke, "PROMPT_LENS",
                        (16, 100, 32, 77, 50, 128, 40, 64))
    monkeypatch.setattr(chip_smoke, "NEW_TOKENS", 8)
    monkeypatch.setattr(chip_smoke, "pool_blocks", lambda cfg, dev: 161)
    monkeypatch.setattr(chip_smoke, "peak_hbm", lambda devices: 0.0)
    def run(dtype="float32"):
        cfg = dataclasses.replace(
            configs.reduced_config("qwen3-8b", d_model=128, vocab=512),
            kv_cache_dtype="int8", dtype=dtype)
        chip_smoke.serve_checks(cfg, jax.devices()[0],
                                runtime.CompileMeter())
    return run


def test_served_checks_pass(small_smoke):
    small_smoke()


def test_served_checks_stop_a_decode_fault(small_smoke, monkeypatch):
    decode = pa_ops.flash_decode_jnp

    def drops_newest(qr, k_q, k_s, v_q, v_s, tables, n_valid, **kw):
        return decode(qr, k_q, k_s, v_q, v_s, tables,
                      jax.numpy.maximum(n_valid - 1, 1), **kw)

    monkeypatch.setattr(pa_ops, "flash_decode_jnp", drops_newest)
    with pytest.raises(AssertionError, match="stray further"):
        small_smoke()


def test_served_checks_tolerate_rounding(small_smoke, monkeypatch):
    """Two correct plans compiled differently round some activations
    differently before their int8 quantizers; in bf16 one such nudge
    moves the dense forward's logits by about a tenth of their rms, which
    the checks must accept."""
    from repro.core import backend as backend_lib
    matmul = backend_lib.W8A8KernelBackend._matmul

    def nudged(self, xq, *args, **kw):
        if xq.dtype != jax.numpy.int8:
            xq = xq * (1 + 2.0 ** -9)
        return matmul(self, xq, *args, **kw)

    monkeypatch.setattr(backend_lib.W8A8KernelBackend, "_matmul", nudged)
    small_smoke(dtype="bfloat16")
