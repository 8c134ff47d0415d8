"""The harness end to end at a toy size on the CPU: a sound run is
correct, the int4 control and each fault of the served path are caught,
and without a TPU the command prints no result."""
import functools
import pathlib
import subprocess
import sys

import pytest

from benchlib import spec

import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]
run = spec.load_module(BENCH / "run.py", "bench_run")
SEED = 2**32 + 77           # a seed above 32 bits


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    d = tiny.make(tmp_path_factory.mktemp("sound"))
    return spec.load_cell("tiny.chat", d / "BENCHMARK.json", d)


def _run(cell, tmp_path, **kw):
    return run.run_cell(cell, SEED, 1.5, False, kv_blocks=64,
                        steps_per_s=100, trace_dir=tmp_path / "trace", **kw)


def test_no_tpu_no_result():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen3-8b.chat-poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_sound_run_is_correct(cell, tmp_path):
    res = _run(cell, tmp_path)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    for name, limit in tiny.LIMITS.items():
        assert res["checks"][name]["limit"] == limit
        assert res["checks"][name]["value"] <= limit
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"setup_s", "ttft_p90_s", "tpot_p90_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert res["device"]["platform"] == "cpu"


def test_int4_control_is_not_correct(cell, tmp_path):
    """The reference at int4 weights, put in the served tokens' place,
    goes through the run's own limits and comes out not correct."""
    res = _run(cell, tmp_path, control=True)
    assert res["correct"] is False
    assert res["checks"]["sampled_complete"]["value"] > 0
    assert any(res["checks"][n]["value"] > limit
               for n, limit in tiny.LIMITS.items())


def _altered_sampler(orig):
    @functools.wraps(orig)
    def make_sample(self, plan, greedy):
        sample = orig(self, plan, greedy)

        def wrong(logits, *a):
            return (sample(logits, *a) + 1) % self.cfg.vocab
        return wrong
    return make_sample


def _stale_decode(orig):
    @functools.wraps(orig)
    def decode_step(params, batch, caches, cfg, **kw):
        logits, _ = orig(params, batch, caches, cfg, **kw)
        return logits, caches           # the KV pool comes back unchanged
    return decode_step


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_fault_is_not_correct(fault, cell, tmp_path, monkeypatch):
    from repro.models import model as model_lib
    from repro.serve import engine as engine_lib
    if fault == "token_altered":
        monkeypatch.setattr(engine_lib.Engine, "make_sample",
                            _altered_sampler(engine_lib.Engine.make_sample))
    else:
        monkeypatch.setattr(model_lib, "decode_step",
                            _stale_decode(model_lib.decode_step))
    res = _run(cell, tmp_path)
    assert res["correct"] is False
    assert any(res["checks"][n]["value"] > limit
               for n, limit in tiny.LIMITS.items())
