"""The serving launcher's entry points (the ones chip_smoke.py drives), at
the reduced width on the CPU."""
import dataclasses

import jax
import numpy as np

from repro import configs as cfg_lib
from repro.core import backend as backend_lib
from repro.launch import serve
from repro.serve import Request, RequestStatus

PLAN = backend_lib.DeploymentPlan(default="w8a8", paged_attn=True)


def _cfg():
    return dataclasses.replace(cfg_lib.reduced_config("qwen3-8b"),
                               kv_cache_dtype="int8")


def test_serve_continuous_chunked_matches_blocking():
    cfg = _cfg()
    params = serve.build_params(cfg, PLAN)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n), max_new=6)
            for i, n in enumerate((5, 40, 17))]
    kw = dict(max_batch=4, kv_blocks=32, block_size=8, segment_len=4)
    toks = {}
    for chunked in (True, False):
        ce, res, dt = serve.serve_continuous(params, cfg, reqs, plan=PLAN,
                                             chunked_prefill=chunked, **kw)
        assert dt > 0 and set(res) == {0, 1, 2}
        assert all(r.status is RequestStatus.OK and len(r.tokens) == 6
                   for r in res.values())
        report = serve.continuous_report(ce, res, dt, "t")
        assert "3/3 OK" in report and "paged-attn" in report
        toks[chunked] = [res[i].tokens[0] for i in range(3)]
    # Every prompt fits one chunk, so both prefills attend fp in-hand K/V.
    assert toks[True] == toks[False]


def test_generate_on_mesh_one_device():
    cfg = _cfg()
    mesh = serve.mesh_for((1, 1), devices=jax.devices()[:1])
    sh = serve.param_shardings(cfg, PLAN, mesh)
    params = serve.build_params(cfg, PLAN, shardings=sh)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    toks, logits = serve.generate_on_mesh(params, cfg, PLAN, mesh, sh,
                                          prompts, 3)
    assert toks.shape == (2, 3) and logits.shape == (2, cfg.vocab)
    assert np.isfinite(logits).all()
    np.testing.assert_array_equal(toks[:, 0], logits.argmax(-1))
