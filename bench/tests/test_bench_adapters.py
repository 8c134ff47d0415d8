"""Architecture adapters: the dense adapter gives the weights, the
reference's names and the operation count the harness gave before the
architecture code moved into ``adapters/`` (pinned numbers); a
configuration that exists only as added files, with an adapter and a
reference of its own, runs through ``run_cell`` to a correct result; a
configuration that names no adapter, or a missing one, is an error that
names the file."""
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from benchlib import model as bm, readers, spec

import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]
PINNED = json.loads((BENCH / "tests" / "data" / "pinned_weights_ops.json")
                    .read_text())
run = spec.load_module(BENCH / "run.py", "bench_run_adapters")
SEED = 2**32 + 53


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    d = tiny.make(tmp_path_factory.mktemp("adapters"))
    return spec.load_cell("tiny.chat", d / "BENCHMARK.json", d)


def _leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path): x
            for path, x in flat}


def _weights(cell, seed: int):
    conf, ad = cell.config, cell.adapter
    a_scale = float(conf["engine"]["a_scale"])
    shapes = bm.frozen_shapes(ad.program_config(conf),
                              bm.deployment_plan(conf), a_scale)
    return bm.make_weights(shapes, seed, a_scale, ad.WEIGHT_RULES)


@pytest.mark.parametrize("seed", sorted(PINNED["weights"]))
def test_weights_match_pinned(seed, tiny_cell):
    """Every leaf bit for bit: same leaves, same key order, same rules."""
    got = {}
    for name, x in _leaves(_weights(tiny_cell, int(seed))).items():
        x = np.asarray(x)
        got[name] = [float(x.astype(np.float64).sum()),
                     hashlib.sha256(x.tobytes()).hexdigest()[:16]]
    assert got == PINNED["weights"][seed]


def test_reference_weights_match_pinned(tiny_cell):
    """The reference's dict holds the frozen tree's own arrays under the
    pinned names."""
    params = _weights(tiny_cell, int(min(PINNED["weights"])))
    frozen = {id(x): name for name, x in _leaves(params).items()}
    ref = tiny_cell.adapter.reference_weights(params, tiny_cell.config)
    assert {name: frozen.get(id(x)) for name, x in _leaves(ref).items()} \
        == PINNED["reference"]


@pytest.mark.parametrize("tokens,heads_out,ctx_sum,ops",
                         PINNED["model_ops"])
def test_model_ops_match_pinned(tokens, heads_out, ctx_sum, ops):
    cell = spec.load_cell("qwen3-8b.chat-poisson")
    assert cell.adapter.model_ops(cell.config, tokens, heads_out,
                                  ctx_sum) == ops


@pytest.mark.parametrize("adapter", [None, "no_such_arch"])
def test_adapter_named_and_present(adapter, tmp_path):
    d = tiny.make(tmp_path)
    path = d / "configs" / "tiny.json"
    conf = json.loads(path.read_text())
    if adapter is None:
        del conf["adapter"]
    else:
        conf["adapter"] = adapter
    path.write_text(json.dumps(conf))
    with pytest.raises((ValueError, FileNotFoundError)) as err:
        spec.load_cell("tiny.chat", d / "BENCHMARK.json", d)
    assert str(path) in str(err.value)
    if adapter is not None:
        assert str(d / "adapters" / f"{adapter}.py") in str(err.value)


# A configuration the harness has not seen: the dense decoder served
# with a float output head (an extra leaf ``lm_head/w``), its own
# adapter and reference, and twice the dense operation count.
TOY_ADAPTER = '''\
"""Toy adapter: the dense decoder with a float output head."""
import pathlib

import jax
import jax.numpy as jnp

from benchlib import spec

dense = spec.load_module(pathlib.Path(__file__).with_name(
    "dense_decoder.py"), "toy_dense_adapter")
program_config = dense.program_config
kv_layout = dense.kv_layout
stand_in_config = dense.stand_in_config


def _float_w(key, shape, dtype, sibling_k, a_scale):
    w = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    return w.astype(dtype)


WEIGHT_RULES = {**dense.WEIGHT_RULES, "w": _float_w}


def reference_weights(params, conf):
    int8_head = dict.fromkeys(("w_q", "w_scale", "a_scale"))
    out = dense.reference_weights({**params, "lm_head": int8_head}, conf)
    out["lm_head"] = {"w": params["lm_head"]["w"]}
    return out


def model_ops(conf, tokens, heads_out, ctx_sum):
    return 2.0 * dense.model_ops(conf, tokens, heads_out, ctx_sum)
'''

TOY_REFERENCE = '''\
"""Toy reference: the dense decoder's, with a float output head."""
import pathlib

import jax.numpy as jnp

from benchlib import spec

dense = spec.load_module(pathlib.Path(__file__).with_name(
    "dense_decoder.py"), "toy_dense_reference")
_int8_linear = dense._linear


def _linear(x, p, weight_bits):
    if "w_scale" not in p:
        return jnp.dot(x, p["w"].astype(jnp.float32))
    return _int8_linear(x, p, weight_bits)


dense._linear = _linear
jitted, reference_logits = dense.jitted, dense.reference_logits
'''


def _toy_cell(tmp_path):
    """Cell ``toy.chat``, added to a toy benchmark directory as new files
    and entries alone."""
    d = tiny.make(tmp_path)
    (d / "adapters" / "toy_float_head.py").write_text(TOY_ADAPTER)
    (d / "references" / "toy_float_head.py").write_text(TOY_REFERENCE)
    conf = json.loads((d / "configs" / "tiny.json").read_text())
    conf.update(adapter="toy_float_head", reference="toy_float_head")
    conf["engine"]["plan"] = {"default": "w8a8",
                              "rules": [["lm_head", {"backend": "exact"}]]}
    (d / "configs" / "toy.json").write_text(json.dumps(conf))
    (d / "limits" / "toy.chat.json").write_text(
        (d / "limits" / "tiny.chat.json").read_text())
    bench = json.loads((d / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "toy.chat", "config": "toy",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.chat" in m.get("workloads", ()):
            m["workloads"].append("toy.chat")
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    return d, spec.load_cell("toy.chat", d / "BENCHMARK.json", d)


def test_added_architecture_runs_correct(tmp_path, monkeypatch):
    d, cell = _toy_cell(tmp_path)
    shapes = bm.frozen_shapes(cell.adapter.program_config(cell.config),
                              bm.deployment_plan(cell.config), 0.05)
    assert "lm_head/w" in _leaves(shapes)   # made by the toy's rule alone
    windows = []
    serve = run.serve

    def keep_window(*a, **kw):
        w, marks = serve(*a, **kw)
        windows.append(w)
        return w, marks
    monkeypatch.setattr(run, "serve", keep_window)
    res = run.run_cell(cell, SEED, 1.5, False, kv_blocks=64,
                       steps_per_s=100, trace_dir=tmp_path / "trace")
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["sampled_complete"]["value"] > 0

    def mfu(adapter):
        return readers.mfu(readers.Context(
            window=windows[0], conf=cell.config, adapter=adapter,
            settings=None, kv_blocks=0, peaks={"int8_ops_per_s": 393e12},
            compiles_in_window=0))
    dense = spec.load_module(d / "adapters" / "dense_decoder.py")
    assert mfu(dense) > 0
    assert mfu(cell.adapter) == 2 * mfu(dense)
