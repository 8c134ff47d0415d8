"""A configuration, a traffic mix and a metric added as new files are
found by their names, with no edit to the harness."""
import json
import pathlib

import pytest

from benchlib import spec

import tiny


def test_cell_parts_found_by_name(tmp_path):
    d = tiny.make(tmp_path)
    (d / "traffic" / "burst.json").write_text(json.dumps(dict(
        tiny.MIX, arrivals={"process": "poisson", "rate_per_step": 0.2})))
    conf = json.loads((d / "configs" / "tiny.json").read_text())
    conf["num_hidden_layers"] = 3
    (d / "configs" / "tiny3.json").write_text(json.dumps(conf))
    (d / "metrics" / "answer.burst.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx else None\n")
    bench = json.loads((d / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny3.burst", "config": "tiny3",
                               "traffic": "burst", "chips": 1, "why": "t"})
    bench["per_layer"].append({
        "name": "answer.burst", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ttft_p90_s", "workloads": ["tiny3.burst"]})
    (d / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("tiny3.burst", d / "BENCHMARK.json", d)
    assert cell.config["num_hidden_layers"] == 3
    assert cell.traffic["arrivals"]["rate_per_step"] == 0.2
    assert [m["name"] for m in cell.per_layer] == ["answer.burst"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}
    assert cell.metric_reader("answer.burst")(object()) == 42.0
    reqs = cell.generator().generate(cell.traffic, 1, 256)
    assert len(reqs) == tiny.MIX["n_requests"]
    assert hasattr(cell.reference(), "reference_logits")


def test_unknown_names_are_errors(tmp_path):
    d = tiny.make(tmp_path)
    with pytest.raises(KeyError):
        spec.load_cell("no.such", d / "BENCHMARK.json", d)
    with pytest.raises(KeyError):
        spec.peaks("TPU v99", d)
    assert spec.peaks("TPU v5 lite", d)["int8_ops_per_s"] == 393e12


def test_benchmark_metrics_have_readers():
    """Every per-layer metric of BENCHMARK.json has its reader file, and
    every cell reports the end-to-end metric each of its metrics moves."""
    bench = json.loads((spec.REPO_ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert (spec.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)
        assert (spec.BENCH_DIR / "limits" / f"{w['name']}.json").is_file()
