"""Finding a cell's parts by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under the benchmark's
directory, and is found here by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   the model configuration as it is run;
  its ``adapter`` and ``reference`` keys name the two files below;
* ``adapters/<name>.py``      what the harness knows of an architecture:
  ``program_config(conf)`` (the program's ``ModelConfig``),
  ``WEIGHT_RULES`` (leaf name -> rule, beyond ``model.COMMON_RULES``),
  ``reference_weights(params, conf)`` (the frozen tree under the
  reference's names), ``model_ops(conf, tokens, heads_out, ctx_sum)``,
  ``kv_layout(cfg)`` (KV heads, head width, query heads per KV head) and
  ``stand_in_config(cfg)`` (every width 1, for the host stand-in);
* ``references/<name>.py``    the plain reference: ``reference_logits(
  weights, model, tokens, read_at, *, weight_bits)`` and ``jitted(model,
  weight_bits)``, its jitted forward;
* ``traffic/<mix>.json``      a generator ``kind`` plus its parameters,
  read by ``traffic/<kind>.py``;
* ``metrics/<metric>.py``     one reader per per-layer metric;
* ``limits/<cell>.json``      the limit of each number the check compares;
* ``peaks.json``              device peaks keyed by ``device_kind``.

A later change adds a cell, of an architecture the harness has not seen
too, by adding files and entries; nothing here or elsewhere in
``benchlib/`` or ``run.py`` needs an edit for it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_DIR.parent


def load_module(path: pathlib.Path, name: str | None = None):
    """Import the Python file `path` (its name may hold dots)."""
    name = name or "bench_" + "_".join(path.with_suffix("").parts[-2:]) \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its parts loaded."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict                 # configs/<config>.json
    adapter: object              # adapters/<name>.py the config names
    traffic: dict                # traffic/<mix>.json
    end_to_end: list[dict]       # the end-to-end metrics this cell reports
    per_layer: list[dict]        # the per-layer metrics this cell reports
    bench_dir: pathlib.Path

    def generator(self):
        """The traffic generator module named by the mix's ``kind``."""
        return load_module(self.bench_dir / "traffic"
                           / f"{self.traffic['kind']}.py")

    def reference(self):
        """The plain reference module the configuration names."""
        return load_module(self.bench_dir / "references"
                           / f"{self.config['reference']}.py")

    def metric_reader(self, name: str):
        """``read(ctx)`` of the per-layer metric `name`."""
        return load_module(self.bench_dir / "metrics" / f"{name}.py").read


def load_adapter(config: dict, config_path: pathlib.Path,
                 bench_dir: pathlib.Path):
    """The adapter module the configuration file names.  A file that
    names none, or one that does not exist, is an error naming the file."""
    name = config.get("adapter")
    if name is None:
        raise ValueError(f"{config_path} names no adapter: give it "
                       f"\"adapter\": \"<name>\" of adapters/<name>.py")
    path = bench_dir / "adapters" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{config_path} names adapter {name!r}, "
                                f"but {path} does not exist")
    return load_module(path)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_json: pathlib.Path | None = None,
              bench_dir: pathlib.Path | None = None) -> Cell:
    """The workload `name` of `bench_json` (default: ``BENCHMARK.json`` at
    the root of the checkout), its parts read from `bench_dir` (default:
    this benchmark's directory)."""
    bench_dir = bench_dir or BENCH_DIR
    bench = read_json(bench_json or REPO_ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    config_path = bench_dir / "configs" / f"{w['config']}.json"
    config = read_json(config_path)
    traffic = read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config,
        adapter=load_adapter(config, config_path, bench_dir), traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        bench_dir=bench_dir)


def peaks(device_kind: str, bench_dir: pathlib.Path | None = None) -> dict:
    """Published peaks of `device_kind`.  A kind missing from the table is
    an error, never a default."""
    table = read_json((bench_dir or BENCH_DIR) / "peaks.json")
    kinds = table["devices"]
    if device_kind not in kinds:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(kinds)})")
    return kinds[device_kind]
