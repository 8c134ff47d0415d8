"""A toy benchmark directory for the tests: the real traffic generator,
adapters, references and metric readers, with a four-layer
configuration."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

MODEL = {"hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "num_hidden_layers": 4, "vocab_size": 256}
MIX = {"kind": "requests", "n_requests": 4000,
       "arrivals": {"process": "poisson", "rate_per_step": 0.05},
       "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                  "min": 12, "max": 48},
       "output": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                  "min": 4, "max": 12}}
# Readings of the toy cell on the CPU: sound runs read a widest gap of
# 0.13-0.74 and a mean gap of 0.004-0.021; the int4 control 0.81-1.88 and
# 0.14-0.24.  The limits lie between; the mean gap separates the two.
LIMITS = {"widest_gap": 1.0, "mean_gap": 0.06}


def make(tmp: pathlib.Path, plan=None) -> pathlib.Path:
    """A benchmark directory under `tmp` holding cell ``tiny.chat``."""
    d = tmp / "bench"
    for sub in ("adapters", "traffic", "references", "metrics"):
        shutil.copytree(BENCH / sub, d / sub)
    shutil.copy(BENCH / "peaks.json", d / "peaks.json")
    (d / "configs").mkdir()
    (d / "limits").mkdir()
    conf = json.loads((BENCH / "configs" / "qwen3-8b.json").read_text())
    conf.update(MODEL)
    conf["engine"].update(max_batch=2, plan=plan or {"default": "w8a8"})
    (d / "configs" / "tiny.json").write_text(json.dumps(conf))
    (d / "traffic" / "tiny-chat.json").write_text(json.dumps(MIX))
    (d / "limits" / "tiny.chat.json").write_text(
        json.dumps({k: {"limit": v} for k, v in LIMITS.items()}))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                           "traffic": "tiny-chat", "chips": 1, "why": "t"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.chat"] if (
                m["name"].endswith(".chat") or m["name"] in (
                    "ttft_p90_s", "tpot_p90_s")) else []
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    return d
