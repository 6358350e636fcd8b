"""What one cell is: its entry in ``BENCHMARK.json`` and the files it names.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); its correctness limits sit in
``limits/<cell>.json`` and each metric it reports is read by
``metrics/<metric>.py``.  Nothing here knows a cell by name, so a new cell
is new files and a new ``workloads`` entry.
"""

from __future__ import annotations

import dataclasses
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))

# HF config.json key -> ModelConfig field, for the keys the model reads
_MODEL_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
}
_ACTS = {"silu": "swiglu"}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file as run
    traffic_name: str
    traffic: dict         # the traffic mix's parameters
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list
    limits: dict          # correctness limits of this cell


def read_json(path):
    with open(path) as f:
        return json.load(f)


def _reports(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


def load_cell(name, bench_path=None):
    bench = read_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=w["chips"], config_name=w["config"],
        config=read_json(os.path.join(ROOT, conf["file"])),
        traffic_name=w["traffic"],
        traffic=read_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        limits=read_json(os.path.join(HERE, "limits", name + ".json")),
        )


def model_config(conf: dict, name: str):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models import ModelConfig

    kw = {field: conf[key] for key, field in _MODEL_KEYS.items()
          if key in conf}
    kw["act"] = _ACTS[conf["hidden_act"]]
    kw["dtype"] = conf["torch_dtype"]
    kw.update(conf.get("execution", {}))
    return ModelConfig(name=name, **kw)
