"""What one cell is: its entry in ``BENCHMARK.json`` and the files it names.

A cell names a configuration (``configs/<config>.json``, whose
``model_type`` names its architecture module ``chipbench/arch/<type>.py``)
and a traffic mix (``traffic/<traffic>.json``); its correctness limits sit
in ``limits/<cell>.json`` and each metric it reports is read by
``metrics/<metric>.py``.  Nothing here knows a cell or an architecture by
name, so a new cell is new files and a new ``workloads`` entry.
"""

from __future__ import annotations

import dataclasses
import json
import os

from . import arch as archs

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))

# keys that say where a configuration comes from and how it was cut, or how
# the program executes it (``execution``: ModelConfig's execution fields)
DESCRIBING = ("source", "model_type", "published", "deployment",
              "departures", "assumed", "execution")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file as run
    arch: object          # its architecture module (chipbench.arch.<type>)
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
    config = read_json(os.path.join(ROOT, conf["file"]))
    try:
        model_config(config, w["config"])
    except ValueError as e:
        raise SystemExit(f"{conf['file']}: {e}") from None
    return Cell(
        name=name, chips=w["chips"], config_name=w["config"],
        config=config, arch=archs.load(config["model_type"]),
        traffic_name=w["traffic"],
        traffic=read_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        limits=read_json(os.path.join(HERE, "limits", name + ".json")),
        )


def model_config(conf: dict, name: str):
    """The program's ``ModelConfig`` for a configuration file, built by the
    module of its ``model_type``.

    Every top-level key is one the module reads (``KEYS``), one it holds at
    the value the program computes (``FIXED``), or one that describes the
    file (``DESCRIBING``).  Any other key, or a fixed one at another value,
    raises ``ValueError`` naming it: the program would run without it.
    """
    module = archs.load(conf.get("model_type"))
    where = f"chipbench/arch/{module.__name__.rsplit('.', 1)[-1]}.py"
    for key in conf:
        if key not in DESCRIBING and key not in module.KEYS \
                and key not in module.FIXED:
            raise ValueError(f"{name}: key {key!r} is read by nothing: "
                             f"{where} neither reads nor fixes it")
    cfg = module.model_config(conf, name)
    for key, want in module.FIXED.items():
        want = want(cfg) if callable(want) else want
        if key in conf and conf[key] != want:
            raise ValueError(f"{name}: key {key!r} is {conf[key]!r}, but "
                             f"the program computes only {key} = {want!r} "
                             f"({where})")
    return cfg
