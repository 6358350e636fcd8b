"""Each metric is read by ``metrics/<name>.py``, whose ``read(run)`` returns
a number, or None where the run holds nothing for it to read."""

from __future__ import annotations

import importlib.util
import os

from .spec import HERE


def reader(name):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(entries, run):
    out = {}
    for m in entries:
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
