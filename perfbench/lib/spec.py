"""Find everything of a cell by its name.

``BENCHMARK.json`` names the cell's configuration and traffic mix; each is a
file of its own (``configs/<config>.json``, ``traffic/<traffic>.json``), as
are the limits of the cell's check (``limits/<cell>.json``) and the reader
of each per-layer metric (``metrics/<metric>.py``, a ``read(ctx)`` function).
A later cell, mix or metric is a new file and a new entry, and no file here
changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; there are "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(os.path.join(ROOT, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict:
    return _json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def limits(cell_name: str) -> Dict[str, float]:
    return _json(os.path.join(BENCH_DIR, "limits", f"{cell_name}.json"))["limits"]


def metrics_of(bench: Dict, cell_name: str, key: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell_name``
    reports (those without a ``workloads`` list go to every cell)."""
    return [m for m in bench[key]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(metric: str) -> Callable:
    """The ``read(ctx)`` function of a per-layer metric."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
