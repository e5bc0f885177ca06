"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

A configuration is ``configs/<name>.json``, a traffic mix is
``traffic/<name>.json`` (data that the unit module named by its ``kind``
reads: ``units/<kind>.py``), a metric is ``metrics/<name>.py``, a reader
with ``read(records) -> float | None``, and an architecture family that
the plain reference does not restate itself is
``harness/families/<family>.py`` (``-`` read as ``_``).  Adding any of
them is adding a file and an entry; no file that exists needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: Dict, name: str, root: str = ROOT) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: str = BENCH_DIR) -> Dict:
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def unit(kind: str, bench_dir: str = BENCH_DIR):
    """The unit module that runs a traffic kind."""
    return _module(os.path.join(bench_dir, "units", f"{kind}.py"),
                   f"perfbench_unit_{kind}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    return _module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                   "perfbench_metric_" + name.replace(".", "_"))


def family(name: str, bench_dir: str = BENCH_DIR):
    """The file that restates an architecture family's layers for the
    plain reference; an error naming the path where there is none."""
    stem = name.replace("-", "_")
    path = os.path.join(bench_dir, "harness", "families", f"{stem}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"the reference does not restate family {name!r}: no {path}")
    return _module(path, f"perfbench_family_{stem}")


def _applies(metric: Dict, cell_name: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves") in e2e_names


def cell_metrics(bench: Dict, cell_name: str, traced: bool) -> List[Dict]:
    """The metrics a run of ``cell_name`` reports: the end-to-end ones
    untraced, the per-layer ones traced."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    if not traced:
        return e2e
    names = [m["name"] for m in e2e]
    return [m for m in bench["per_layer"] if _applies(m, cell_name, names)]
