"""BENCHMARK.json and the files a cell's names lead to.

Everything that belongs to one configuration, one traffic mix, one job
kind or one per-layer metric sits in a file of its own, found by name:
  configs/<config>.json   traffic/<traffic>.json
  jobs/<job>.py           metrics/<metric>.py
A new cell, configuration, job kind or metric is new files plus new
entries in BENCHMARK.json; no file here is edited for it.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module, by file."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind}/{name}.py under {BENCH_DIR}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell: str, reported: set | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def resolve(workload: str) -> dict:
    """The cell: its entry, configuration, traffic and the metrics it
    reports (end to end, per layer)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"workload {workload!r} is not in BENCHMARK.json "
                       f"({sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    end_to_end = [m for m in bench["end_to_end"]
                  if _applies(m, workload, None)]
    names = {m["name"] for m in end_to_end}
    return {
        "cell": cell,
        "config": load_json(ROOT, configs[cell["config"]]["file"]),
        "traffic": load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json"),
        "end_to_end": end_to_end,
        "per_layer": [m for m in bench["per_layer"]
                      if _applies(m, workload, names)],
    }
