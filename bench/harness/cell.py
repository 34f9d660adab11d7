"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell names a configuration (``bench/configs/<config>.json``, whose
``family`` names ``bench/families/<family>/``), a traffic mix
(``bench/traffic/<traffic>.json``, whose ``mode`` names the general
generator in ``bench/harness/<mode>.py``), and its metrics, each read by
``bench/metrics/<metric>.py``.  Adding a cell is adding files and entries.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(bench: dict, workload: str) -> dict:
    """The cell ``workload`` with its configuration, traffic mix and the
    metrics it reports, each metric as its ``BENCHMARK.json`` entry."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"choose from {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))

    def reports(m):
        return workload in m.get("workloads", [workload])

    end_to_end = [m for m in bench["end_to_end"] if reports(m)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if reports(m) and m["moves"] in moved]
    return {"name": workload, "chips": w["chips"], "config": config,
            "traffic": traffic, "end_to_end": end_to_end,
            "per_layer": per_layer}


def family(config: dict) -> tuple:
    """(program, ref, work) modules of the configuration's family."""
    pkg = f"bench.families.{config['family']}"
    return tuple(importlib.import_module(f"{pkg}.{m}")
                 for m in ("program", "ref", "work"))


def mode(traffic: dict):
    return importlib.import_module(f"bench.harness.{traffic['mode']}")


def metric_reader(name: str):
    """The reader module of metric ``name`` (``bench/metrics/<name>.py``;
    loaded by path, so a name may hold dots)."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
