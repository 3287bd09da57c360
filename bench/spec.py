"""The benchmark's data, found by name.

``BENCHMARK.json`` at the checkout root names the cells; everything that
belongs to one configuration, one traffic mix, one cell or one per-layer
metric sits in a file of its own under ``bench/``:

* ``bench/configs/<config>.json``   the deployment's sizes, its reference,
                                    its comparison, its faults and the
                                    size the harness's CPU tests run it at
                                    (``cpu``);
* ``bench/traffic/<traffic>.json``  the mix's parameters and its driver;
* ``bench/cells/<cell>.json``       the cell's correctness limits;
* ``bench/metrics/<metric>.py``     one per-layer metric's reader;
* ``bench/drivers/<driver>.py``     a general generator, named by a mix;
* ``bench/references/<ref>.py``     a plain reference, named by a config;
* ``bench/comparisons/<name>.py``   what ``correct`` compares (the numbers
                                    held to limits and printed, and what a
                                    user reads of a run), named by a config;
* ``bench/faults/<name>.py``        the faults planted in the timed path
                                    that must fail ``correct``, named by a
                                    config.

Adding a cell, a mix, a configuration or a metric adds files and entries;
no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (metric names carry dots, so the module
    system's dotted names cannot address them)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    params: Dict[str, Any] = field(default_factory=dict)
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)
    root: str = ROOT

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, "bench", *parts)

    def driver(self):
        name = self.traffic["driver"]
        return load_module(self.path("drivers", f"{name}.py"),
                           f"bench_driver_{name}")

    def reference(self):
        name = self.config["reference"]
        return load_module(self.path("references", f"{name}.py"),
                           f"bench_reference_{name}")

    def comparison(self):
        name = self.config["comparison"]
        return load_module(self.path("comparisons", f"{name}.py"),
                           f"bench_comparison_{name}")

    def faults(self):
        name = self.config["faults"]
        return load_module(self.path("faults", f"{name}.py"),
                           f"bench_faults_{name}")

    def metric_reader(self, name: str):
        return load_module(self.path("metrics", f"{name}.py"),
                           f"bench_metric_{name}")


def _reports(metric: Dict[str, Any], cell: str,
             end_to_end: List[Dict[str, Any]]) -> bool:
    """Whether a cell reports a metric: its ``workloads`` list, or, for an
    end-to-end metric without one, every cell."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if metric.get("moves"):
        return any(m["name"] == metric["moves"] and _reports(m, cell, [])
                   for m in end_to_end)
    return True


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config_file = configs[w["config"]]["file"]
    config = load_json(os.path.join(root, config_file))
    unnamed = [k for k in ("comparison", "faults") if k not in config]
    if unnamed:
        raise KeyError(f"{config_file} names no {' and no '.join(unnamed)}:"
                       f" every configuration names what its correct "
                       f"compares and the faults that must fail it")
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{w['traffic']}.json"))
    cell_file = load_json(os.path.join(root, "bench", "cells",
                                       f"{name}.json"))
    e2e = [m for m in bench["end_to_end"]
           if _reports(m, name, bench["end_to_end"])]
    layer = [m for m in bench["per_layer"]
             if _reports(m, name, bench["end_to_end"])]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=cell_file["limits"],
                params=cell_file.get("params", {}), end_to_end=e2e,
                per_layer=layer, root=root)
