"""Finds a cell, its configuration, traffic and metric readers by name.

Nothing here is specific to one cell: a new configuration, traffic mix or
metric is a new file plus a new entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    """Import a Python file by path (names may hold dots and dashes), once
    per path."""
    path = os.path.abspath(path)
    key = "benchmark_dyn_%s_%x" % (
        name.replace(".", "_").replace("-", "_"), hash(path) & 0xffffffff)
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str
    readers: Dict[str, ModuleType] = field(default_factory=dict)

    def family(self) -> ModuleType:
        fam = self.config["family"]
        return load_module(os.path.join(self.root, "benchmark", "models",
                                        f"{fam}.py"), f"family_{fam}")

    def reader(self, metric: str) -> ModuleType:
        if metric not in self.readers:
            self.readers[metric] = load_module(
                os.path.join(self.root, "benchmark", "metrics",
                             f"{metric}.py"), f"metric_{metric}")
        return self.readers[metric]


def _applies(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    """An end-to-end metric without ``workloads`` holds in every cell; a
    per-layer one in every cell that reports the metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def find_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` with its files loaded; KeyError names what is
    missing."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {name!r} names config {w['config']!r}, "
                       f"which BENCHMARK.json does not list")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "benchmark", "traffic",
                                      f"{w['traffic']}.json"))
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, root=root)
