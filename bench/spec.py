"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); the configuration names
its model family under ``"family"``, whose module
``bench/families/<family>.py`` is all the benchmark knows of that kind
of model; each per-layer metric is read by ``bench/metrics/<metric>.py``.
Adding a cell, a mix, a configuration, a family or a metric adds files
and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    family: Any                          # the config's family module
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]     # metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def config_path(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "configs", f"{name}.json")


def traffic_path(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "traffic", f"{name}.json")


def _for_cell(metrics: List[Dict[str, Any]], cell: str):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    config = _load_json(config_path(w["config"], bench_dir))
    if "family" not in config:
        raise KeyError(f"configuration {w['config']!r} names no family; "
                       f"known: {known_families(bench_dir)}")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                family=family(config["family"], bench_dir),
                traffic=_load_json(traffic_path(w["traffic"], bench_dir)),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


def _load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses look their module up while the class is made
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR
                  ) -> Callable[[Any], Optional[float]]:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    return _load_module(path, f"bench_metric_{name.replace('.', '_')}").read


def known_families(bench_dir: str = BENCH_DIR) -> List[str]:
    d = os.path.join(bench_dir, "families")
    return sorted(f[:-3] for f in os.listdir(d) if f.endswith(".py")) \
        if os.path.isdir(d) else []


def family(name: str, bench_dir: str = BENCH_DIR) -> ModuleType:
    """The module ``bench/families/<name>.py``: a model family's sizes
    (``Arch``), its program config, weights, reference and work counts,
    behind the one interface every family gives (the dense family's
    docstring lists it)."""
    path = os.path.join(bench_dir, "families", f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no family {name!r} in {os.path.dirname(path)}; "
                       f"known: {known_families(bench_dir)}")
    return _load_module(path, f"bench_family_{name.replace('.', '_')}")
