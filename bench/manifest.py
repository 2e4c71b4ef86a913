"""Finds a cell's pieces by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic; each lives in a file of its own
(``bench/configs/``, ``bench/traffic/<name>.json``,
``bench/limits/<cell>.json``, ``bench/metrics/<metric>.py``). Adding a cell
or a metric adds files and entries; no file here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    compute: Callable[..., Any]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Metric]


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_metric(name: str, unit: str, bench: str = BENCH) -> Metric:
    path = os.path.join(bench, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return Metric(name, unit, mod.compute)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = os.path.join(root, "bench")
    manifest = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(bench, "traffic",
                                      f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(bench, "limits", f"{name}.json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits={k: float(v) for k, v in limits["limits"].items()
                if v is not None},
        end_to_end=[m for m in manifest["end_to_end"] if _reports(m, name)],
        per_layer=[load_metric(m["name"], m["unit"], bench)
                   for m in manifest["per_layer"] if _reports(m, name)])
