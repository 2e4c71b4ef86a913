"""Finds a cell's pieces by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic; each lives in a file of its own
(``bench/configs/``, ``bench/traffic/<name>.json``,
``bench/limits/<cell>.json``, ``bench/metrics/<metric>.py``), and the
configuration's ``model.kind`` names its model's module
(``bench/models/<kind>.py``). A metric that reads scope times declares the
scopes it reads (``SCOPES``); the trace reader attributes ops to the union
over a cell's metrics. Adding a cell, a metric or a model kind adds files
and entries; no file here changes."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Callable, Dict, List, Tuple

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
    scopes: Tuple[str, ...] = ()


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Metric]
    kind: ModuleType

    @property
    def scopes(self) -> Tuple[str, ...]:
        """The program scopes the cell's per-layer metrics read, each
        once, in the order the metrics name them."""
        return tuple(dict.fromkeys(s for m in self.per_layer
                                   for s in m.scopes))


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def _module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, unit: str, bench: str = BENCH) -> Metric:
    mod = _module(os.path.join(bench, "metrics", f"{name}.py"),
                  f"bench_metric_{name.replace('.', '_')}")
    return Metric(name, unit, mod.compute,
                  tuple(getattr(mod, "SCOPES", ())))


def kinds(bench: str = BENCH) -> List[str]:
    """The model kinds that have a module under ``bench/models``."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(bench, "models"))
                  if f.endswith(".py"))


@functools.lru_cache(maxsize=None)
def _model_module(path: str, kind: str) -> ModuleType:
    # one module object per file, so that the reference's compiled steps,
    # keyed by the kind's loss, are built once per process
    return _module(path, f"bench_model_{kind}")


def load_model(kind: str, bench: str = BENCH) -> ModuleType:
    """The module of model kind ``kind``, ``bench/models/<kind>.py``. A
    kind without one is an error that names the known kinds."""
    path = os.path.abspath(os.path.join(bench, "models", f"{kind}.py"))
    if not os.path.isfile(path):
        raise SystemExit(f"no model kind {kind!r} (no {path}); known "
                         f"kinds: {kinds(bench)}")
    return _model_module(path, kind)


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
                   for m in manifest["per_layer"] if _reports(m, name)],
        kind=load_model(config["model"]["kind"], bench))
