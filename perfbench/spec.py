"""What one run is asked to do, read from BENCHMARK.json and the files it names.

A cell (an entry of ``workloads``) names a configuration, whose file lives
under ``configs/``, and a traffic mix, read from ``traffic/<traffic>.json``.
Every metric, end-to-end or per-layer, is read by ``metrics/<name>.py``.
Nothing here knows a particular cell, mix or metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's content
    traffic: dict         # the traffic file's content
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def limits(self) -> Dict[str, float]:
        """Each compared number's limit: the configuration's, then the mix's."""
        return {**self.config.get("limits", {}),
                **self.traffic.get("limits", {})}


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str, reported: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves", entry["name"]) in reported


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    e2e = [e for e in bench["end_to_end"]
           if "workloads" not in e or name in e["workloads"]]
    reported = {e["name"] for e in e2e}
    layer = [e for e in bench["per_layer"] if _applies(e, name, reported)]

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[Metric(e["name"], e["unit"]) for e in e2e],
                per_layer=[Metric(e["name"], e["unit"]) for e in layer])


def reader(metric: str) -> Callable:
    """The ``read(record)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
