"""What a cell is made of, read from data files and nothing else.

``BENCHMARK.json`` at the root of the checkout names the cells, the
configurations and the metrics.  Everything that belongs to one of them
sits in a file of its own under ``benchmarks/``, found by its name:

* ``configs/<config>.json`` (the path is the entry's ``file``),
* ``traffic/<traffic>.json``,
* ``end_to_end/<metric>.json`` and ``layer_metrics/<metric>.json``,
* ``sources/<source>.py`` for the ``source`` a metric file names,
* ``entries/<entry>.py`` for the ``program.entry`` a configuration names
  (how the program is built and driven: ``serve``, ``train``),
* ``loops/<kind>.py`` for the ``kind`` a serving traffic file names (how
  requests are offered: ``open_loop``, ``closed_loop``).

Nothing here knows the name of a cell, a configuration or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    kind: str                   # 'end_to_end' or 'per_layer'
    source: str                 # file stem under sources/
    params: Dict[str, Any]
    entry: Dict[str, Any]       # the BENCHMARK.json entry

    def read(self, run) -> Optional[float]:
        """The value, or None when the source found nothing to read."""
        value = load_module("sources", self.source).read(run,
                                                         **self.params)
        return None if value is None else float(value)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    run_seconds: int
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)

    def traffic_for_config(self) -> Dict[str, Any]:
        """The traffic's parameters with this configuration's own entry
        (its swept rate, say) laid over the common ones."""
        merged = {k: v for k, v in self.traffic.items() if k != "per_config"}
        merged.update(self.traffic.get("per_config", {}).get(
            self.config_name, {}))
        return merged


_MODULES: Dict[tuple, Any] = {}


def load_module(folder: str, name: str):
    """The module ``<folder>/<name>.py`` under ``benchmarks/``: a new
    kind of source, entry or loop is a new file in its folder."""
    if (folder, name) not in _MODULES:
        path = os.path.join(BENCH_DIR, folder, name + ".py")
        if not os.path.isfile(path):
            raise SystemExit(f"no benchmarks/{folder}/{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{folder}_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _MODULES[(folder, name)] = module
    return _MODULES[(folder, name)]


def _in_cell(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _metric(entry: dict, kind: str, folder: str) -> Metric:
    path = os.path.join(BENCH_DIR, folder, entry["name"] + ".json")
    body = _load_json(path)
    if body.get("unit", entry["unit"]) != entry["unit"]:
        raise ValueError(f"{path}: unit {body['unit']!r} is not "
                         f"BENCHMARK.json's {entry['unit']!r}")
    return Metric(entry["name"], entry["unit"], kind, body["source"],
                  body.get("params", {}), entry)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    cell = Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"], traffic_name=entry["traffic"],
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=_load_json(os.path.join(BENCH_DIR, "traffic",
                                        entry["traffic"] + ".json")),
        run_seconds=int(bench["run_seconds"]))
    cell.end_to_end = [_metric(m, "end_to_end", "end_to_end")
                       for m in bench["end_to_end"] if _in_cell(m, workload)]
    cell.per_layer = [_metric(m, "per_layer", "layer_metrics")
                      for m in bench["per_layer"] if _in_cell(m, workload)]
    return cell


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; a device that is not in the table is
    an error, never a default."""
    table = _load_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"benchmarks/harness/peaks.json")
    return table["devices"][device_kind]
