"""Find everything by name. ``BENCHMARK.json`` lists the cells, the
configurations and the metrics; what belongs to one of them sits in a
file of its own that this module finds by that name:

- ``configs/<configuration>.json``: the deployment as it is run;
- ``workloads/<traffic>.json``: the mix's parameters (``traffic.py``)
  and its warm-up;
- ``metrics/<metric>.json``: which reader computes it, with what
  arguments; ``readers/<reader>.py`` holds ``read(records)``;
- ``entries/<entry>.py``: the adapter into the program a configuration
  names; ``<dataset>/``: its generator, statements and reference.

So a later PR adds a cell, a configuration or a metric by adding files
and an entry to ``BENCHMARK.json``, and edits nothing that is here.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


class Catalog:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _json(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    def cell(self, name: str) -> Dict[str, Any]:
        if name not in self.cells:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{sorted(self.cells)}")
        return self.cells[name]

    def config(self, name: str) -> Dict[str, Any]:
        entry = next(c for c in self.doc["configs"] if c["name"] == name)
        return _json(os.path.join(self.root, entry["file"]))

    def traffic(self, name: str) -> Dict[str, Any]:
        return _json(os.path.join(HERE, "workloads", name + ".json"))

    def metrics_for(self, cell: str, traced: bool) -> List[Dict[str, Any]]:
        """The metrics a run of ``cell`` reports: its end-to-end metrics
        untraced, its per-layer metrics traced."""
        table = self.per_layer if traced else self.end_to_end
        return [m for m in table.values()
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        """``read(records)`` of the metric, its arguments bound."""
        spec = _json(os.path.join(HERE, "metrics", metric + ".json"))
        path = os.path.join(HERE, "readers", spec["reader"] + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "benchmark.readers." + spec["reader"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        args = spec.get("args", {})
        return lambda records: mod.read(records, **args)


def entry(name: str):
    return importlib.import_module("benchmark.entries." + name)


def dataset(name: str):
    """The dataset's ``data``, ``statements``, ``oracle`` and ``bytes``."""
    return {part: importlib.import_module(f"benchmark.{name}.{part}")
            for part in ("data", "statements", "oracle", "bytes")}


def peak(device_kind: str) -> Dict[str, Any]:
    peaks = _json(os.path.join(HERE, "peaks.json"))["peaks"]
    if device_kind not in peaks:
        raise RuntimeError(f"unknown device_kind {device_kind!r}: add its "
                           "peaks, with their source, to benchmark/peaks.json")
    return peaks[device_kind]
