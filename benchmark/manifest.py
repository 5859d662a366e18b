"""``BENCHMARK.json`` and the data files it names.

A cell names a configuration and a traffic mix; a per-layer metric names
itself.  Each is a file found by that name under ``benchmark/``:

    configs/<config>.json     sizes, stated precision, guarantees, limits
    traffic/<traffic>.json    ``kind`` + parameters of the generator
    metrics/<metric>.json     ``reader`` + its parameters (event patterns,
                              work function, counters)
    metrics/<metric>.py       optional reader of its own: ``read(ctx, spec)``

so adding a cell, a configuration or a metric adds files and one entry in
``BENCHMARK.json`` and edits nothing that is there.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise SystemExit(
                f"benchmark: no workload {name!r} in BENCHMARK.json "
                f"(has: {', '.join(sorted(self.cells))})")
        return self.cells[name]

    def config(self, cell: dict) -> dict:
        entry = self.configs[cell["config"]]
        doc = load_json(os.path.join(self.root, entry["file"]))
        doc.setdefault("name", entry["name"])
        return doc

    def traffic(self, cell: dict) -> dict:
        doc = load_json(os.path.join(
            self.root, "benchmark", "traffic", cell["traffic"] + ".json"))
        doc.setdefault("name", cell["traffic"])
        return doc

    def metrics_of(self, cell_name: str, group: str) -> list:
        """Entries of ``end_to_end`` or ``per_layer`` that this cell reports:
        those that list it under ``workloads``, or list nothing and (per
        layer) move an end-to-end metric the cell reports."""
        e2e = [m for m in self.doc["end_to_end"]
               if cell_name in m.get("workloads", [cell_name])]
        if group == "end_to_end":
            return e2e
        mine = {m["name"] for m in e2e}
        return [m for m in self.doc["per_layer"]
                if (cell_name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def metric_spec(self, name: str) -> dict:
        return load_json(os.path.join(
            self.root, "benchmark", "metrics", name + ".json"))

    def metric_reader(self, name: str, spec: dict):
        """``read(ctx, spec)`` from ``metrics/<name>.py`` if it is there,
        else from ``readers/<spec['reader']>.py``."""
        own = os.path.join(self.root, "benchmark", "metrics", name + ".py")
        if os.path.exists(own):
            mod_spec = importlib.util.spec_from_file_location(
                "benchmark_metric_" + name.replace(".", "_").replace("-", "_"),
                own)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            return mod.read
        return importlib.import_module(
            "benchmark.readers." + spec["reader"]).read


def load_kind(kind: str):
    """The traffic generator ``kinds/<kind>.py``: a module with ``Cell``."""
    return importlib.import_module("benchmark.kinds." + kind)
