"""Finds everything by the names in ``BENCHMARK.json``: a cell's
configuration and traffic mix, each configuration's modules, each per-layer
metric's reader.  A later PR adds files and entries; nothing here names a
configuration, a cell or a metric."""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str):
    """A module from a file of its own (directories here are named after
    configurations, hyphens and all, so they are no packages)."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


class Configuration:
    """One model configuration: its file of sizes, and beside it
    ``model.py`` (builds it from the program), ``ops.py`` (operations from
    shapes) and ``reference.py`` (the plain reference)."""

    def __init__(self, entry: dict, root: str, overrides: dict | None):
        self.name = entry["name"]
        self.file = os.path.join(root, entry["file"])
        self.dir = os.path.dirname(self.file)
        self.sizes = {**_read_json(self.file), **(overrides or {})}
        self._modules: dict = {}

    def module(self, which: str):
        if which not in self._modules:
            self._modules[which] = load_module(
                os.path.join(self.dir, which + ".py"))
        return self._modules[which]


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))
        self.home = os.path.join(root, self.doc["paths"][0])

    def cell(self, name: str) -> dict:
        for cell in self.doc["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[c['name'] for c in self.doc['workloads']]}")

    def configuration(self, name: str,
                      overrides: dict | None = None) -> Configuration:
        for entry in self.doc["configs"]:
            if entry["name"] == name:
                return Configuration(entry, self.root, overrides)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str, overrides: dict | None = None) -> dict:
        return {**_read_json(os.path.join(self.home, "traffic",
                                          name + ".json")),
                **(overrides or {})}

    def limits(self, cell: str) -> dict:
        """The cell's limits on the numbers that decide ``correct``."""
        return _read_json(os.path.join(self.home, "limits",
                                       cell + ".json"))["limits"]

    def job(self, kind: str):
        return load_module(os.path.join(self.home, "jobs", kind + ".py"))

    def _metrics_of(self, group: str, cell: str, reported: set | None):
        for metric in self.doc[group]:
            cells = metric.get("workloads")
            if cells is not None and cell not in cells:
                continue
            if reported is not None and cells is None \
                    and metric.get("moves") not in reported:
                continue
            yield metric

    def end_to_end(self, cell: str) -> list[dict]:
        return list(self._metrics_of("end_to_end", cell, None))

    def per_layer(self, cell: str) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end(cell)}
        return list(self._metrics_of("per_layer", cell, reported))

    def reader(self, metric: str):
        """``read(run) -> float | None`` of one per-layer metric."""
        return load_module(os.path.join(self.home, "layer_metrics",
                                        metric + ".py")).read

    def peaks(self, device_kind: str) -> dict:
        table = _read_json(os.path.join(self.home, "peaks.json"))
        if device_kind not in table or device_kind.startswith("_"):
            raise KeyError(f"no peaks for device kind {device_kind!r}: "
                           f"add a row with its source to peaks.json")
        return table[device_kind]


def sibling(file: str, name: str):
    """The module ``name``.py beside ``file``."""
    return load_module(os.path.join(os.path.dirname(os.path.abspath(file)),
                                    name + ".py"))
