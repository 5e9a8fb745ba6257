"""Everything the harness runs, found by name — no table of cells in code.

* ``BENCHMARK.json`` (repository root): cells, metrics, bounds;
* ``configs/<config>.json``: one deployment's shapes and settings;
* ``traffic/<traffic>.json``: one traffic mix, whose ``kind`` names
* ``kinds/<kind>.py``: the code that builds and drives that kind of work;
* ``metrics/<metric>.py``: one per-layer metric's reader; a metric split
  by the end-to-end metric it moves, ``<metric>.<split>``, is read by
  ``metrics/<metric>.py`` unless it has a file of its own;
* ``limits/<workload>.json``: the correctness limits of one cell;
* ``peaks.json``: the chip's peaks, keyed by ``device_kind``.

A new cell is new files plus a new entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _load_module(path: str, name: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """Name lookups under one benchmark root (the checkout, or a copy)."""

    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "bench")
        self.benchmark = _read_json(os.path.join(root, "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        for w in self.benchmark["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.benchmark["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")

    def config(self, name: str) -> dict:
        for c in self.benchmark["configs"]:
            if c["name"] == name:
                return _read_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _read_json(os.path.join(self.bench_dir, "traffic",
                                       f"{name}.json"))

    def kind(self, name: str):
        return _load_module(os.path.join(self.bench_dir, "kinds",
                                         f"{name}.py"), f"bench_kind_{name}")

    def metric_reader(self, name: str):
        own = os.path.join(self.bench_dir, "metrics", f"{name}.py")
        if not os.path.exists(own) and "." in name:
            name = name.split(".", 1)[0]
            own = os.path.join(self.bench_dir, "metrics", f"{name}.py")
        return _load_module(own, "bench_metric_" + name.replace(".", "_"))

    def limits(self, workload: str) -> dict:
        return _read_json(os.path.join(self.bench_dir, "limits",
                                       f"{workload}.json"))["limits"]

    def peaks(self, device_kind: str) -> dict:
        table = _read_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"bench/peaks.json ({', '.join(table)})")
        return table[device_kind]

    def _applies(self, metric: dict, workload: str) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    def end_to_end(self, workload: str) -> list[dict]:
        return [m for m in self.benchmark["end_to_end"]
                if self._applies(m, workload)]

    def per_layer(self, workload: str) -> list[dict]:
        return [m for m in self.benchmark["per_layer"]
                if self._applies(m, workload)]
