"""Finds a cell's pieces by the names in ``BENCHMARK.json``: the
configuration file it names, ``bench/traffic/<traffic>.json``,
``bench/drivers/<driver>.py`` (the configuration's ``driver``) and
``bench/metrics/<metric>.py``.  A new deployment, traffic mix or metric
is a new file and a new entry; no file here changes for it."""
from __future__ import annotations

import importlib.util
import json
import os


def _module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` at ``root``, with the benchmark's own files
    under ``root/bench``."""

    def __init__(self, root: str):
        self.root, self.bench = root, os.path.join(root, "bench")
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: "
                           f"{', '.join(sorted(cells))}")
        return cells[name]

    def config(self, name: str) -> dict:
        entry = {c["name"]: c for c in self.spec["configs"]}[name]
        return _json(os.path.join(self.root, entry["file"]))

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(self.bench, "traffic", f"{name}.json"))

    def driver(self, kind: str):
        return _module(os.path.join(self.bench, "drivers", f"{kind}.py"),
                       f"bench_driver_{kind}")

    def reader(self, metric: str):
        """The metric's ``read(ctx)``: its value, or None where the run
        holds nothing for it to read."""
        path = os.path.join(self.bench, "metrics", f"{metric}.py")
        return _module(path, "bench_metric_" + metric.replace(".", "_")).read

    def metrics(self, cell: str, per_layer: bool) -> list:
        """The cell's end-to-end metrics, or its per-layer ones."""
        key = "per_layer" if per_layer else "end_to_end"
        return [m for m in self.spec[key]
                if cell in m.get("workloads", [cell])]
