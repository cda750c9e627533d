"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Each configuration, traffic mix, metric and limit is a file of its own, so a
later cell, mix or metric is added with new files and entries only:

    configs  the entry's ``file``             (sizes, as run)
    traffic  bench/traffic/<traffic>.json     (parameters of the generator)
    metrics  bench/metrics/<metric>.py        (its reader, and work function)
    limits   bench/limits/<workload>.json     (what ``correct`` compares)
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Spec:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_path(self, name: str) -> Path:
        for c in self.data["configs"]:
            if c["name"] == name:
                return self.root / c["file"]
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def limits(self, workload: str) -> dict:
        return json.loads((self.bench / "limits" / f"{workload}.json")
                          .read_text())

    def metric_module(self, name: str) -> ModuleType:
        path = self.bench / "metrics" / f"{name}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_')}", path)
        if mod_spec is None or not path.is_file():
            raise FileNotFoundError(f"metric {name!r}: no {path}")
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod

    def metrics_for(self, workload: str, traced: bool) -> list:
        """The metric entries a run of ``workload`` reports: the end-to-end
        ones untraced, the per-layer ones traced; an entry with a
        ``workloads`` key only in the cells it lists."""
        key = "per_layer" if traced else "end_to_end"
        return [m for m in self.data[key]
                if workload in m.get("workloads", [workload])]
