"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Each configuration, architecture, traffic mix, metric and limit is a file of
its own, so a later cell, mix, metric or model is added with new files and
entries only:

    configs  the entry's ``file``             (sizes, as run)
    arch     bench/arch/<model_type>.py       (the config's ``model_type``)
    traffic  bench/traffic/<traffic>.json     (parameters of the generator)
    metrics  bench/metrics/<metric>.py        (its reader)
    limits   bench/limits/<workload>.json     (what ``correct`` compares)

An architecture module provides:

    load_dims(config: dict)       a frozen dataclass of the sizes, with at
                                  least name, dtype, dtype_bytes, page_size,
                                  num_pages, slots, max_seq_len and
                                  attn_layers (layers that call the paged
                                  attention kernels in one forward)
    program_config(dims, backend) the program's ``ModelConfig``
    weights_fn(dims)              key -> weight tree in the program's layout
                                  (``model.make_weights`` jits it)
    logits(params, dims, tokens, rows, *, quant=None)
                                  the plain float32 reference, importing
                                  nothing of the program
    step_flops(ctx), prefill_attn_work(ctx), decode_attn_work(ctx)
                                  the work the traffic requires, from the
                                  metric ``Context`` (its dims, counts, jobs)
"""
from __future__ import annotations

import importlib.util
import json
import sys
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

    def config(self, name: str) -> dict:
        return json.loads(self.config_path(name).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def limits(self, workload: str) -> dict:
        return json.loads((self.bench / "limits" / f"{workload}.json")
                          .read_text())

    def arch_module(self, model_type: str) -> ModuleType:
        return self._module("arch", model_type)

    def metric_module(self, name: str) -> ModuleType:
        return self._module("metrics", name)

    def _module(self, kind: str, name: str) -> ModuleType:
        """bench/<kind>/<name>.py, loaded by path (so another root works)
        and registered under its own name (as a dataclass in it needs)."""
        path = self.bench / kind / f"{name}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_')}", path)
        if mod_spec is None or not path.is_file():
            raise FileNotFoundError(f"{kind} {name!r}: no {path}")
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[mod_spec.name] = mod
        mod_spec.loader.exec_module(mod)
        return mod

    def metrics_for(self, workload: str, traced: bool) -> list:
        """The metric entries a run of ``workload`` reports: the end-to-end
        ones untraced, the per-layer ones traced; an entry with a
        ``workloads`` key only in the cells it lists."""
        key = "per_layer" if traced else "end_to_end"
        return [m for m in self.data[key]
                if workload in m.get("workloads", [workload])]
