"""repro-lint configuration: ``repro-lint.toml`` at the repo root.

ruff.toml-style: a small TOML file holding the knobs rules read —
today the R6 VMEM budget and the worst-case symbolic dims its abstract
evaluator assumes for shape-derived block dimensions::

    [vmem]
    budget_bytes = 67108864      # the kernels' 64 MiB scoped limit
    assumed_input_bytes = 4      # dtype width assumed for i/o blocks

    [vmem.dims]
    hd = 128                     # head dim
    ps = 128                     # page size (paged-pool KV block)
    group = 8                    # q heads per kv head (GQA group)

Parsed with :mod:`tomllib`.
"""
from __future__ import annotations

import dataclasses
import tomllib
from pathlib import Path
from typing import Dict, Optional

DEFAULT_VMEM_BUDGET = 64 * 1024 * 1024   # kernels' scoped-VMEM limit
DEFAULT_DIMS = {
    "hd": 128,     # head dim (MXU-aligned worst case)
    "ps": 128,     # page size: pool KV block = one page
    "group": 8,    # GQA group width (q heads per kv head)
    "hkv": 8,      # kv head count (every KV block holds all kv heads)
    "h": 64,       # q head count (prefill q/out blocks hold all heads)
    "block_k": 256,  # decode KV tile
}


@dataclasses.dataclass
class LintConfig:
    vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET
    assumed_input_bytes: int = 4
    dims: Dict[str, int] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_DIMS))


def load_config(path: Optional[Path]) -> LintConfig:
    """Load ``repro-lint.toml``; a missing file yields the defaults."""
    cfg = LintConfig()
    if path is None or not Path(path).exists():
        return cfg
    data = tomllib.loads(Path(path).read_text())
    vmem = data.get("vmem", {})
    if "budget_bytes" in vmem:
        cfg.vmem_budget_bytes = int(vmem["budget_bytes"])
    if "assumed_input_bytes" in vmem:
        cfg.assumed_input_bytes = int(vmem["assumed_input_bytes"])
    for k, v in vmem.get("dims", {}).items():
        cfg.dims[k] = int(v)
    return cfg
