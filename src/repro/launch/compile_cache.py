"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``), applied at start-up by :func:`configure` — never
at import and never in tests.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set here; otherwise the cache goes to
the fixed ``.jax_cache/`` at the checkout root (gitignored), so
successive runs of one checkout reuse each other's compiled programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point the persistent cache at its directory; returns that path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
