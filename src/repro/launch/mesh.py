"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before importing
jax to obtain 512 placeholder host devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """The serving and training code is written for GSPMD: it commits
    inputs to NamedShardings and lets the partitioner propagate the rest.
    ``jax.make_mesh`` defaults to Explicit axes, under which every gather
    or contraction over a sharded dim (the vocab-sharded embedding first)
    must state its output sharding, so the axes are made Auto here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host actually has (smoke tests / examples / the
    serving engine's default mesh).

    A ("data", "model") mesh over every local device: serving shards
    decode rows over "data" and attention heads over "model".  Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get 8
    logical CPU devices for mesh tests on a laptop."""
    n = len(jax.devices())
    if n % model_parallel != 0:
        raise ValueError(
            f"model_parallel={model_parallel} does not divide the "
            f"{n} available devices")
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))
