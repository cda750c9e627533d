"""The random weights of a configuration, made from the seed.

The weights belong to the benchmark, not the program: one jitted call makes
every leaf on the device, in the type the configuration serves in, and the
same tree goes to the engine and, after the window, to the reference.  Their
layout is the architecture's (``weights_fn`` of ``bench/arch/<model_type>.py``).
"""
from __future__ import annotations


def make_weights(arch, dims, seed: int):
    """Random weights from ``seed`` in the program's parameter layout, made
    on the default device by one jitted call of ``arch.weights_fn(dims)``."""
    import jax
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.block_until_ready(jax.jit(arch.weights_fn(dims))(key))
