"""R6 firing fixture: structurally consistent pallas_call whose
worst-case VMEM footprint blows the budget.

Blocks are (2048, 4096) f32 = 32 MiB each; with in + out double-buffered
the footprint is 128 MiB against the default 64 MiB budget.  R4 stays
quiet — the call is shape/arity-consistent; only the economics are wrong.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def oversized_call(x):
    return pl.pallas_call(
        _kernel,
        grid=(2, 1),
        in_specs=[pl.BlockSpec((2048, 4096), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((2048, 4096), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((4096, 4096), jnp.float32),
    )(x)
