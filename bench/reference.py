"""What every architecture's plain reference shares (``bench/arch/<model_type>.py``
holds the forward itself, its ``logits``): float32 building blocks in
straightforward ``jax.numpy``, every matrix product at ``Precision.HIGHEST``,
and the reading of served tokens against the reference's logits.  Nothing
here imports the program.

An architecture's ``logits`` runs one sequence at a time, layer by layer, so
that it fits beside nothing else on the chip: each layer's weights are
widened to float32 inside the jitted layer step, and the sequence is padded
at its end to a multiple of ``PAD_TO`` (causal attention leaves the real
positions untouched) so that a handful of programs serve every length.

``quant`` makes the control: the same forward with every matrix-product
operand (weights, activations, queries, keys and values) rounded to the
next precision below the served one, the step a faster path would be
tempted to take: ``"fp8"`` (float8_e4m3fn under a per-tensor scale) for a
bfloat16 configuration, ``"bf16"`` for a float32 one.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_TO = 1024
FP8_MAX = 448.0        # largest finite float8_e4m3fn


def quantize(x, quant: Optional[str]):
    """Round ``x`` to the control's precision (identity for the reference)."""
    if quant is None:
        return x
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, b, quant):
    return jnp.matmul(quantize(a, quant), quantize(b, quant), precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """Rotate-half rotary embedding of x (T, heads, hd) at ``positions``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def served_rows(prompt_len: int, n_served: int) -> list:
    """Positions whose next-token logits chose the served tokens: the last
    prompt token, then each served token but the last."""
    return list(range(prompt_len - 1, prompt_len - 1 + n_served))


def served_gaps(ref_logits: np.ndarray, served: Sequence[int]) -> np.ndarray:
    """Per served token, how far its reference logit lies below the
    reference's best: 0 where the reference agrees."""
    ref = np.asarray(ref_logits, np.float64)
    picked = ref[np.arange(len(served)), np.asarray(served)]
    return ref.max(-1) - picked
