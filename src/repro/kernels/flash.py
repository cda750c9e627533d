"""Online-softmax (flash attention) state shared by the Pallas kernels.

Every kernel owns a whole KV tile for ALL kv heads per grid step (block
``(block_k, Hkv, hd)``, whose last two dims equal the cache's, as Mosaic
requires) and loops over the heads inside the step.  Per head ``h`` the
running state lives in VMEM scratch: ``acc[h]`` (rows, hd) and the
lane-replicated row max / denominator ``m[h]``, ``l[h]`` (rows, 128).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# the all-heads q/out tiles of the prefill kernels outgrow the default
# 16 MiB scoped-VMEM budget of a v5e (128 MiB physical)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def compiler_params(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def update(h: int, q, k, v, mask, acc_ref, m_ref, l_ref, *,
           scale: float = 1.0):
    """Fold one KV tile into head ``h``'s state: q (rows, hd), k/v (bk,
    hd), mask (rows, bk) or None where every key is visible.  The f32
    scores are multiplied by ``scale`` (1 where q comes pre-scaled); the
    probabilities meet v in v's dtype, accumulating in f32."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[h] = m_new
    acc_ref[h] = acc_ref[h] * alpha[:, :1] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def result(h: int, acc_ref, l_ref):
    """Head ``h``'s normalised output (rows, hd)."""
    return acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-30)
