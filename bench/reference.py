"""The plain reference: a llama-style decoder forward in float32.

Written from the published description (Touvron et al. 2023, as the Yi and
DeepSeek-LLM configurations state it: RMSNorm, rotary positions in the
rotate-half form, grouped-query attention, SwiGLU MLP, untied LM head) in
straightforward ``jax.numpy``: no kernels, no cache, no batching, every
matrix product at ``Precision.HIGHEST``.  It imports nothing of the program
and reads only the weight tree the benchmark made.

One sequence at a time, layer by layer, so that it fits beside nothing else
on the chip: each layer's weights are widened to float32 inside the jitted
layer step, and the sequence is padded at its end to a multiple of
``PAD_TO`` (causal attention leaves the real positions untouched) so that a
handful of programs serve every length.

``quant`` makes the control: the same forward with every matrix-product
operand (weights, activations, queries, keys and values) rounded to the
next precision below the served one, the step a faster path would be
tempted to take: ``"fp8"`` (float8_e4m3fn under a per-tensor scale) for a
bfloat16 configuration, ``"bf16"`` for a float32 one.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_TO = 1024
FP8_MAX = 448.0        # largest finite float8_e4m3fn


def _q(x, quant: Optional[str]):
    """Round ``x`` to the control's precision (identity for the reference)."""
    if quant is None:
        return x
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """Rotate-half rotary embedding of x (T, heads, hd) at ``positions``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "quant"))
def _layer(x, lp, *, heads, kv_heads, theta, eps, quant):
    t, d = x.shape
    f32 = lambda a: a.astype(jnp.float32)          # noqa: E731
    hd = lp["attn"]["wq"].shape[1] // heads
    positions = jnp.arange(t)
    h = _rms_norm(x, f32(lp["norm1"]), eps)
    q = _mm(h, f32(lp["attn"]["wq"]), quant).reshape(t, heads, hd)
    k = _mm(h, f32(lp["attn"]["wk"]), quant).reshape(t, kv_heads, hd)
    v = _mm(h, f32(lp["attn"]["wv"]), quant).reshape(t, kv_heads, hd)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    q, k, v = _q(q, quant), _q(k, quant), _q(v, quant)
    g = heads // kv_heads
    causal = positions[None, :] <= positions[:, None]

    def one_kv_head(args):
        """Causal softmax attention of the g query heads that read one kv
        head (one at a time, so the score matrix stays g x T x T)."""
        qh, kh, vh = args                          # (T, g, hd), (T, hd) x2
        s = jnp.einsum("qgd,sd->gqs", qh, kh, precision=HIGHEST)
        s = jnp.where(causal[None], s / math.sqrt(hd), -jnp.inf)
        return jnp.einsum("gqs,sd->qgd", jax.nn.softmax(s, axis=-1), vh,
                          precision=HIGHEST)

    o = jax.lax.map(one_kv_head, (q.reshape(t, kv_heads, g, hd)
                                  .transpose(1, 0, 2, 3),
                                  k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3)                    # (T, kv_heads, g, hd)
    x = x + _mm(o.reshape(t, heads * hd), f32(lp["attn"]["wo"]), quant)
    h = _rms_norm(x, f32(lp["norm2"]), eps)
    a = jax.nn.silu(_mm(h, f32(lp["mlp"]["gate"]), quant))
    u = _mm(h, f32(lp["mlp"]["up"]), quant)
    return x + _mm(a * u, f32(lp["mlp"]["down"]), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, rows, final_norm, lm_head, *, eps, quant):
    h = _rms_norm(x[rows], final_norm.astype(jnp.float32), eps)
    return _mm(h, lm_head.astype(jnp.float32), quant)


def logits(params, dims, tokens: Sequence[int], rows: Sequence[int], *,
           quant: Optional[str] = None) -> np.ndarray:
    """float32 logits (len(rows), vocab) of the next token after each
    position in ``rows``, for the sequence ``tokens``."""
    t = len(tokens)
    t_pad = -(-t // PAD_TO) * PAD_TO
    ids = np.zeros(t_pad, np.int32)
    ids[:t] = tokens
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    kw = dict(heads=dims.heads, kv_heads=dims.kv_heads,
              theta=float(dims.rope_theta), eps=float(dims.norm_eps),
              quant=quant)
    for lp in params["layers"]:
        x = _layer(x, lp, **kw)
    out = _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                params["final_norm"], params["lm_head"],
                eps=float(dims.norm_eps), quant=quant)
    return np.asarray(out)


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
