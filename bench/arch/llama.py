"""A llama-style dense decoder (``model_type`` "llama"): its sizes, the
program's ``ModelConfig``, its weight tree, its plain reference and the work
its traffic requires.

The reference is written from the published description (Touvron et al.
2023, as the Yi and DeepSeek-LLM configurations state it: RMSNorm, rotary
positions in the rotate-half form, grouped-query attention, SwiGLU MLP,
untied LM head) in straightforward ``jax.numpy`` (``bench/reference.py``
holds the parts every architecture shares): no kernels, no cache, no
batching, every matrix product at ``Precision.HIGHEST``.  It imports nothing
of the program and reads only the weight tree the benchmark made.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, PAD_TO, mm, quantize, rms_norm, rope


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the benchmark reads from a configuration file."""
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str
    page_size: int
    num_pages: int
    slots: int
    max_seq_len: int

    @property
    def dtype_bytes(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]

    @property
    def attn_layers(self) -> int:
        """Layers that call the paged attention kernels: every one."""
        return self.layers


def load_dims(config: dict) -> Dims:
    """Sizes of a configuration (HF ``config.json`` keys plus its
    ``serving`` block)."""
    c = config
    serving = c["serving"]
    dims = Dims(
        name=c["name"],
        layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // c["num_attention_heads"]),
        d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype=c["torch_dtype"],
        page_size=serving["page_size"],
        num_pages=serving["num_pages"],
        slots=serving["slots"],
        max_seq_len=serving["max_seq_len"],
    )
    if c.get("tie_word_embeddings"):
        raise ValueError(f"{c['name']}: tied embeddings are not modelled here")
    return dims


def program_config(dims: Dims, attention_backend: str = "pallas"):
    """The program's ``ModelConfig`` for these sizes (a plain llama-style
    decoder, untied head, served through the Pallas kernels)."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=dims.name, family="dense", num_layers=dims.layers,
        d_model=dims.d_model, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab_size=dims.vocab, rope_theta=dims.rope_theta,
        tie_embeddings=False, dtype=dims.dtype, norm_eps=dims.norm_eps,
        attention_backend=attention_backend)


def weights_fn(dims: Dims):
    """key -> weight tree.  Matrices are N(0, 1/fan_in), the embedding
    N(0, 0.02^2) and the norm scales 1 + N(0, 0.05^2), so a path that
    ignored a norm's scale would show in the check."""
    d, hd, dt = dims.d_model, dims.head_dim, jnp.dtype(dims.dtype)
    shapes = {
        "wq": (d, dims.heads * hd), "wk": (d, dims.kv_heads * hd),
        "wv": (d, dims.kv_heads * hd), "wo": (dims.heads * hd, d),
        "gate": (d, dims.d_ff), "up": (d, dims.d_ff), "down": (dims.d_ff, d),
    }

    def matrix(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * shape[0] ** -0.5).astype(dt)

    def norm(key):
        return (1.0 + 0.05 * jax.random.normal(key, (d,), jnp.float32)
                ).astype(dt)

    def make(key):
        keys = iter(jax.random.split(key, dims.layers * 9 + 3))
        layers = []
        for _ in range(dims.layers):
            m = {n: matrix(next(keys), s) for n, s in shapes.items()}
            layers.append({
                "norm1": norm(next(keys)),
                "attn": {n: m[n] for n in ("wq", "wk", "wv", "wo")},
                "norm2": norm(next(keys)),
                "mlp": {n: m[n] for n in ("gate", "up", "down")},
            })
        return {
            "embed": (0.02 * jax.random.normal(
                next(keys), (dims.vocab, d), jnp.float32)).astype(dt),
            "final_norm": norm(next(keys)),
            "lm_head": matrix(next(keys), (d, dims.vocab)),
            "layers": layers,
        }

    return make


# -- the plain reference ------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "quant"))
def _layer(x, lp, *, heads, kv_heads, theta, eps, quant):
    t, d = x.shape
    f32 = lambda a: a.astype(jnp.float32)          # noqa: E731
    hd = lp["attn"]["wq"].shape[1] // heads
    positions = jnp.arange(t)
    h = rms_norm(x, f32(lp["norm1"]), eps)
    q = mm(h, f32(lp["attn"]["wq"]), quant).reshape(t, heads, hd)
    k = mm(h, f32(lp["attn"]["wk"]), quant).reshape(t, kv_heads, hd)
    v = mm(h, f32(lp["attn"]["wv"]), quant).reshape(t, kv_heads, hd)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    q, k, v = quantize(q, quant), quantize(k, quant), quantize(v, quant)
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
    x = x + mm(o.reshape(t, heads * hd), f32(lp["attn"]["wo"]), quant)
    h = rms_norm(x, f32(lp["norm2"]), eps)
    a = jax.nn.silu(mm(h, f32(lp["mlp"]["gate"]), quant))
    u = mm(h, f32(lp["mlp"]["up"]), quant)
    return x + mm(a * u, f32(lp["mlp"]["down"]), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, rows, final_norm, lm_head, *, eps, quant):
    h = rms_norm(x[rows], final_norm.astype(jnp.float32), eps)
    return mm(h, lm_head.astype(jnp.float32), quant)


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


# -- the work the traffic requires (bench/work.py counts the tokens) -----------

def matmul_flops_per_token(dims) -> int:
    """Linear-layer FLOPs of one token through every layer (no LM head)."""
    d, hd = dims.d_model, dims.head_dim
    per_layer = (d * dims.heads * hd + 2 * d * dims.kv_heads * hd
                 + dims.heads * hd * d + 3 * d * dims.d_ff)
    return 2 * per_layer * dims.layers


def lm_head_flops(dims) -> int:
    return 2 * dims.d_model * dims.vocab


def attn_flops_per_key(dims) -> int:
    """Q.K and P.V FLOPs of one query row against one key, all layers."""
    return 4 * dims.heads * dims.head_dim * dims.layers


def step_flops(ctx) -> int:
    """Everything the traffic requires of the model: linear layers for every
    distinct prompt token and decode forward, attention over each row's
    causal context, and the LM head where a logit is needed."""
    dims, c = ctx.dims, ctx.counts
    return (matmul_flops_per_token(dims) * (c.prompt_tokens
                                            + c.decode_forwards)
            + attn_flops_per_key(dims) * (c.prompt_ctx + c.decode_ctx)
            + lm_head_flops(dims) * c.logit_rows)


def prefill_attn_work(ctx) -> Tuple[int, int]:
    """(FLOPs, bytes) of prefill attention: every distinct prompt row
    against its causal context; Q and the output once per row, K and V
    once per distinct token, every layer."""
    dims, c = ctx.dims, ctx.counts
    b = dims.dtype_bytes * dims.head_dim * dims.layers
    flops = attn_flops_per_key(dims) * c.prompt_ctx
    byts = c.prompt_tokens * b * (2 * dims.heads + 2 * dims.kv_heads)
    return flops, byts


def decode_attn_work(ctx) -> Tuple[int, int]:
    """(FLOPs, bytes) of decode attention: each decode forward's query row
    against its causal context; K/V reads as counted in ``work.counts``,
    plus the query and output of each forward, every layer."""
    dims, c = ctx.dims, ctx.counts
    b = dims.dtype_bytes * dims.head_dim * dims.layers
    flops = attn_flops_per_key(dims) * c.decode_ctx
    byts = (c.decode_kv_reads * 2 * dims.kv_heads * b
            + c.decode_forwards * 2 * dims.heads * b)
    return flops, byts
