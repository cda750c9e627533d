"""A configuration file as the benchmark runs it: its sizes, the program's
``ModelConfig`` built from them, and the random weights made from the seed.

The weights belong to the benchmark, not the program: one jitted call makes
every leaf on the device, in the type the configuration serves in, and the
same tree goes to the engine and, after the window, to the reference.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path


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


def load_dims(path: Path) -> Dims:
    """Sizes of a configuration file (HF ``config.json`` keys plus its
    ``serving`` block)."""
    c = json.loads(Path(path).read_text())
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
        raise ValueError(f"{path}: tied embeddings are not modelled here")
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


def make_weights(dims: Dims, seed: int):
    """Random weights from ``seed`` in the program's parameter layout, made
    on the default device by one jitted call."""
    import jax
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.block_until_ready(jax.jit(weights_fn(dims))(key))


def weights_fn(dims: Dims):
    """key -> weight tree.  Matrices are N(0, 1/fan_in), the embedding
    N(0, 0.02^2) and the norm scales 1 + N(0, 0.05^2), so a path that
    ignored a norm's scale would show in the check."""
    import jax
    import jax.numpy as jnp

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
