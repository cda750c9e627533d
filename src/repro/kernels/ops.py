"""Public jit'd wrappers around the Pallas kernels.

Handles padding to block multiples and the one interpret-mode decision:
the kernels compile to Mosaic on a TPU backend and run in the Pallas
interpreter everywhere else (the kernel body runs as jnp ops, for
correctness validation on CPU).  A chip run never interprets.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .chunked_prefill import chunked_prefill_attention, paged_prefill_attention
from .gqa_decode import gqa_decode_attention, paged_gqa_decode_attention

PAD_SEGMENT = -1


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def chunked_prefill(q, k, v, segment_ids, *, block_q: int = 128,
                    block_k: int = 128):
    """Block-diagonal causal flash attention (B,S,H,hd)x(B,S) -> (B,S,H,hd).

    kv may have fewer heads (GQA) — handled natively inside the kernel's
    grid step, so K/V are never materialised head-repeated (HBM traffic
    and memory stay at the kv head count instead of growing by q_per_kv).
    Sequence padded to the block size with segment id -1 (matches nothing
    real).  This is the engine's packed-prefill kernel: distinct segment
    ids per packed job give exact job isolation and the kernel skips KV
    tiles whose segment range cannot intersect the query tile's.
    """
    b, s, h, hd = q.shape
    assert h % k.shape[2] == 0, (h, k.shape[2])
    blk = max(block_q, block_k)
    pad = (-s) % blk
    if pad:
        zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, zpad)
        k = jnp.pad(k, zpad)
        v = jnp.pad(v, zpad)
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)),
                              constant_values=PAD_SEGMENT)
    out = chunked_prefill_attention(q, k, v, segment_ids, block_q=block_q,
                                    block_k=block_k, interpret=_interpret())
    return out[:, :s]


def gqa_decode(q, k_cache, v_cache, valid_len, *, start=None,
               block_k: int = 256):
    """GQA decode attention.  q: (B,H,hd) or (B,1,H,hd); caches
    (B,L,Hkv,hd) NOT head-repeated; valid_len scalar or (B,).

    ``start`` (scalar or (B,), optional) is the first valid cache slot per
    row: the engine's left-padded ragged rows mark their pad prefix invalid
    by passing the prompt's start offset, and the kernel skips KV tiles
    entirely outside [start, valid_len)."""
    squeeze = False
    if q.ndim == 4:
        q = q[:, 0]
        squeeze = True
    b, h, hd = q.shape
    l = k_cache.shape[1]
    valid_len = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,))
    start = jnp.broadcast_to(jnp.asarray(0 if start is None else start,
                                         jnp.int32), (b,))
    pad = (-l) % block_k
    if pad:
        zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, zpad)
        v_cache = jnp.pad(v_cache, zpad)
    out = gqa_decode_attention(q, k_cache, v_cache, valid_len, start,
                               block_k=block_k, interpret=_interpret())
    return out[:, None] if squeeze else out


def paged_gqa_decode(q, k_pool, v_pool, page_table, valid_len):
    """Paged GQA decode attention.  q: (B,H,hd) or (B,1,H,hd); pools
    (num_pages, page_size, Hkv, hd); page_table (B,P) int32 (0 = null
    page); valid_len scalar or (B,).

    No padding needed: the KV block is one page and padded page-table
    columns point at the null page, masked by ``valid_len``."""
    squeeze = False
    if q.ndim == 4:
        q = q[:, 0]
        squeeze = True
    b = q.shape[0]
    valid_len = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,))
    page_table = jnp.asarray(page_table, jnp.int32)
    out = paged_gqa_decode_attention(q, k_pool, v_pool, page_table,
                                     valid_len, interpret=_interpret())
    return out[:, None] if squeeze else out


def paged_prefill(q, k_pool, v_pool, page_table, positions, *,
                  block_q: int = 128):
    """Paged suffix-prefill attention: (B,S,H,hd) queries at global
    ``positions`` (B,S) against K/V gathered through ``page_table``
    (suffix K/V already scattered into the pool).  Queries are padded to
    the block size with position 0 (they attend only slot 0 — finite
    softmax — and their output is sliced off)."""
    b, s, h, hd = q.shape
    pad = (-s) % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, pad)))
    page_table = jnp.asarray(page_table, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    out = paged_prefill_attention(q, k_pool, v_pool, page_table, positions,
                                  block_q=block_q, interpret=_interpret())
    return out[:, :s]
