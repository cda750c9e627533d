"""Pallas TPU kernel: single-token GQA decode attention vs. a KV cache.

The MinionS decode hot path: many parallel local jobs each decode one token
per step against their own chunk's KV cache.  Grouped-query heads are
processed together so the MXU sees a (q_per_kv × block_k) matmul per tile
instead of q_per_kv separate vector dots, and the KV cache is streamed
HBM→VMEM once (not once per q head — no materialised ``repeat_kv``).

Grid: (batch, kv_blocks); kv innermost with VMEM online-softmax scratch.
One grid step owns the (block_k, Hkv, hd) tile of every kv head — the
cache's own (Hkv, hd) minor dims, which is what Mosaic accepts — and
loops over the heads inside.  ``valid_len`` (B,) masks unwritten
ring-buffer slots and is delivered via scalar prefetch so fully-dead KV
tiles are skipped.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash

LANES = 128


def _kernel(first_ref, valid_ref, q_ref, k_ref, v_ref, out_ref,
            acc_ref, m_ref, l_ref, *, block_k: int, sm_scale: float,
            num_kv_blocks: int, paged: bool):
    """``first_ref`` is the per-row start slot (dense) or the page table
    (paged, where every row starts at slot 0)."""
    bb = pl.program_id(0)
    kj = pl.program_id(1)
    hkv, group, _ = q_ref.shape

    @pl.when(kj == 0)
    def _init():
        flash.init(acc_ref, m_ref, l_ref)

    start = 0 if paged else first_ref[bb]
    valid = valid_ref[bb]
    # skip tiles entirely before the row's first valid slot (left-padding)
    # or entirely at/after its write frontier
    live = jnp.logical_and(kj * block_k < valid,
                           (kj + 1) * block_k > start)

    @pl.when(live)
    def _compute():
        kpos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_k), 1)
        mask = (kpos >= start) & (kpos < valid)
        for h in range(hkv):
            q = q_ref[h].astype(jnp.float32) * sm_scale        # (G, hd)
            flash.update(h, q, k_ref[:, h, :].astype(jnp.float32),
                         v_ref[:, h, :].astype(jnp.float32), mask,
                         acc_ref, m_ref, l_ref)

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        for h in range(hkv):
            out_ref[h] = flash.result(h, acc_ref, l_ref).astype(out_ref.dtype)


def _decode_call(first, valid_len, q, k, v, *, block_k: int, n_blocks: int,
                 kv_index, paged: bool, interpret: bool):
    b, h, hd = q.shape
    hkv = k.shape[2]
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    # (B, H, hd) -> (B, Hkv, G, hd) so each kv head's q group is one slab
    qg = q.reshape(b, hkv, group, hd)
    kernel = functools.partial(_kernel, block_k=block_k,
                               sm_scale=1.0 / math.sqrt(hd),
                               num_kv_blocks=n_blocks, paged=paged)
    row = pl.BlockSpec((None, hkv, group, hd),
                       lambda bb, kj, first, valid: (bb, 0, 0, 0))
    kv = pl.BlockSpec((None, block_k, hkv, hd), kv_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_blocks),
        in_specs=[row, kv, kv],
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((hkv, group, hd), jnp.float32),
                        pltpu.VMEM((hkv, group, LANES), jnp.float32),
                        pltpu.VMEM((hkv, group, LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, hd), q.dtype),
        compiler_params=flash.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        # the trace names the kernel by its jitted wrapper's name
        name="paged_gqa_decode_attention" if paged else "gqa_decode_attention",
    )(first, valid_len, qg, k, v)
    return out.reshape(b, h, hd)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def gqa_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, valid_len: jnp.ndarray,
                         start: jnp.ndarray, *, block_k: int,
                         interpret: bool) -> jnp.ndarray:
    """q: (B, H, hd); caches: (B, L, Hkv, hd); valid_len, start: (B,) int32.

    Slots in [start, valid_len) attend; everything else (left-padding from
    the engine's ragged batches, unwritten tail) is masked and fully-dead
    KV tiles are skipped.  Returns (B, H, hd).  L must be a multiple of
    block_k (ops.py pads).
    """
    l = k_cache.shape[1]
    assert l % block_k == 0, (l, block_k)
    return _decode_call(start, valid_len, q, k_cache, v_cache,
                        block_k=block_k, n_blocks=l // block_k,
                        kv_index=lambda bb, kj, start, valid: (bb, kj, 0, 0),
                        paged=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gqa_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray, page_table: jnp.ndarray,
                               valid_len: jnp.ndarray, *,
                               interpret: bool) -> jnp.ndarray:
    """Paged variant: K/V live in a shared page pool and each row gathers
    its cache through a page table delivered via scalar prefetch — the K/V
    index maps translate the grid's page coordinate to a physical page, so
    rows sharing prefix pages stream the same HBM tiles.

    q: (B, H, hd); pools: (num_pages, page_size, Hkv, hd); page_table:
    (B, P) int32 physical page per logical page (0 = null page); valid_len:
    (B,) int32 — slot j of row b (page j // page_size) holds the KV of
    global position j, positions >= valid_len are masked.  The KV block is
    one page (block_k == page_size).  Returns (B, H, hd).
    """
    return _decode_call(page_table, valid_len, q, k_pool, v_pool,
                        block_k=k_pool.shape[1],
                        n_blocks=page_table.shape[1],
                        kv_index=lambda bb, kj, pt, valid:
                        (pt[bb, kj], 0, 0, 0),
                        paged=True, interpret=interpret)
