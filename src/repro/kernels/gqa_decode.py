"""Pallas TPU kernels: single-token GQA decode attention vs. a KV cache.

The MinionS decode hot path: many parallel local jobs each decode one token
per step against their own chunk's KV cache.  Grouped-query heads are
processed together so the MXU sees a (q_per_kv × block_k) matmul per tile
instead of q_per_kv separate vector dots, and the KV cache is streamed
HBM→VMEM once (not once per q head — no materialised ``repeat_kv``).

Dense cache — grid: (batch, kv_blocks); kv innermost with VMEM
online-softmax scratch.  One grid step owns the (block_k, Hkv, hd) tile of
every kv head — the cache's own (Hkv, hd) minor dims, which is what Mosaic
accepts — and loops over the heads inside.  ``valid_len`` (B,) masks
unwritten ring-buffer slots and is delivered via scalar prefetch so
fully-dead KV tiles are skipped.

Page pool — grid: (batch, page blocks).  A grid step folds
``pages_per_block`` pages of one row into the softmax state, DMA'd by hand
from the pool in HBM into a double-buffered VMEM block while the previous
block computes; only live pages are copied.  The pool is read through its
(pages, page_size * Hkv, hd) view, whose rows interleave the kv heads of
each slot: for the pool's tiled layout that view is the same bytes, so a
page lands in VMEM as one dense (page_size * Hkv, hd) tile.  All q heads
then meet the whole block in one f32 QKᵀ, with the rows of other kv heads
masked: Hkv times the MXU work of a per-head product, but no per-head
slice of an (Hkv, hd)-minor tile, which costs more than the matmul.  Every
grid step also pays a fixed cost (bookkeeping, DMA issue and wait), so a
block of ~512 keys rather than one page keeps it from setting the time.
Mosaic copies a page by hand only in whole 128-lane rows; where hd is
narrower a step streams one page through the dense kernel's pipeline.
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
# keys a paged grid step folds in at most
BLOCK_KEYS = 512


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


def pages_per_block(pool_shape, dtype, width: int, num_heads: int,
                    block_q: int | None = None) -> int:
    """Pages one grid step of a paged kernel streams, from the pool's
    (num_pages, page_size, Hkv, hd) shape and dtype, the page-table width
    and the q head count: the largest power of two that covers at most
    ``BLOCK_KEYS`` keys, divides ``width`` and keeps the block's VMEM —
    K and V pages in two slots, their f32 copies, and the f32 scores and
    probabilities — within half the kernel's VMEM budget.  In decode
    every q head meets every row of the block; in prefill (``block_q``
    queries a q head) each q head meets its own kv head's keys."""
    _, page_size, hkv, hd = pool_shape
    itemsize = jnp.dtype(dtype).itemsize

    def vmem(ppb):
        rows = ppb * page_size * hkv          # flat-view rows of a block
        scores = (num_heads * rows if block_q is None
                  else num_heads * block_q * ppb * page_size)
        return (2 * 2 * rows * hd * itemsize + 2 * rows * hd * 4
                + 2 * scores * 4)

    ppb = 1
    while (2 * ppb * page_size <= BLOCK_KEYS and width % (2 * ppb) == 0
           and vmem(2 * ppb) <= flash.VMEM_LIMIT_BYTES // 2):
        ppb *= 2
    return ppb


def _paged_kernel(pt_ref, valid_ref, q_ref, k_hbm, v_hbm, out_ref,
                  k_buf, v_buf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
                  ppb: int, page_size: int, hkv: int, sm_scale: float):
    bb = pl.program_id(0)
    kj = pl.program_id(1)
    n_rows = pl.num_programs(0)
    n_blocks = pl.num_programs(1)
    h = q_ref.shape[0]
    group = h // hkv
    block = ppb * page_size                   # keys of a grid step
    rows = page_size * hkv                    # flat-view rows of one page

    def copy_block(row, j, slot, wait: bool):
        """Start (or wait for) the copies of block ``j`` of ``row`` into
        ``slot``: one K and one V copy per page that holds a live slot."""
        for i in range(ppb):
            col = j * ppb + i

            @pl.when(col * page_size < valid_ref[row])
            def _page():
                page = pt_ref[row, col]
                dst = pl.ds(i * rows, rows)
                for src, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]),
                                      (v_hbm, v_buf, sems.at[1, slot])):
                    dma = pltpu.make_async_copy(src.at[page],
                                                buf.at[slot, dst], sem)
                    if wait:
                        dma.wait()
                    else:
                        dma.start()

    valid = valid_ref[bb]
    live = kj * block < valid

    @pl.when(jnp.logical_and(bb == 0, kj == 0))
    def _first():
        slot_ref[0] = 0
        copy_block(0, 0, 0, wait=False)

    @pl.when(kj == 0)
    def _init():
        flash.init(acc_ref, m_ref, l_ref)

    # a row's first step and each live one own a block in flight; each
    # starts the next block (this row's, else the next row's first), then
    # waits for its own
    @pl.when(jnp.logical_or(kj == 0, live))
    def _stream():
        slot = slot_ref[0]
        more = jnp.logical_and(kj + 1 < n_blocks, (kj + 1) * block < valid)

        @pl.when(more)
        def _next_block():
            copy_block(bb, kj + 1, 1 - slot, wait=False)

        @pl.when(jnp.logical_and(jnp.logical_not(more), bb + 1 < n_rows))
        def _next_row():
            copy_block(bb + 1, 0, 1 - slot, wait=False)

        copy_block(bb, kj, slot, wait=True)
        slot_ref[0] = 1 - slot

    @pl.when(live)
    def _compute():
        slot = 1 - slot_ref[0]
        n_live = (valid - kj * block) * hkv   # live rows of the block
        shape = (h, ppb * rows)
        r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = (c % hkv == r // group) & (c < n_live)
        q = q_ref[...].astype(jnp.float32) * sm_scale          # (H, hd)
        k = k_buf[slot].astype(jnp.float32)
        # rows past the frontier hold whatever the buffer held before:
        # zero them so a masked probability meets no NaN
        v = v_buf[slot].astype(jnp.float32)
        v_live = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) < n_live
        flash.update(0, q, k, jnp.where(v_live, v, 0.0), mask,
                     acc_ref, m_ref, l_ref)

    @pl.when(kj == n_blocks - 1)
    def _finalize():
        out_ref[...] = flash.result(0, acc_ref, l_ref).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gqa_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray, page_table: jnp.ndarray,
                               valid_len: jnp.ndarray, *,
                               interpret: bool) -> jnp.ndarray:
    """Paged variant: K/V live in a shared page pool and each row gathers
    its cache through a page table delivered via scalar prefetch, so rows
    sharing prefix pages read the same HBM pages.

    q: (B, H, hd); pools: (num_pages, page_size, Hkv, hd); page_table:
    (B, P) int32 physical page per logical page (0 = null page); valid_len:
    (B,) int32 — slot j of row b (page j // page_size) holds the KV of
    global position j, positions >= valid_len are masked.  A grid step
    streams ``pages_per_block`` pages; pages and blocks past ``valid_len``
    are neither copied nor computed.  Where hd is not a multiple of 128
    lanes a grid step streams one page through the dense kernel's
    pipeline.  Returns (B, H, hd).
    """
    hd = q.shape[2]
    assert q.shape[1] % k_pool.shape[2] == 0, (q.shape, k_pool.shape)
    if hd % LANES:
        # Mosaic slices a pool page for a hand DMA only in whole 128-lane
        # rows ("Slice shape along dimension 2 must be aligned to tiling
        # (128)"): stream one page per grid step through the BlockSpec
        # pipeline
        return _decode_call(page_table, valid_len, q, k_pool, v_pool,
                            block_k=k_pool.shape[1],
                            n_blocks=page_table.shape[1],
                            kv_index=lambda bb, kj, pt, valid:
                            (pt[bb, kj], 0, 0, 0),
                            paged=True, interpret=interpret)
    return _paged_block_call(q, k_pool, v_pool, page_table, valid_len,
                             interpret=interpret)


def _paged_block_call(q, k_pool, v_pool, page_table, valid_len, *,
                      interpret: bool):
    """The multi-page paged call: ``pages_per_block`` pages a grid step,
    copied by hand from the pool in HBM (hd a multiple of 128 lanes)."""
    b, h, hd = q.shape
    n_pages, page_size, hkv, _ = k_pool.shape
    width = page_table.shape[1]
    ppb = pages_per_block(k_pool.shape, k_pool.dtype, width, h)
    rows = page_size * hkv
    kernel = functools.partial(_paged_kernel, ppb=ppb, page_size=page_size,
                               hkv=hkv, sm_scale=1.0 / math.sqrt(hd))
    row = pl.BlockSpec((None, h, hd), lambda bb, kj, pt, valid: (bb, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, width // ppb),
        in_specs=[row, pool, pool],
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((2, ppb * rows, hd), k_pool.dtype),
                        pltpu.VMEM((2, ppb * rows, hd), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((1, h, hd), jnp.float32),
                        pltpu.VMEM((1, h, LANES), jnp.float32),
                        pltpu.VMEM((1, h, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        # the DMA chain runs across rows: both axes in order
        compiler_params=flash.compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
        name="paged_gqa_decode_attention",
    )(page_table, valid_len, q, k_pool.reshape(n_pages, rows, hd),
      v_pool.reshape(n_pages, rows, hd))
