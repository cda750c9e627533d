"""Pallas TPU kernel: block-diagonal (chunked) flash-attention prefill.

This is the MinionS local execute-step hot path: all parallel jobs'
chunks are concatenated into one sequence per batch row with
``segment_ids`` marking chunk membership, and ONE fused kernel runs
flash attention with a causal ∧ same-segment mask.

TPU-native adaptation: rather than launching one small attention per
chunk (which starves the MXU), the kernel tiles the whole concatenated
sequence through VMEM and *skips* KV tiles that cannot intersect the
query tile — either because they are entirely in the causal future, or
because their segment range does not overlap the query tile's segment
range.  The skip realises the paper's `2n²d/c` attention-FLOP saving
(App. C.2.3) structurally on the systolic array.

Grid: (batch, num_q_blocks, num_kv_blocks); the kv dimension is
innermost/"arbitrary" so VMEM scratch carries the online-softmax state
across kv iterations.  A grid step owns the (block, H|Hkv, hd) tiles of
every head (the arrays' own minor dims, as Mosaic requires) and loops
over the heads inside.  Per-token ids travel as (B, S, 1) column and
(B, 1, S) row views so their blocks are legal for any batch size.

Paged prefill — grid: (batch, q blocks, page blocks).  A grid step folds
``pages_per_block`` pages of one row (~512 keys) into the softmax state,
DMA'd by hand from the pool in HBM into a double-buffered VMEM block
while the previous block computes, as the paged decode kernel does; only
pages at or before the q tile's last position are copied.  Each kv head
then meets the block in one QKᵀ and one PV whose q side is all G of its
q heads, stacked head-major into a (G * block_q, hd) operand that is laid
out once per q tile, not once per step.  The block's rows interleave the
kv heads of each slot; one kv head's rows are read out once per block
(a strided load: of 32-bit words for a bfloat16 pool, where two rows
share a word).  Operands stay in the stored dtype with f32 accumulation:
the probabilities meet V rounded to V's dtype.  Where hd is narrower than
128 lanes a grid step streams one page through the BlockSpec pipeline.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash
from .gqa_decode import pages_per_block

LANES = 128


def _heads(q_ref, k_ref, v_ref, mask, sm_scale, acc_ref, m_ref, l_ref):
    """Fold this step's KV tile into every q head's state; q head ``hh``
    reads kv head ``hh // group`` (native GQA, no repeated K/V)."""
    h, hkv = q_ref.shape[1], k_ref.shape[1]
    group = h // hkv
    for kh in range(hkv):
        k = k_ref[:, kh, :].astype(jnp.float32)
        v = v_ref[:, kh, :].astype(jnp.float32)
        for hh in range(kh * group, (kh + 1) * group):
            q = q_ref[:, hh, :].astype(jnp.float32) * sm_scale
            flash.update(hh, q, k, v, mask, acc_ref, m_ref, l_ref)


def _finalize(out_ref, acc_ref, l_ref):
    # out_ref is float32 whatever q's dtype: Mosaic cannot store a packed
    # (bf16) head slice into the (block, H, hd) tile
    for hh in range(out_ref.shape[1]):
        out_ref[:, hh, :] = flash.result(hh, acc_ref, l_ref)


def _kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref, out_ref,
            acc_ref, m_ref, l_ref, *, block_q: int, block_k: int,
            sm_scale: float, num_kv_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        flash.init(acc_ref, m_ref, l_ref)

    seg_q = segq_ref[...]                        # (block_q, 1)
    seg_k = segk_ref[...]                        # (1, block_k)

    # --- tile-level skip: causal future or disjoint segment ranges --------
    q_start = qi * block_q
    k_start = kj * block_k
    causal_live = k_start <= q_start + block_q - 1
    seg_live = jnp.logical_and(jnp.max(seg_k) >= jnp.min(seg_q),
                               jnp.min(seg_k) <= jnp.max(seg_q))

    @pl.when(jnp.logical_and(causal_live, seg_live))
    def _compute():
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = (kpos <= qpos) & (seg_q == seg_k)
        _heads(q_ref, k_ref, v_ref, mask, sm_scale, acc_ref, m_ref, l_ref)

    @pl.when(kj == num_kv_blocks - 1)
    def _done():
        _finalize(out_ref, acc_ref, l_ref)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def chunked_prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                              segment_ids: jnp.ndarray, *,
                              block_q: int = 128, block_k: int = 128,
                              interpret: bool) -> jnp.ndarray:
    """q: (B, S, H, hd); k,v: (B, S, Hkv, hd) at NATIVE kv head count;
    segment_ids (B,S).

    GQA is handled inside the step: q head ``hh`` attends with kv head
    ``hh // group`` of the shared KV tile — K/V are never materialised
    head-repeated, so HBM traffic and footprint stay at the Hkv head count.

    S must be a multiple of the block sizes (ops.py pads).
    """
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    assert h % hkv == 0, (h, hkv)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    nq, nk = s // block_q, s // block_k
    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                               sm_scale=1.0 / math.sqrt(hd),
                               num_kv_blocks=nk)
    q_spec = pl.BlockSpec((None, block_q, h, hd),
                          lambda bb, qi, kj: (bb, qi, 0, 0))
    kv_spec = pl.BlockSpec((None, block_k, hkv, hd),
                           lambda bb, qi, kj: (bb, kj, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, nq, nk),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((None, block_q, 1), lambda bb, qi, kj: (bb, qi, 0)),
            pl.BlockSpec((None, 1, block_k), lambda bb, qi, kj: (bb, 0, kj)),
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((h, block_q, hd), jnp.float32),
                        pltpu.VMEM((h, block_q, LANES), jnp.float32),
                        pltpu.VMEM((h, block_q, LANES), jnp.float32)],
        compiler_params=flash.compiler_params("parallel", "parallel",
                                              "arbitrary"),
        interpret=interpret,
        name="chunked_prefill_attention",
    )(q, k, v, segment_ids[:, :, None],
      segment_ids[:, None, :]).astype(q.dtype)


def _paged_kernel(pt_ref, q_ref, k_ref, v_ref, pos_ref, out_ref,
                  acc_ref, m_ref, l_ref, *, block_q: int, page_size: int,
                  sm_scale: float, num_kv_pages: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        flash.init(acc_ref, m_ref, l_ref)

    qpos = pos_ref[...]                           # (block_q, 1) global pos

    # skip pages entirely in the causal future of every query in the tile
    @pl.when(kj * page_size <= jnp.max(qpos))
    def _compute():
        kpos = kj * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 1)
        # position-based causality: queries see the whole cached prefix
        # plus earlier (already-scattered) suffix tokens; slots beyond the
        # prompt hold stale pool data and satisfy kpos > qpos
        _heads(q_ref, k_ref, v_ref, kpos <= qpos, sm_scale,
               acc_ref, m_ref, l_ref)

    @pl.when(kj == num_kv_pages - 1)
    def _done():
        _finalize(out_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def paged_prefill_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, page_table: jnp.ndarray,
                            positions: jnp.ndarray, *, block_q: int = 128,
                            interpret: bool) -> jnp.ndarray:
    """Suffix prefill against a paged cache: each row's queries (the novel
    suffix of its prompt, at global ``positions``) attend to K/V gathered
    through its page table — shared prefix pages are streamed from the
    pool, never re-prefilled.  The suffix's own K/V must already be
    scattered into the pool (slot j holds position j's key), so a single
    position-based causal mask covers prefix and intra-suffix attention.

    q: (B, S, H, hd); pools (num_pages, page_size, Hkv, hd) at native kv
    head count; page_table (B, P) int32 (0 = null page); positions (B, S)
    int32 (left-pad queries with position 0 — they attend only slot 0 and
    the caller drops their output).  A grid step streams
    ``pages_per_block`` pages; pages after a q tile's last position are
    neither copied nor computed.  Where hd is not a multiple of 128 lanes,
    or the pool's rows cannot be read one kv head at a time (a 16-bit
    pool other than bfloat16, or an odd kv head count above 1), a grid
    step streams one page through the BlockSpec pipeline.  S must be a
    multiple of block_q (ops.py pads).  Returns (B, S, H, hd).
    """
    b, s, h, hd = q.shape
    _, ps, hkv, _ = k_pool.shape
    assert h % hkv == 0, (h, hkv)
    assert s % block_q == 0, (s, block_q)
    if _reads_kv_heads(k_pool):
        return _paged_block_call(q, k_pool, v_pool, page_table, positions,
                                 block_q=block_q, interpret=interpret)
    p_max = page_table.shape[1]
    kernel = functools.partial(_paged_kernel, block_q=block_q, page_size=ps,
                               sm_scale=1.0 / math.sqrt(hd),
                               num_kv_pages=p_max)
    q_spec = pl.BlockSpec((None, block_q, h, hd),
                          lambda bb, qi, kj, pt: (bb, qi, 0, 0))
    kv_spec = pl.BlockSpec((None, ps, hkv, hd),
                           lambda bb, qi, kj, pt: (pt[bb, kj], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // block_q, p_max),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((None, block_q, 1),
                         lambda bb, qi, kj, pt: (bb, qi, 0)),
        ],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((h, block_q, hd), jnp.float32),
                        pltpu.VMEM((h, block_q, LANES), jnp.float32),
                        pltpu.VMEM((h, block_q, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=flash.compiler_params("parallel", "parallel",
                                              "arbitrary"),
        interpret=interpret,
        name="paged_prefill_attention",
    )(page_table, q, k_pool, v_pool, positions[:, :, None]).astype(q.dtype)


def _reads_kv_heads(pool) -> bool:
    """Whether the multi-page call can take the pool: Mosaic copies a page
    by hand only in whole 128-lane rows ("Slice shape along dimension 2
    must be aligned to tiling (128)"), and reads one kv head's rows of
    the flat block with a 32-bit strided load, which a bfloat16 pool
    meets as word pairs of rows (one slot's kv heads 2i and 2i+1)."""
    _, _, hkv, hd = pool.shape
    return hd % LANES == 0 and (
        pool.dtype == jnp.float32 or hkv == 1
        or (pool.dtype == jnp.bfloat16 and hkv % 2 == 0))


def _kv_head_rows(buf, slot, kh: int, hkv: int):
    """Kv head ``kh``'s rows of the flat (keys * Hkv, hd) block in
    ``buf[slot]``, as (keys, hd) in the pool's dtype.  A bfloat16 pool
    packs rows 2i and 2i+1 into one 32-bit word, row 2i in the low half:
    read the words of kh's pair with a strided load and keep kh's half,
    which is kh's value as the top bits of an f32 (exact)."""
    keys = buf.shape[1] // hkv
    if hkv == 1:
        return buf[slot]
    if buf.dtype == jnp.float32:
        return buf[slot, pl.ds(kh, keys, stride=hkv), :]
    words = buf.bitcast(jnp.uint32)[slot, pl.ds(kh // 2, keys,
                                                 stride=hkv // 2), :]
    top = words << 16 if kh % 2 == 0 else words & jnp.uint32(0xFFFF0000)
    return pltpu.bitcast(top, jnp.float32).astype(buf.dtype)


def _paged_block_kernel(pt_ref, first_ref, last_ref, q_ref, pos_ref, k_hbm,
                        v_hbm, out_ref, k_buf, v_buf, sems, slot_ref,
                        q_buf, qpos_ref, acc_ref, m_ref, l_ref, *,
                        ppb: int, page_size: int, sm_scale: float):
    """``first_ref``/``last_ref`` (B, num_q_blocks): the least and the
    largest position of each q tile."""
    bb = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_rows = pl.num_programs(0)
    n_tiles = pl.num_programs(1)
    n_blocks = pl.num_programs(2)
    block_q, h, _ = q_ref.shape
    hkv = q_buf.shape[0]
    group = h // hkv
    block = ppb * page_size                   # keys of a grid step
    rows = page_size * hkv                    # flat-view rows of one page

    def copy_block(row, tile, j, slot, wait: bool):
        """Start (or wait for) the copies of block ``j`` of ``row`` for q
        tile ``tile`` into ``slot``: one K and one V copy per page that
        starts at or before the tile's last position."""
        for i in range(ppb):
            col = j * ppb + i

            @pl.when(col * page_size <= last_ref[row, tile])
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

    last = last_ref[bb, qi]
    # block 0 is live in every tile: positions are >= 0
    live = kj * block <= last

    @pl.when(jnp.logical_and(jnp.logical_and(bb == 0, qi == 0), kj == 0))
    def _first():
        slot_ref[0] = 0
        copy_block(0, 0, 0, 0, wait=False)

    @pl.when(kj == 0)
    def _init():
        flash.init(acc_ref, m_ref, l_ref)
        # each kv head's q group head-major, and the positions of its rows
        for hh in range(h):
            kh, g = divmod(hh, group)
            q_buf[kh, pl.ds(g * block_q, block_q), :] = (
                q_ref[:, hh, :].astype(q_buf.dtype))
        for g in range(group):
            qpos_ref[pl.ds(g * block_q, block_q), :] = pos_ref[...]

    # each live step starts the next live block (this tile's, else the
    # next tile's first), then waits for its own
    @pl.when(live)
    def _stream():
        slot = slot_ref[0]
        more = jnp.logical_and(kj + 1 < n_blocks, (kj + 1) * block <= last)

        @pl.when(more)
        def _next_block():
            copy_block(bb, qi, kj + 1, 1 - slot, wait=False)

        row_ends = qi + 1 == n_tiles
        row = jnp.where(row_ends, bb + 1, bb)

        @pl.when(jnp.logical_and(jnp.logical_not(more), row < n_rows))
        def _next_tile():
            copy_block(row, jnp.where(row_ends, 0, qi + 1), 0, 1 - slot,
                       wait=False)

        copy_block(bb, qi, kj, slot, wait=True)
        slot_ref[0] = 1 - slot

    def fold(masked: bool):
        slot = 1 - slot_ref[0]
        mask = v_live = None
        if masked:
            kpos = kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            mask = kpos <= qpos_ref[...]
            # pages after the tile's last position were not copied: their
            # rows hold whatever the buffer held, so zero V there, lest a
            # masked probability meet a NaN
            v_live = (kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)) <= last
        for kh in range(hkv):
            v = _kv_head_rows(v_buf, slot, kh, hkv)
            if v_live is not None:
                v = jnp.where(v_live, v, jnp.zeros_like(v))
            k = _kv_head_rows(k_buf, slot, kh, hkv).astype(q_buf.dtype)
            flash.update(kh, q_buf[kh], k, v, mask, acc_ref, m_ref, l_ref,
                         scale=sm_scale)

    # a block wholly at or before the tile's least position needs no mask
    unmasked = (kj + 1) * block - 1 <= first_ref[bb, qi]

    @pl.when(jnp.logical_and(live, unmasked))
    def _visible():
        fold(masked=False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(unmasked)))
    def _causal():
        fold(masked=True)

    @pl.when(kj == n_blocks - 1)
    def _finalize():
        # out_ref is float32 whatever q's dtype, as in _finalize
        for kh in range(hkv):
            res = flash.result(kh, acc_ref, l_ref)   # (G * block_q, hd)
            for g in range(group):
                out_ref[:, kh * group + g, :] = res[g * block_q:
                                                    (g + 1) * block_q]


def _paged_block_call(q, k_pool, v_pool, page_table, positions, *,
                      block_q: int = 128, interpret: bool):
    """The multi-page paged prefill call: ``pages_per_block`` pages a grid
    step, copied by hand from the pool in HBM (``_reads_kv_heads``)."""
    b, s, h, hd = q.shape
    n_pages, page_size, hkv, _ = k_pool.shape
    group = h // hkv
    width = page_table.shape[1]
    ppb = pages_per_block(k_pool.shape, k_pool.dtype, width, h,
                          block_q=block_q)
    rows = page_size * hkv
    tiles = positions.reshape(b, s // block_q, block_q)
    kernel = functools.partial(_paged_block_kernel, ppb=ppb,
                               page_size=page_size,
                               sm_scale=1.0 / math.sqrt(hd))
    q_spec = pl.BlockSpec((None, block_q, h, hd),
                          lambda bb, qi, kj, pt, first, last: (bb, qi, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, s // block_q, width // ppb),
        in_specs=[
            q_spec,
            pl.BlockSpec((None, block_q, 1),
                         lambda bb, qi, kj, pt, first, last: (bb, qi, 0)),
            pool, pool,
        ],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((2, ppb * rows, hd), k_pool.dtype),
                        pltpu.VMEM((2, ppb * rows, hd), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((hkv, group * block_q, hd),
                                   jnp.promote_types(q.dtype, k_pool.dtype)),
                        pltpu.VMEM((group * block_q, 1), jnp.int32),
                        pltpu.VMEM((hkv, group * block_q, hd), jnp.float32),
                        pltpu.VMEM((hkv, group * block_q, LANES),
                                   jnp.float32),
                        pltpu.VMEM((hkv, group * block_q, LANES),
                                   jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        # the DMA chain runs across tiles and rows: every axis in order
        compiler_params=flash.compiler_params("arbitrary", "arbitrary",
                                              "arbitrary"),
        interpret=interpret,
        name="paged_prefill_attention",
    )(page_table, tiles.min(axis=2), tiles.max(axis=2), q,
      positions[:, :, None], k_pool.reshape(n_pages, rows, hd),
      v_pool.reshape(n_pages, rows, hd)).astype(q.dtype)
