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
    the caller drops their output).  The KV block is one page.  S must be
    a multiple of block_q (ops.py pads).  Returns (B, S, H, hd).
    """
    b, s, h, hd = q.shape
    _, ps, hkv, _ = k_pool.shape
    assert h % hkv == 0, (h, hkv)
    assert s % block_q == 0, (s, block_q)
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
