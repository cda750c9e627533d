"""Batched inference engine: the MinionS local execute substrate.

Left-pads ragged prompt batches (segment ids mask the padding), runs a
jitted prefill, then ONE jitted ``lax.while_loop`` decode that fuses
sampling, the per-row done mask (EOS + stop-sequence detection) and
early exit entirely on device — results cross the host boundary once per
``generate_batch`` call (O(1) transfers, not O(tokens)).  Shapes are
bucketed (next power of two) so repeated protocol rounds reuse compiled
executables.

Job packing (the MinionS "execute locally in parallel" step): when the
model supports it, several short worker jobs are packed into one prefill
row with distinct segment ids — the block-diagonal attention mask keeps
jobs isolated while padding slots stop burning FLOPs — and the primed KV
cache is then scattered into one decode row per job.  RoPE positions are
assigned from each job's eventual decode-row layout, so packed and
unpacked prefill are numerically equivalent.

Continuous batching (:meth:`InferenceEngine.serve`): a persistent pool of
``slots`` decode rows runs one jitted while_loop that exits as soon as ANY
row finishes (EOS / stop / per-row token budget) instead of waiting for
all of them.  The host then harvests the finished rows, prefills queued
jobs with the RoPE positions of their destination layout (prompt ending at
the pool's current decode position) and scatters the primed KV straight
into the freed rows — the same gather machinery packed prefill uses — then
resumes the loop.  Each row carries its own traced token budget, stop
state, temperature and RNG lane, so admissions never recompile and never
perturb what a live neighbour row samples.  Host transfers stay O(number
of admissions), not O(tokens); one long job no longer convoys its
siblings.

Sharded serving (``mesh=``): the same hot path runs SPMD over a JAX
("data", "model") mesh — pass a ``jax.sharding.Mesh`` (or ``"auto"`` for
:func:`repro.launch.mesh.make_host_mesh` over every local device).  The
layout, from the rules in :mod:`repro.parallel.sharding`:

  params      param_specs(..., decode=True): q/kv head dims over "model"
              when they divide, flat weight sharding otherwise; placed
              once at construction.
  batch rows  batch_specs: prefill token/segment/position rows over the
              data axes when the row count divides, else replicated.
  cache       cache_specs: batch(row) axis over "data", KV heads over
              "model" when divisible (flash-decode sequence sharding as
              the documented fallback — it reorders float reductions, so
              bit-identity with single-device is only guaranteed for
              row-aligned pools).
  lanes       row_specs: per-row sampler state (tok / done / emit cursor /
              RNG lane / budget / temperature) shards with the rows it
              serves, so admission scatters touch only the owning shard.

Everything else is unchanged: prefill/decode loops are jitted once and
GSPMD partitions them from the committed input shardings (computation
follows data), and slot admission stays O(admissions) — primed KV is
scattered into the live sharded cache on device, never gathered to host.

Paged KV cache (``paged=True``): instead of dense per-row ``(B, L, Hkv,
hd)`` buffers, K/V live in a fixed-size page pool ``(num_pages,
page_size, Hkv, hd)`` per layer, shared by every row and every call.
Each row addresses its tokens through an int32 page table (token ``i``
lives in ``pool[page_table[i // ps], i % ps]``); page 0 is a reserved
null page that dead rows and padding write into, never read unmasked.
A radix/trie prefix index (:class:`repro.serving.paging.RadixIndex`)
keys full pages by their token-id chunks, so admission matches the
longest cached prefix, shares those pages by refcount, copy-on-writes
the partially-filled divergence page, and prefills ONLY the novel
suffix — the MinionS win, since every worker job in a round shares the
same instruction prefix.  Unreferenced prefixes are LRU-evicted when
the pool runs dry.  RoPE positions are canonical (token ``i`` at
position ``i``), which is what makes one prefix's pages bit-reusable by
every job that shares it; the paged path is token-identical to the
dense oracle.  Dense buffers remain the default (``paged=False``).

Equivalence-test matrix (tests/test_equivalence.py): every execution path
the engine has grown — {reference, pallas} backend x {generate_batch,
serve} x {packed, unpacked} prefill x {single-device, 8-device host mesh}
x {dense, paged} cache — must produce token-identical greedy output for
identical seeds; the differential harness pins all cells to the
single-device reference unpacked oracle.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.parallel.sharding import (batch_specs, cache_specs, param_specs,
                                     row_specs, to_shardings)

from .paging import PagePool, RadixIndex, _lcp, cow_copy
from .sampler import job_keys, sample_rows, sample_traced, split_rows
from .tokenizer import ByteTokenizer


def _sanitize() -> bool:
    """Runtime sanitizer switch: ``REPRO_SANITIZE=1`` turns on the page-
    pool refcount audit on every admission wave and the host-transfer
    budget asserts in :meth:`InferenceEngine.serve`.  Read per call (not
    cached) so tests can flip it with monkeypatch.setenv; off by
    default, on in CI smoke."""
    return bool(os.environ.get("REPRO_SANITIZE"))


@dataclasses.dataclass
class EngineUsage:
    """Cumulative usage accounting for one engine.

    Counters accumulate over the engine's LIFETIME (like a billing meter):
    callers wanting per-call figures snapshot before/after and diff, or
    call :meth:`reset` between phases.  They are deliberately NOT cleared
    between ``serve``/``generate_batch`` calls — a MinionS protocol round
    spans many engine calls and meters the total."""

    prefill_tokens: int = 0
    decode_tokens: int = 0
    calls: int = 0
    # padded prefill slots actually computed (real + padding): the gap to
    # prefill_tokens is the bucket-padding waste packing exists to shrink
    prefill_slots: int = 0
    # host<->device result transfers; the fused decode loop keeps this O(1)
    # per generate_batch call regardless of max_new_tokens
    host_transfers: int = 0
    # continuous-batching counters: jobs admitted into pool rows, jobs
    # harvested from them, and cache epochs started (serve only)
    admitted_jobs: int = 0
    finished_jobs: int = 0
    serve_epochs: int = 0
    # serve decode steps whose dense slot mask had a hole, so the Pallas
    # backend's decode ran the jnp reference attention instead of the
    # kernel (always 0 on the reference backend and on paged engines)
    decode_fallback_steps: int = 0
    # paged-KV counters (paged=True engines):
    #   pages_allocated     fresh pages handed out by the pool
    #   pages_reused        full pages attached to a row without a copy
    #                       (radix hits + intra-wave sibling sharing)
    #   prefix_hit_tokens   prompt tokens served from those shared pages
    #   prefill_tokens_saved  prompt tokens NOT prefilled (shared pages +
    #                       the COW-copied partial page) — the gap between
    #                       submitted and computed prefill work
    pages_allocated: int = 0
    pages_reused: int = 0
    prefix_hit_tokens: int = 0
    prefill_tokens_saved: int = 0
    # high-water KV-cache HBM footprint in bytes: the page pool for paged
    # engines, the largest epoch cache for dense ones
    cache_hbm_bytes: int = 0
    # ("admit" | "finish", job_index, decode_position, row) in event order —
    # the observable record that a queued job entered a freed row while its
    # siblings were still decoding.  Bounded: only the most recent
    # MAX_EVENTS survive, so a long-lived engine doesn't grow memory with
    # every job it ever served.
    events: List[Tuple[str, int, int, int]] = dataclasses.field(
        default_factory=list)
    MAX_EVENTS = 4096

    def record(self, kind: str, job: int, pos: int, row: int):
        self.events.append((kind, job, pos, row))
        if len(self.events) > self.MAX_EVENTS:
            del self.events[:len(self.events) - self.MAX_EVENTS]

    def add(self, prefill: int, decode: int):
        self.prefill_tokens += prefill
        self.decode_tokens += decode
        self.calls += 1

    def reset(self):
        """Zero every counter and drop the event log (fresh billing
        period for a reused engine)."""
        for f in dataclasses.fields(self):
            setattr(self, f.name, f.default_factory()
                    if f.default_factory is not dataclasses.MISSING
                    else f.default)


def _bucket(n: int, minimum: int = 64) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _bucket_clamped(n: int, max_seq_len: int, minimum: int = 64) -> int:
    # clamp: _bucket rounds up, so a non-power-of-two max_seq_len
    # (cap 3000 -> bucket 4096) must not push a batch past the limit
    # callers (and _truncate) enforce
    return min(_bucket(n, minimum), max_seq_len)


def _pack_plan(lens: Sequence[int], row_cap: int) -> List[List[int]]:
    """First-fit-decreasing bin packing of job lengths into rows of
    ``row_cap`` token slots.  Returns job indices per row."""
    order = sorted(range(len(lens)), key=lambda i: (-lens[i], i))
    rows: List[List[int]] = []
    space: List[int] = []
    for i in order:
        for r in range(len(rows)):
            if space[r] >= lens[i]:
                rows[r].append(i)
                space[r] -= lens[i]
                break
        else:
            rows.append([i])
            space.append(row_cap - lens[i])
    return rows


@dataclasses.dataclass
class _PagedPlan:
    """One job's admission plan against the page pool.

    ``pages`` is the row's full page run: ``reused_full`` shared pages
    (radix hits and/or pages borrowed from an earlier plan in the same
    wave), then the freshly allocated tail (whose first page is the COW
    destination when ``cow`` is set).  ``matched`` prompt tokens are
    already present (shared pages + COW fill) and only the remaining
    suffix is prefilled.  ``level`` orders intra-wave prefills: a plan
    borrowing pages written by a level-l sibling prefills at level l+1.
    """
    jid: int
    tokens: Tuple[int, ...]
    budget: int
    matched: int
    reused_full: int
    cow: Optional[Tuple[int, int, int]]    # (src_page, dst_page, fill)
    fresh: List[int]
    pages: List[int]
    level: int


def _cow_layers(layers, src, dst, fill):
    """Apply one batched COW copy to every layer's K and V pools."""
    return [{name: cow_copy(lc[name], src, dst, fill)
             for name in ("k", "v")} for lc in layers]


def _cache_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
               if hasattr(x, "dtype"))


def _fused_decode_loop(params, cfg: ModelConfig, first_logits, cache, key,
                       stop_ids, limit, temperature, *, buf_len: int,
                       greedy: bool):
    """Device-bound decode: sample/EOS/stop/early-exit inside one
    ``lax.while_loop``; returns (out_tokens (B, buf_len), n_decoded).

    ``buf_len`` (static) sizes the output buffer — the engine buckets it so
    nearby ``max_new_tokens`` values share one compiled executable — while
    ``limit`` (traced, <= buf_len) is the exact token budget the loop
    honours, so varying the budget costs no recompile.  ``temperature`` is
    likewise traced (only the ``greedy`` structure is static), so sweeping
    sampling temperatures never recompiles either.

    Per-row termination: EOS, or the last ``len(stop_ids)`` emitted tokens
    matching ``stop_ids`` (the stop marker itself is emitted so host-side
    ``text.split(stop)`` behaves identically).  The loop exits as soon as
    every row is done, and the final gated ``decode_step`` is skipped so no
    wasted step runs after the last live token.
    """
    b = first_logits.shape[0]
    n_stop = stop_ids.shape[0]
    eos = ByteTokenizer.EOS
    pad = ByteTokenizer.PAD
    limit = jnp.asarray(limit, jnp.int32)
    temperature = jnp.asarray(temperature, jnp.float32)

    key, sk = jax.random.split(key)
    tok0 = sample_traced(first_logits, sk, temperature, greedy=greedy)
    out0 = jnp.full((b, buf_len), pad, jnp.int32)
    state = (jnp.zeros((), jnp.int32), tok0, jnp.zeros((b,), bool), out0,
             jnp.zeros((), jnp.int32), cache, key)

    def cond(st):
        step, _tok, done, _out, _n, _cache, _key = st
        return (step < limit) & ~jnp.all(done)

    def body(st):
        step, tok, done, out, n, cache, key = st
        is_eos = tok == eos
        emit = ~done & ~is_eos
        out = jax.lax.dynamic_update_slice(
            out, jnp.where(emit, tok, pad)[:, None], (0, step))
        done = done | is_eos
        if 0 < n_stop <= buf_len:
            # rolling stop-sequence check over the last n_stop emitted
            # tokens (dynamic_slice clamps, and unwritten columns hold PAD
            # which never matches real stop bytes)
            win = jax.lax.dynamic_slice(out, (0, step - n_stop + 1),
                                        (b, n_stop))
            done = done | jnp.all(win == stop_ids[None, :], axis=1)
        n = n + jnp.sum(emit)

        cont = (step + 1 < limit) & ~jnp.all(done)

        def advance(operand):
            tok, cache, key = operand
            logits, cache = T.decode_step(params, cfg, tok[:, None], cache)
            key, sk = jax.random.split(key)
            return (sample_traced(logits[:, -1], sk, temperature,
                                  greedy=greedy), cache, key)

        tok, cache, key = jax.lax.cond(cont, advance, lambda op: op,
                                       (tok, cache, key))
        return step + 1, tok, done, out, n, cache, key

    _, _, _, out, n, _, _ = jax.lax.while_loop(cond, body, state)
    return out, n


def _serve_decode_loop(params, cfg: ModelConfig, tok, finished, out, n,
                       cache, keys, live, limit, temperature, stop_ids, *,
                       buf_len: int):
    """Slot-pool decode: run until ANY live row finishes, then yield.

    Unlike :func:`_fused_decode_loop` (which drains a whole batch), this
    loop services a persistent row pool: per-row traced token budget
    (``limit``), temperature and RNG lane (``keys``), and a per-row emit
    cursor ``n`` so rows admitted at different times write independent
    output prefixes.  The condition exits the moment a live row raises its
    ``finished`` flag, handing control to the host scheduler, which
    harvests that row, admits a queued job into it and resumes with the
    same compiled executable.

    On entry every live row's ``tok`` is a PENDING token (sampled, not yet
    emitted); the body emits pending tokens, checks termination, then
    unconditionally samples the next pending token — so at exit the
    surviving rows again hold pending tokens and resume seamlessly.  The
    price is one speculative ``decode_step`` per yield (O(admissions)
    waste, not O(tokens)).  The last output counts the steps whose Pallas
    decode fell back to reference attention (``T.decode_falls_back``).

    Stop detection mirrors the fused loop: the marker is emitted, rows
    whose window would start before their first emitted token never
    false-match (the gather is guarded by ``base >= 0``), and a stop
    longer than ``buf_len`` disables on-device detection entirely.
    """
    eos = ByteTokenizer.EOS
    n_stop = stop_ids.shape[0]
    cols = jnp.arange(buf_len)[None, :]

    def cond(st):
        _tok, finished, _out, _n, _cache, _keys, _fb = st
        return ~jnp.any(finished & live)

    def body(st):
        tok, finished, out, n, cache, keys, fallbacks = st
        is_eos = tok == eos
        emit = live & ~finished & ~is_eos & (n < limit)
        idx = jnp.clip(n, 0, buf_len - 1)
        out = jnp.where(emit[:, None] & (cols == idx[:, None]),
                        tok[:, None], out)
        n = n + emit.astype(jnp.int32)
        finished = finished | (live & is_eos)
        if 0 < n_stop <= buf_len:
            base = n - n_stop
            wcols = jnp.clip(base[:, None] + jnp.arange(n_stop)[None, :],
                             0, buf_len - 1)
            win = jnp.take_along_axis(out, wcols, axis=1)
            hit = (base >= 0) & jnp.all(win == stop_ids[None, :], axis=1)
            finished = finished | (live & hit)
        finished = finished | (live & (n >= limit))

        logits, new_cache = T.decode_step(params, cfg, tok[:, None], cache)
        fallbacks = fallbacks + T.decode_falls_back(cfg, cache, new_cache)
        keys, sub = split_rows(keys)
        tok = sample_rows(logits[:, -1], sub, temperature)
        return tok, finished, out, n, new_cache, keys, fallbacks

    return jax.lax.while_loop(
        cond, body, (tok, finished, out, n, cache, keys,
                     jnp.zeros((), jnp.int32)))


class InferenceEngine:
    """Serves one JAX model for batched generation.

    ``pack_jobs`` (default True) enables packed prefill for ragged job
    batches on supported configs (pure-attention decoder, no sliding
    window, no layer scan); unsupported configs or batches with nothing to
    gain fall back to one job per row transparently.

    ``mesh`` shards the whole hot path SPMD (see the module docstring for
    the layout): ``None`` keeps the single-device fast path, a
    ``jax.sharding.Mesh`` shards over it, and ``"auto"`` builds the
    default :func:`repro.launch.mesh.make_host_mesh` over every local
    device.  Params are placed once here; caches, prefill batches and
    per-row sampler lanes are committed to their shardings as they are
    created, and the jitted loops partition from there (computation
    follows data — admission scatters never gather the cache to host).

    ``paged=True`` replaces dense per-row KV buffers with a shared page
    pool + radix prefix index (``page_size`` tokens per page,
    ``num_pages`` pages per layer; requires a pure-attention decoder
    with a float KV dtype — ``can_page``).  Pool layout and admission
    flow:

      pool        per layer ``{"k","v"}: (num_pages, page_size, Hkv,
                  hd)``; page 0 is the reserved null page (dead rows'
                  speculative decode writes land there).  The pool — and
                  the radix index over it — PERSISTS across calls, so a
                  later call sharing a prompt prefix with an earlier one
                  prefills only the suffix.
      admission   jobs in a wave are lexicographically planned: each
                  matches the radix for its longest cached prefix (or
                  borrows full pages from the preceding job's plan when
                  that is longer), refcounts the shared pages,
                  copy-on-writes the partial divergence page, LRU-evicts
                  unreferenced prefixes if the pool is short, then batch-
                  prefills only the novel suffixes (one jitted prefill
                  per dependency level).  Full prompt pages are inserted
                  back into the radix for future reuse.
      decode      gathers K/V through the row's page table; the write
                  frontier page is never radix-indexed, so decode cannot
                  corrupt a committed prefix.

    Prefix-reuse observability lands in ``usage``: ``pages_allocated`` /
    ``pages_reused`` / ``prefix_hit_tokens`` / ``prefill_tokens_saved``
    and the cache HBM high-water ``cache_hbm_bytes``.
    """

    def __init__(self, cfg: ModelConfig, params, *,
                 tokenizer: Optional[ByteTokenizer] = None,
                 max_seq_len: int = 4096, decode_margin: int = 256,
                 truncate_long: bool = False, pack_jobs: bool = True,
                 mesh: "Mesh | str | None" = None, paged: bool = False,
                 page_size: int = 64, num_pages: int = 512):
        self.cfg = cfg
        if mesh == "auto":
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        elif isinstance(mesh, str):
            raise ValueError(f"mesh must be a Mesh, 'auto' or None: {mesh!r}")
        self.mesh = mesh
        if mesh is not None:
            params = jax.device_put(params, to_shardings(
                mesh, param_specs(mesh, params, cfg, decode=True)))
        self.params = params
        self.tokenizer = tokenizer or ByteTokenizer()
        self.max_seq_len = max_seq_len
        self.decode_margin = decode_margin
        self.truncate_long = truncate_long
        self.pack_jobs = pack_jobs
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.usage = EngineUsage()
        if self.paged and not self.can_page:
            raise ValueError(
                "paged=True requires a pure-attention decoder with a float "
                "KV dtype (no layer scan / enc-dec / MoE / sliding window / "
                "int8 KV)")
        # lazily built on first paged call: host-side allocator + prefix
        # index, and the device-resident per-layer K/V page pools
        self._pool: Optional[PagePool] = None
        self._radix: Optional[RadixIndex] = None
        self._kv_pool = None
        self._pool_bytes = 0

        self._prefill = jax.jit(
            partial(T.prefill, cfg=cfg), static_argnames=("capacity",))
        self._prefill_hidden = jax.jit(
            partial(T.prefill, cfg=cfg, return_hidden=True),
            static_argnames=("capacity",))
        self._decode = jax.jit(lambda params, tok, cache: T.decode_step(
            params, cfg, tok, cache))
        self._decode_loop = jax.jit(
            lambda params, first_logits, cache, key, stop_ids, limit,
            temperature, *, buf_len, greedy: _fused_decode_loop(
                params, cfg, first_logits, cache, key, stop_ids, limit,
                temperature, buf_len=buf_len, greedy=greedy),
            static_argnames=("buf_len", "greedy"))
        self._serve_loop = jax.jit(
            lambda params, tok, finished, out, n, cache, keys, live, limit,
            temperature, stop_ids, *, buf_len: _serve_decode_loop(
                params, cfg, tok, finished, out, n, cache, keys, live,
                limit, temperature, stop_ids, buf_len=buf_len),
            static_argnames=("buf_len",))
        self._paged_prefill_fn = jax.jit(
            lambda params, toks, pos, pta, dstp, dsts, layers:
            T.paged_prefill(params, cfg, toks, pos, pta, dstp, dsts, layers))
        self._cow_fn = jax.jit(_cow_layers)

    # ------------------------------------------------------------------
    @property
    def can_serve(self) -> bool:
        """Whether the cache layout supports slot admission (and packing):
        scattering a primed prompt into a live row addresses per-slot KV,
        so only pure-attention decoders qualify — SSM/hybrid state and
        cross-attention memory have no slot axis, sliding windows ring-wrap
        it, and MoE routing would let an admitted neighbour change which
        experts a live row's tokens reach."""
        cfg = self.cfg
        return (not cfg.scan_layers
                and not cfg.is_encdec
                and not cfg.is_moe
                and not cfg.sliding_window
                and all(cfg.layer_kind(i) == "attn"
                        for i in range(cfg.num_layers)))

    @property
    def can_pack(self) -> bool:
        return self.pack_jobs and self.can_serve

    @property
    def can_page(self) -> bool:
        """Whether the model supports the paged KV cache: the pool stores
        dequantized slot-addressable K/V, so everything :attr:`can_serve`
        needs plus a float KV dtype (int8 scales would have to be paged
        alongside the data — not implemented)."""
        return self.can_serve and self.cfg.kv_cache_dtype != "int8"

    # ------------------------------------------------------------------
    # mesh placement: commit arrays to their canonical shardings.  Each
    # helper is a no-op on a single-device engine; on a sharded engine it
    # is called O(1) per prefill / epoch (device-to-device placement,
    # never a host gather), so serve stays O(admissions).
    def _shard_batch(self, batch):
        if self.mesh is None:
            return batch
        return jax.device_put(batch, to_shardings(
            self.mesh, batch_specs(self.mesh, self.cfg, batch)))

    def _shard_cache(self, cache):
        if self.mesh is None:
            return cache
        return jax.device_put(cache, to_shardings(
            self.mesh, cache_specs(self.mesh, self.cfg, cache)))

    def _shard_rows(self, tree):
        """Per-row lanes (first-logits rows, sampler state) follow the
        decode rows across the data axes."""
        if self.mesh is None:
            return tree
        return jax.device_put(tree, to_shardings(
            self.mesh, row_specs(self.mesh, tree)))

    # ------------------------------------------------------------------
    def _bucket_checked(self, prompt_ids: Sequence[Sequence[int]]) -> int:
        max_len = max(len(p) for p in prompt_ids)
        if max_len > self.max_seq_len:
            raise ValueError(f"prompt length {max_len} exceeds engine "
                             f"max_seq_len {self.max_seq_len}")
        return _bucket_clamped(max_len, self.max_seq_len)

    def _truncate(self, prompt_ids: Sequence[Sequence[int]]):
        if not self.truncate_long:
            return list(prompt_ids)
        # keep the prompt TAIL (instructions come last in the worker
        # format); graceful degradation for over-long chunks
        lim = self.max_seq_len
        return [p if len(p) <= lim else p[-lim:] for p in prompt_ids]

    def _prepare_batch(self, prompt_ids: Sequence[Sequence[int]],
                       s: Optional[int] = None
                       ) -> Tuple[Dict[str, jnp.ndarray], int]:
        """Left-pad to a shared bucketed length; segment -1 marks padding."""
        if s is None:
            s = self._bucket_checked(prompt_ids)
        b = len(prompt_ids)
        toks = np.full((b, s), ByteTokenizer.PAD, np.int32)
        segs = np.full((b, s), -1, np.int32)
        for i, ids in enumerate(prompt_ids):
            toks[i, s - len(ids):] = ids
            segs[i, s - len(ids):] = 0
        return {"tokens": jnp.asarray(toks),
                "segment_ids": jnp.asarray(segs)}, s

    # ------------------------------------------------------------------
    def _prime_jobs(self, prompt_ids: Sequence[Sequence[int]],
                    plan: List[List[int]], s_job: int, end_pos: int):
        """Prefill jobs (packed into rows per ``plan``) and gather each
        job's KV into its own left-padded (n_jobs, s_job) row.

        Each job carries the RoPE positions of its destination layout —
        tokens occupy cache slots [end_pos - len, end_pos) — so the primed
        keys are rotated exactly as a direct prefill into that layout
        would rotate them, and decode continues seamlessly at position
        ``end_pos``.  ``end_pos == s_job`` reproduces packed prefill for a
        fresh batch; serve admission passes the pool's current decode
        position instead.  Returns (first_logits, per-layer KV dicts of
        (n_jobs, s_job, ...) arrays, valid mask (n_jobs, s_job)).
        """
        lens = [len(p) for p in prompt_ids]
        n_jobs, n_rows = len(prompt_ids), len(plan)

        toks = np.full((n_rows, s_job), ByteTokenizer.PAD, np.int32)
        segs = np.full((n_rows, s_job), -1, np.int32)
        poss = np.zeros((n_rows, s_job), np.int32)
        job_row = np.zeros(n_jobs, np.int32)
        job_off = np.zeros(n_jobs, np.int32)
        for r, jobs in enumerate(plan):
            off = 0
            for sid, i in enumerate(jobs):
                ln = lens[i]
                toks[r, off:off + ln] = prompt_ids[i]
                segs[r, off:off + ln] = sid
                poss[r, off:off + ln] = np.arange(end_pos - ln, end_pos)
                job_row[i], job_off[i] = r, off
                off += ln

        batch = self._shard_batch({"tokens": jnp.asarray(toks),
                                   "segment_ids": jnp.asarray(segs),
                                   "positions": jnp.asarray(poss)})
        _, cache_p, hidden = self._prefill_hidden(
            self.params, batch=batch, capacity=s_job)

        # logits of each job's LAST prompt token -> first sampled token
        last_slot = job_off + np.asarray(lens, np.int32) - 1
        h_last = hidden[jnp.asarray(job_row), jnp.asarray(last_slot)]
        first_logits = T.lm_head(self.params, h_last)

        # gather each job's packed KV slots into its decode row (device-side
        # fancy-indexing with host-precomputed static index maps)
        idx_row = np.zeros((n_jobs, s_job), np.int32)
        idx_slot = np.zeros((n_jobs, s_job), np.int32)
        valid = np.zeros((n_jobs, s_job), bool)
        for i in range(n_jobs):
            dst = s_job - lens[i]
            idx_row[i, dst:] = job_row[i]
            idx_slot[i, dst:] = np.arange(job_off[i], job_off[i] + lens[i])
            valid[i, dst:] = True
        ir, isl = jnp.asarray(idx_row), jnp.asarray(idx_slot)
        vmask = jnp.asarray(valid)

        layers = []
        for lc in cache_p["layers"]:
            nlc = {}
            for name, arr in lc.items():
                g = arr[ir, isl]                # (n_jobs, s_job, ...)
                ex = vmask.reshape(vmask.shape + (1,) * (g.ndim - 2))
                nlc[name] = jnp.where(ex, g, jnp.zeros((), g.dtype))
            layers.append(nlc)
        self.usage.prefill_slots += n_rows * s_job
        return first_logits, layers, vmask

    def _packed_prefill(self, prompt_ids: Sequence[Sequence[int]],
                        plan: List[List[int]], s_job: int,
                        max_new_tokens: int):
        """Packed prefill for a fresh batch: prime the jobs, then zero-pad
        the gathered rows out to the decode capacity.  Returns
        (first_logits, decode cache)."""
        first_logits, layers, vmask = self._prime_jobs(
            prompt_ids, plan, s_job, end_pos=s_job)
        cap = _bucket(s_job + max_new_tokens + self.decode_margin)
        new_layers = []
        for nlc in layers:
            new_layers.append({
                name: jnp.pad(
                    g, ((0, 0), (0, cap - s_job)) + ((0, 0),) * (g.ndim - 2))
                for name, g in nlc.items()})
        cache = {"layers": new_layers,
                 "pos": jnp.asarray(s_job, jnp.int32),
                 "slot_mask": jnp.pad(vmask, ((0, 0), (0, cap - s_job)))}
        return first_logits, cache

    # ------------------------------------------------------------------
    # paged KV cache: pool lifecycle, wave planning, admission
    # ------------------------------------------------------------------
    def _ensure_pool(self):
        """Build the page pool + radix index on first paged use.  The pool
        is engine-lifetime state: committed prefix pages survive across
        ``generate_batch``/``serve`` calls, so later calls sharing a
        prompt prefix skip its prefill entirely."""
        if self._kv_pool is not None:
            return
        self._pool = PagePool(self.num_pages, self.page_size)
        self._radix = RadixIndex(self.page_size)
        layers = T.init_paged_cache(self.cfg, self.num_pages, self.page_size)
        if self.mesh is not None:
            shell = {"layers": layers,
                     "page_table": jnp.zeros((1, 1), jnp.int32),
                     "row_len": jnp.zeros((1,), jnp.int32)}
            layers = self._shard_cache(shell)["layers"]
        self._kv_pool = layers
        self._pool_bytes = _cache_bytes(layers)
        self.usage.cache_hbm_bytes = max(self.usage.cache_hbm_bytes,
                                         self._pool_bytes)

    def _plan_paged_wave(self, jobs, *, strict: bool):
        """Plan one admission wave: ``jobs`` is [(jid, token_tuple,
        budget)].  Returns (plans, deferred jids).

        Jobs are planned in lexicographic prompt order so adjacent jobs
        share the longest prefixes.  Each job takes the better of two
        candidates: (A) the radix index's longest committed prefix —
        shared full pages plus an optional COW at a mid-page divergence —
        or (B) full pages borrowed from the previous plan in this wave
        (whose content a level-ordered prefill writes before this job's).
        The remainder (suffix + decode budget + margin) is freshly
        allocated, evicting LRU index-only prefixes if needed.  A job
        that still cannot allocate is deferred (``strict=False`` — serve
        retries after a harvest frees pages) or raises (``strict=True`` —
        generate_batch must admit everything)."""
        ps = self.page_size
        pool, radix = self._pool, self._radix
        order = sorted(jobs, key=lambda it: (it[1], it[0]))
        plans: List[_PagedPlan] = []
        deferred: List[int] = []
        fill_level: Dict[int, int] = {}
        prev: Optional[_PagedPlan] = None
        for jid, toks, budget in order:
            L = len(toks)
            cap = L - 1       # the last prompt token is always prefilled:
            #                   sampling needs its logits
            mpages, mfills = radix.match(toks)
            run: List[Tuple[int, int]] = []
            acc = 0
            for pg, fl in zip(mpages, mfills):
                take = min(fl, cap - acc)
                if take <= 0:
                    break
                run.append((pg, take))
                acc += take
                if take < fl:
                    break
            shared = [pg for pg, t in run if t == ps]
            cowsrc = run[-1] if run and run[-1][1] < ps else None
            borrow: List[int] = []
            if prev is not None:
                n_borrow = min(_lcp(toks, prev.tokens), cap) // ps
                if n_borrow * ps > acc:
                    # full-page borrowing beats the committed match (the
                    # borrowed content covers the same tokens, physical
                    # page identity is irrelevant to attention)
                    borrow = prev.pages[len(shared):n_borrow]
                    cowsrc = None
            matched = ((len(shared) + len(borrow)) * ps
                       + (cowsrc[1] if cowsrc else 0))
            for pg in shared + borrow:
                pool.retain(pg)
            if cowsrc is not None:
                pool.retain(cowsrc[0])   # pin the COW source until it runs
            need = (-(-(L + budget + self.decode_margin) // ps)
                    - len(shared) - len(borrow))
            if need > pool.available:
                radix.evict(pool, need)
            try:
                fresh = pool.alloc(need)
            except RuntimeError:
                for pg in shared + borrow:
                    pool.release(pg)
                if cowsrc is not None:
                    pool.release(cowsrc[0])
                if strict:
                    raise RuntimeError(
                        f"page pool exhausted: job {jid} needs {need} "
                        f"pages, {pool.available} free (num_pages="
                        f"{self.num_pages}, page_size={ps})")
                deferred.append(jid)
                continue
            cow = (cowsrc[0], fresh[0], cowsrc[1]) if cowsrc else None
            pages = shared + borrow + fresh
            level = 1 + max((fill_level.get(pg, 0) for pg in borrow),
                            default=0)
            for k in range(matched // ps, L // ps):
                fill_level[pages[k]] = level
            plan = _PagedPlan(jid=jid, tokens=toks, budget=budget,
                              matched=matched,
                              reused_full=len(shared) + len(borrow),
                              cow=cow, fresh=fresh, pages=pages,
                              level=level)
            plans.append(plan)
            prev = plan
        return plans, deferred

    def _prefill_paged_level(self, members: List[_PagedPlan], layers):
        """One batched suffix prefill: left-padded suffix tokens with
        canonical positions, per-token destination (page, slot) pairs and
        per-row page tables, through the jitted paged prefill."""
        ps = self.page_size
        m = len(members)
        sfx = [len(p.tokens) - p.matched for p in members]
        s_sfx = _bucket_clamped(max(sfx), self.max_seq_len, minimum=8)
        p_att = _bucket(max(-(-len(p.tokens) // ps) for p in members),
                        minimum=1)
        toks = np.full((m, s_sfx), ByteTokenizer.PAD, np.int32)
        poss = np.zeros((m, s_sfx), np.int32)
        dstp = np.zeros((m, s_sfx), np.int32)
        dsts = np.zeros((m, s_sfx), np.int32)
        pta = np.zeros((m, p_att), np.int32)
        for i, p in enumerate(members):
            ln = sfx[i]
            gpos = np.arange(p.matched, len(p.tokens))
            row_pages = np.asarray(p.pages, np.int32)
            toks[i, s_sfx - ln:] = p.tokens[p.matched:]
            poss[i, s_sfx - ln:] = gpos
            dstp[i, s_sfx - ln:] = row_pages[gpos // ps]
            dsts[i, s_sfx - ln:] = gpos % ps
            n_att = -(-len(p.tokens) // ps)
            pta[i, :n_att] = row_pages[:n_att]
        batch = self._shard_rows({
            "t": jnp.asarray(toks), "p": jnp.asarray(poss),
            "a": jnp.asarray(pta), "dp": jnp.asarray(dstp),
            "ds": jnp.asarray(dsts)})
        first_logits, layers = self._paged_prefill_fn(
            self.params, batch["t"], batch["p"], batch["a"], batch["dp"],
            batch["ds"], layers)
        self.usage.prefill_slots += m * s_sfx
        return first_logits, layers

    def _admit_plans(self, plans: List[_PagedPlan], layers):
        """Execute a planned wave: batched COW copies first (their sources
        are committed pages, untouched by this wave's prefills), then one
        batched suffix prefill per level (level l+1 reads pages level l
        wrote), then index every plan's full prompt pages for future
        reuse.  Returns (first_logits stacked in plan order, layers)."""
        ps = self.page_size
        cows = [p for p in plans if p.cow is not None]
        if cows:
            layers = self._cow_fn(
                layers,
                jnp.asarray([p.cow[0] for p in cows], jnp.int32),
                jnp.asarray([p.cow[1] for p in cows], jnp.int32),
                jnp.asarray([p.cow[2] for p in cows], jnp.int32))
            for p in cows:
                self._pool.release(p.cow[0])      # unpin the COW source
        by_level: Dict[int, List[int]] = {}
        for i, p in enumerate(plans):
            by_level.setdefault(p.level, []).append(i)
        rows = [None] * len(plans)
        for lvl in sorted(by_level):
            idxs = by_level[lvl]
            fl, layers = self._prefill_paged_level(
                [plans[i] for i in idxs], layers)
            for pos_in_level, i in enumerate(idxs):
                rows[i] = fl[pos_in_level]
        for p in plans:
            n_full = len(p.tokens) // ps
            self._radix.insert(p.tokens[:n_full * ps], p.pages[:n_full],
                               self._pool)
            self.usage.pages_allocated += len(p.fresh)
            self.usage.pages_reused += p.reused_full
            self.usage.prefix_hit_tokens += p.reused_full * ps
            self.usage.prefill_tokens_saved += p.matched
        self.usage.cache_hbm_bytes = max(self.usage.cache_hbm_bytes,
                                         self._pool_bytes)
        if _sanitize():
            self._pool.audit()
            if self._radix is not None:
                self._radix.audit(self._pool)
        return jnp.stack(rows), layers

    def _release_pages(self, pages):
        for pg in pages:
            self._pool.release(pg)

    def _paged_prime_batch(self, prompt_ids, max_new_tokens: int):
        """Paged prefill for a whole generate_batch: plan + admit every
        prompt in one wave, then assemble the (B-row) paged decode cache.
        Returns (first_logits, cache, plans) in batch order."""
        self._ensure_pool()
        jobs = [(i, tuple(p), max_new_tokens)
                for i, p in enumerate(prompt_ids)]
        plans, _ = self._plan_paged_wave(jobs, strict=True)
        first_logits, layers = self._admit_plans(plans, self._kv_pool)
        # keep the post-prefill pool: committed prefix pages hold prompt
        # KV; the decode loop's writes go to non-indexed tail pages of a
        # functional copy that is discarded with the batch
        self._kv_pool = layers
        n = len(prompt_ids)
        p_max = _bucket(max(len(p.pages) for p in plans), minimum=2)
        pt = np.zeros((n, p_max), np.int32)
        rl = np.zeros((n,), np.int32)
        inv = np.zeros((n,), np.int64)
        for row, p in enumerate(plans):
            pt[p.jid, :len(p.pages)] = p.pages
            rl[p.jid] = len(p.tokens)
            inv[p.jid] = row
        cache = {"layers": layers, "page_table": jnp.asarray(pt),
                 "row_len": jnp.asarray(rl)}
        return first_logits[jnp.asarray(inv)], cache, plans

    # ------------------------------------------------------------------
    def generate_batch(self, prompts: Sequence[str], *,
                       max_new_tokens: int = 128, temperature: float = 0.0,
                       key=None, stop: str = "\n###") -> List[str]:
        """Generate completions for a ragged batch of prompts."""
        if key is None:
            key = jax.random.PRNGKey(0)
        prompt_ids = self._truncate(
            [self.tokenizer.encode(p) for p in prompts])
        lens = [len(p) for p in prompt_ids]
        s_job = self._bucket_checked(prompt_ids)

        plan = plans = None
        if self.paged:
            # paged prefill: match each prompt against the prefix index /
            # its wave siblings and prefill only the novel suffixes (no
            # packing — prefix sharing subsumes it)
            first_logits, cache, plans = self._paged_prime_batch(
                prompt_ids, max_new_tokens)
        else:
            if self.can_pack and len(prompts) > 1:
                plan = _pack_plan(lens, s_job)
                if len(plan) >= len(prompts):    # nothing to gain
                    plan = None
            if plan is not None:
                first_logits, cache = self._packed_prefill(
                    prompt_ids, plan, s_job, max_new_tokens)
            else:
                batch, s = self._prepare_batch(prompt_ids, s_job)
                batch = self._shard_batch(batch)
                capacity = _bucket(s + max_new_tokens + self.decode_margin)
                logits, cache = self._prefill(self.params, batch=batch,
                                              capacity=capacity)
                first_logits = logits[:, -1]
                self.usage.prefill_slots += int(batch["tokens"].size)
        # commit the decode state to its canonical mesh layout (no-op on a
        # single-device engine): rows over "data", KV heads over "model"
        cache = self._shard_cache(cache)
        first_logits = self._shard_rows(first_logits)
        self.usage.cache_hbm_bytes = max(self.usage.cache_hbm_bytes,
                                         _cache_bytes(cache["layers"]))

        stop_ids = jnp.asarray(
            self.tokenizer.encode(stop, bos=False) if stop else [],
            jnp.int32)
        # output buffer is bucketed (static) and budget/temperature stay
        # traced scalars: nearby max_new_tokens values and all positive
        # temperatures share one compiled executable
        out, n_dec = self._decode_loop(
            self.params, first_logits, cache, key, stop_ids,
            max_new_tokens, temperature,
            buf_len=_bucket(max_new_tokens, minimum=8),
            greedy=temperature <= 0.0)

        # the ONLY host<->device result transfers of the call
        out_np = np.asarray(out)
        n_decoded = int(n_dec)
        self.usage.host_transfers += 2

        self.usage.add(sum(lens) if plans is None
                       else sum(len(p.tokens) - p.matched for p in plans),
                       n_decoded)
        if plans is not None:
            for p in plans:
                self._release_pages(p.pages)
        texts = [self.tokenizer.decode(row) for row in out_np]
        if stop:
            texts = [t.split(stop)[0] for t in texts]
        return texts

    # ------------------------------------------------------------------
    def serve(self, prompts: Sequence[str], *,
              max_new_tokens=128, temperature=0.0, key=None,
              per_job_keys=None, stop: str = "\n###",
              slots: int = 4) -> List[str]:
        """Continuously-batched generation over a fixed pool of decode rows.

        Jobs stream through ``slots`` persistent rows: the jitted
        :func:`_serve_decode_loop` yields whenever any row finishes, the
        freed rows are harvested, and queued jobs are prefilled (packed,
        with destination-layout RoPE positions) and scattered into them
        before the loop resumes — a short job never waits for a long
        sibling to drain (no convoy effect).  ``max_new_tokens`` and
        ``temperature`` may be scalars or per-job sequences; results come
        back in submission order; all jobs share one ``stop`` string.

        ``per_job_keys`` (optional, (n_jobs, 2) uint32) supplies each
        job's PRNG lane explicitly — the :class:`~repro.serving.
        JobScheduler` derives lanes from stable job identities so a
        shared multi-task pool samples independently of drain
        composition.  Without it, lanes default to
        ``fold_in(key, position)`` as before.

        Admission is length-aware: a fresh cache epoch admits the longest
        queued jobs (they define the prompt bucket and can only start at an
        epoch boundary), while mid-epoch the longest job that fits the
        current decode position and remaining cache capacity is preferred.
        When the pool drains and nothing fits the epoch's capacity, the
        cache is retired and a fresh epoch starts.  Configs whose caches
        have no slot axis (see :attr:`can_serve`) degrade to convoy batches
        of ``slots`` jobs.

        On a sharded engine the pool's rows (cache + sampler lanes) are
        distributed over the mesh's data axes and admission scatters run
        on device against the live sharded cache; ``slots`` is rounded up
        to a whole multiple of the data-axis size so every shard owns
        whole rows (surplus rows just stay non-live).
        """
        n = len(prompts)
        if n == 0:
            return []
        budgets = (list(max_new_tokens)
                   if isinstance(max_new_tokens, (list, tuple))
                   else [int(max_new_tokens)] * n)
        temps = (list(temperature) if isinstance(temperature, (list, tuple))
                 else [float(temperature)] * n)
        if key is None:
            key = jax.random.PRNGKey(0)
        if per_job_keys is not None:
            per_job_keys = jnp.asarray(per_job_keys, jnp.uint32)
            if per_job_keys.shape[0] != n:
                # a short array would gather-clamp to the last lane and
                # silently correlate the overflow jobs' samples
                raise ValueError(f"per_job_keys has {per_job_keys.shape[0]} "
                                 f"rows for {n} jobs")
        if not self.can_serve:
            # degrade to the scheduler's grouped convoy path — the single
            # implementation of param-class isolation (a greedy job never
            # inherits a stochastic neighbour's temperature or budget) and
            # within-class length grouping.  The plain-lambda target keeps
            # the scheduler off its engine path, so no recursion.
            from .scheduler import JobScheduler
            sched = JobScheduler(
                lambda ps, **kw: self.generate_batch(ps, stop=stop, **kw),
                max_batch=max(slots, 1))
            for j in range(n):
                sched.submit(prompts[j], temperature=temps[j],
                             max_new_tokens=budgets[j])
            return [r.text for r in sched.drain(key=key,
                                                lanes=per_job_keys)]

        pad = ByteTokenizer.PAD
        slots = max(1, min(slots, n))
        if self.mesh is not None:
            # round the pool up to whole rows per data shard: a 4-slot
            # pool on an 8-way data axis would fall into the
            # sequence-sharded cache fallback (reordered reductions, no
            # bit-identity guarantee); surplus rows just stay non-live
            from repro.parallel.sharding import data_axis_size
            da = data_axis_size(self.mesh)
            slots = -(-slots // da) * da
        prompt_ids = self._truncate(
            [self.tokenizer.encode(p) for p in prompts])
        self._bucket_checked(prompt_ids)     # raise early on over-long jobs
        buf_len = _bucket(max(budgets + [1]), minimum=8)
        stop_ids = jnp.asarray(
            self.tokenizer.encode(stop, bos=False) if stop else [],
            jnp.int32)

        if self.paged:
            return self._serve_paged(prompt_ids, n, budgets, temps, key,
                                     per_job_keys, stop, stop_ids, slots,
                                     buf_len)

        results: List[Optional[str]] = [None] * n
        queue = list(range(n))
        row_job = [-1] * slots
        cache = None
        tok = finished = out = n_emit = keys = live = limit = temp = None
        pos = 0
        total_prefill = total_decode = 0

        def by_length(jobs):
            return sorted(jobs, key=lambda j: (-len(prompt_ids[j]), j))

        def admission_groups(rows, jids):
            """Split an admission set into prefill groups: a packing engine
            primes the whole set in one packed prefill (first-fit absorbs
            the short jobs into the outlier's row); otherwise group by
            length bucket so a long outlier doesn't pad every short
            sibling's prefill row."""
            if self.can_pack and len(jids) > 1:
                return [(list(rows), list(jids))]
            groups: Dict[int, Tuple[List[int], List[int]]] = {}
            for r, j in zip(rows, jids):
                b = _bucket_clamped(len(prompt_ids[j]), self.max_seq_len)
                grp = groups.setdefault(b, ([], []))
                grp[0].append(r)
                grp[1].append(j)
            return [groups[b] for b in sorted(groups)]

        def admit(rows, jids):
            """Prefill ``jids`` and scatter their primed KV into ``rows``:
            job prompts land in slots [pos - len, pos) of their row, so the
            pool's shared decode position needs no per-row offset."""
            nonlocal tok, finished, out, n_emit, keys, live, limit, temp
            ids = [prompt_ids[j] for j in jids]
            lens = [len(p) for p in ids]
            s_a = self._bucket_checked(ids)
            plan = (_pack_plan(lens, s_a)
                    if self.can_pack and len(ids) > 1
                    else [[i] for i in range(len(ids))])
            first_logits, layers, _ = self._prime_jobs(ids, plan, s_a,
                                                       end_pos=pos)
            rows_arr = jnp.asarray(rows, jnp.int32)
            window = jnp.asarray(pos - s_a + np.arange(s_a), jnp.int32)
            new_layers = []
            for lc, nlc in zip(cache["layers"], layers):
                new_layers.append({
                    name: arr.at[rows_arr[:, None], window[None, :]].set(
                        nlc[name].astype(arr.dtype))
                    for name, arr in lc.items()})
            cache["layers"] = new_layers
            cap = cache["slot_mask"].shape[1]
            mrows = np.zeros((len(jids), cap), bool)
            for i, ln in enumerate(lens):
                mrows[i, pos - ln:pos] = True
            cache["slot_mask"] = cache["slot_mask"].at[rows_arr].set(
                jnp.asarray(mrows))
            base = (per_job_keys[jnp.asarray(jids, jnp.int32)]
                    if per_job_keys is not None else job_keys(key, jids))
            jkeys, sub = split_rows(base)
            jtemp = jnp.asarray([temps[j] for j in jids], jnp.float32)
            tok = tok.at[rows_arr].set(sample_rows(first_logits, sub, jtemp))
            finished = finished.at[rows_arr].set(False)
            live = live.at[rows_arr].set(True)
            out = out.at[rows_arr].set(pad)
            n_emit = n_emit.at[rows_arr].set(0)
            keys = keys.at[rows_arr].set(jkeys)
            limit = limit.at[rows_arr].set(
                jnp.asarray([budgets[j] for j in jids], jnp.int32))
            temp = temp.at[rows_arr].set(jtemp)
            for r, j in zip(rows, jids):
                row_job[r] = j
                queue.remove(j)
                self.usage.admitted_jobs += 1
                self.usage.record("admit", j, pos, r)
            return sum(lens)

        fallbacks = jnp.zeros((), jnp.int32)
        sanitize = _sanitize()
        if sanitize:
            xfer0 = self.usage.host_transfers
            waves0 = self.usage.admitted_jobs + self.usage.finished_jobs

        while queue or any(j >= 0 for j in row_job):
            if cache is None:
                self.usage.serve_epochs += 1
                first = by_length(queue)[:slots]
                s0 = self._bucket_checked([prompt_ids[j] for j in first])
                cap = _bucket(s0 + buf_len + self.decode_margin)
                cache = T.init_cache(self.cfg, slots, cap)
                pos = s0
                cache["pos"] = jnp.asarray(pos, jnp.int32)
                cache = self._shard_cache(cache)
                self.usage.cache_hbm_bytes = max(
                    self.usage.cache_hbm_bytes, _cache_bytes(cache))
                tok = jnp.zeros((slots,), jnp.int32)
                finished = jnp.ones((slots,), bool)
                live = jnp.zeros((slots,), bool)
                out = jnp.full((slots, buf_len), pad, jnp.int32)
                n_emit = jnp.zeros((slots,), jnp.int32)
                keys = jnp.zeros((slots, 2), jnp.uint32)
                limit = jnp.zeros((slots,), jnp.int32)
                temp = jnp.zeros((slots,), jnp.float32)
                # per-row sampler lanes shard with the rows they serve
                (tok, finished, live, out, n_emit, keys, limit,
                 temp) = self._shard_rows((tok, finished, live, out,
                                           n_emit, keys, limit, temp))
                row_job = [-1] * slots
                for g_rows, g_jids in admission_groups(
                        list(range(len(first))), first):
                    total_prefill += admit(g_rows, g_jids)
            else:
                free = [r for r in range(slots) if row_job[r] == -1]
                cap = cache["slot_mask"].shape[1]
                fits = [j for j in by_length(queue)
                        if _bucket_clamped(len(prompt_ids[j]),
                                           self.max_seq_len) <= pos
                        and pos + budgets[j] <= cap]
                if free and fits:
                    pick = fits[:len(free)]
                    for g_rows, g_jids in admission_groups(
                            free[:len(pick)], pick):
                        total_prefill += admit(g_rows, g_jids)
                elif not any(j >= 0 for j in row_job):
                    cache = None     # pool drained, nothing fits: new epoch
                    continue

            tok, finished, out, n_emit, cache, keys, fb = self._serve_loop(
                self.params, tok, finished, out, n_emit, cache, keys,
                live, limit, temp, stop_ids, buf_len=buf_len)
            fallbacks = fallbacks + fb

            # harvest — the only host<->device result transfers per yield
            fin_np = np.asarray(finished)
            n_np = np.asarray(n_emit)
            out_np = np.asarray(out)
            pos = int(cache["pos"])
            self.usage.host_transfers += 4
            done_rows = [r for r in range(slots)
                         if row_job[r] >= 0 and fin_np[r]]
            for r in done_rows:
                j = row_job[r]
                text = self.tokenizer.decode(out_np[r, :int(n_np[r])])
                results[j] = text.split(stop)[0] if stop else text
                total_decode += int(n_np[r])
                row_job[r] = -1
                self.usage.finished_jobs += 1
                self.usage.record("finish", j, pos, r)
            if done_rows:
                live = live.at[jnp.asarray(done_rows, jnp.int32)].set(False)

        self.usage.add(total_prefill, total_decode)
        self.usage.decode_fallback_steps += int(fallbacks)
        self.usage.host_transfers += 1
        if sanitize:
            # every 4-transfer harvest follows a wave that admitted or
            # finished >= 1 job, plus one read of the fallback count per
            # call, so transfers stay O(admissions), never O(decoded
            # tokens)
            used = self.usage.host_transfers - xfer0
            waves = (self.usage.admitted_jobs + self.usage.finished_jobs
                     - waves0)
            assert used <= 4 * waves + 5, (
                f"host-transfer budget exceeded: {used} transfers for "
                f"{waves} admit/finish events (budget 4*waves+5) — a "
                "per-token sync leaked into the serve loop")
        return [t if t is not None else "" for t in results]

    # ------------------------------------------------------------------
    def _serve_paged(self, prompt_ids, n, budgets, temps, key,
                     per_job_keys, stop, stop_ids, slots, buf_len):
        """Continuous batching over the page pool: no epochs, no shared
        decode position.  Each row carries its own page table and length
        (canonical positions), so admission is just planning pages for the
        next queued jobs and prefilling their novel suffixes — a freed
        row's pages return to the pool immediately and its page table is
        zeroed (speculative decode writes land in the null page)."""
        pad = ByteTokenizer.PAD
        ps = self.page_size
        self._ensure_pool()
        self.usage.serve_epochs += 1
        p_max = _bucket(
            max(-(-(len(prompt_ids[j]) + budgets[j] + self.decode_margin)
                  // ps) for j in range(n)), minimum=2)

        results: List[Optional[str]] = [None] * n
        queue = list(range(n))
        row_job = [-1] * slots
        row_pages: Dict[int, List[int]] = {}
        cache = {"layers": self._kv_pool,
                 "page_table": jnp.zeros((slots, p_max), jnp.int32),
                 "row_len": jnp.zeros((slots,), jnp.int32)}
        cache = self._shard_cache(cache)
        tok = jnp.zeros((slots,), jnp.int32)
        finished = jnp.ones((slots,), bool)
        live = jnp.zeros((slots,), bool)
        out = jnp.full((slots, buf_len), pad, jnp.int32)
        n_emit = jnp.zeros((slots,), jnp.int32)
        keys = jnp.zeros((slots, 2), jnp.uint32)
        limit = jnp.zeros((slots,), jnp.int32)
        temp = jnp.zeros((slots,), jnp.float32)
        (tok, finished, live, out, n_emit, keys, limit,
         temp) = self._shard_rows((tok, finished, live, out, n_emit, keys,
                                   limit, temp))
        total_prefill = total_decode = 0
        sanitize = _sanitize()
        if sanitize:
            xfer0 = self.usage.host_transfers
            waves0 = self.usage.admitted_jobs + self.usage.finished_jobs

        while queue or any(j >= 0 for j in row_job):
            free = [r for r in range(slots) if row_job[r] == -1]
            if free and queue:
                cand = queue[:len(free)]
                plans, _ = self._plan_paged_wave(
                    [(j, tuple(prompt_ids[j]), budgets[j]) for j in cand],
                    strict=False)
                if not plans and not any(j >= 0 for j in row_job):
                    j = cand[0]
                    raise RuntimeError(
                        f"page pool cannot fit job {j} "
                        f"({len(prompt_ids[j])} prompt + {budgets[j]} "
                        f"budget tokens) even with the pool idle: raise "
                        f"num_pages (={self.num_pages}) or lower "
                        f"max_new_tokens")
                if plans:
                    first_logits, layers = self._admit_plans(
                        plans, cache["layers"])
                    cache["layers"] = layers
                    rows = free[:len(plans)]
                    jids = [p.jid for p in plans]
                    pt_rows = np.zeros((len(plans), p_max), np.int32)
                    rl_rows = np.zeros((len(plans),), np.int32)
                    for i, p in enumerate(plans):
                        pt_rows[i, :len(p.pages)] = p.pages
                        rl_rows[i] = len(p.tokens)
                    rows_arr = jnp.asarray(rows, jnp.int32)
                    cache["page_table"] = cache["page_table"].at[
                        rows_arr].set(jnp.asarray(pt_rows))
                    cache["row_len"] = cache["row_len"].at[rows_arr].set(
                        jnp.asarray(rl_rows))
                    base = (per_job_keys[jnp.asarray(jids, jnp.int32)]
                            if per_job_keys is not None
                            else job_keys(key, jids))
                    jkeys, sub = split_rows(base)
                    jtemp = jnp.asarray([temps[j] for j in jids],
                                        jnp.float32)
                    tok = tok.at[rows_arr].set(
                        sample_rows(first_logits, sub, jtemp))
                    finished = finished.at[rows_arr].set(False)
                    live = live.at[rows_arr].set(True)
                    out = out.at[rows_arr].set(pad)
                    n_emit = n_emit.at[rows_arr].set(0)
                    keys = keys.at[rows_arr].set(jkeys)
                    limit = limit.at[rows_arr].set(
                        jnp.asarray([budgets[j] for j in jids], jnp.int32))
                    temp = temp.at[rows_arr].set(jtemp)
                    for r, p in zip(rows, plans):
                        row_job[r] = p.jid
                        row_pages[r] = p.pages
                        queue.remove(p.jid)
                        total_prefill += len(p.tokens) - p.matched
                        self.usage.admitted_jobs += 1
                        self.usage.record("admit", p.jid, len(p.tokens), r)

            # paged decode has no dense slot mask, hence no fallback count
            tok, finished, out, n_emit, cache, keys, _ = self._serve_loop(
                self.params, tok, finished, out, n_emit, cache, keys,
                live, limit, temp, stop_ids, buf_len=buf_len)

            # harvest — the only host<->device result transfers per yield
            fin_np = np.asarray(finished)
            n_np = np.asarray(n_emit)
            out_np = np.asarray(out)
            self.usage.host_transfers += 3
            done_rows = [r for r in range(slots)
                         if row_job[r] >= 0 and fin_np[r]]
            for r in done_rows:
                j = row_job[r]
                text = self.tokenizer.decode(out_np[r, :int(n_np[r])])
                results[j] = text.split(stop)[0] if stop else text
                total_decode += int(n_np[r])
                row_job[r] = -1
                self._release_pages(row_pages.pop(r))
                self.usage.finished_jobs += 1
                self.usage.record("finish", j,
                                  len(prompt_ids[j]) + int(n_np[r]), r)
            if done_rows:
                done_arr = jnp.asarray(done_rows, jnp.int32)
                live = live.at[done_arr].set(False)
                # quarantine dead rows: their pages may be reallocated
                # while the loop keeps speculatively decoding them, so
                # writes must drop into the null page and reads must not
                # touch freed pages
                cache["page_table"] = cache["page_table"].at[done_arr].set(0)
                cache["row_len"] = cache["row_len"].at[done_arr].set(0)

        # commit the decode-era pool: indexed prefix pages were never
        # written after indexing (decode lands beyond each prompt's full
        # pages), so the radix stays valid for future calls
        self._kv_pool = cache["layers"]
        self.usage.add(total_prefill, total_decode)
        if sanitize:
            self._pool.audit()   # all rows released: catch page leaks
            if self._radix is not None:
                self._radix.audit(self._pool)   # trie/pool reconcile
            used = self.usage.host_transfers - xfer0
            waves = (self.usage.admitted_jobs + self.usage.finished_jobs
                     - waves0)
            assert used <= 3 * waves + 3, (
                f"host-transfer budget exceeded: {used} transfers for "
                f"{waves} admit/finish events (budget 3*waves+3) — a "
                "per-token sync leaked into the paged serve loop")
        return [t if t is not None else "" for t in results]

    # ------------------------------------------------------------------
    def generate(self, prompt: str, **kw) -> str:
        return self.generate_batch([prompt], **kw)[0]
