"""Job scheduler — the "execute locally in parallel" MinionS step.

The single streaming entry point for worker fan-out: callers ``submit``
jobs — optionally replicating each one ``samples`` times for repeated
test-time sampling, §6.3 — and ``drain`` runs everything queued through
the engine's continuously-batched :meth:`InferenceEngine.serve` pool,
where length-aware admission streams queued jobs into decode rows the
moment they free up.  Results always come back in submission order.

One drain serves MULTIPLE waiters: a :class:`~repro.core.runtime.
ProtocolRunner` submits the pending worker batches of many concurrent
protocol tasks and drains once, so the slot pool continuously batches
jobs *across* tasks.  To keep that sound, a job's PRNG lane is derived
from its stable ``rng_id`` identity (the runner passes
``(task_id, job_index)``; the sample index is folded in per replica) —
``fold_in(fold_in(..fold_in(key, id0).., idN), sample_index)`` — never
from the job's position in whatever drain it happens to share.  Which
jobs coexist in a drain therefore cannot perturb a stochastic job's
sample stream.

Wrapping a plain ``generate_fn`` callable (no engine) falls back to the
legacy convoy path: jobs are length-sorted so same-batch prompts land in
the same engine length bucket, then run in fixed-size groups.  Plain
callables take ONE key per batch, so each group uses its first member's
lane (a function of that job's identity only — not of which other param
classes coexist in the drain, which is what the old split-per-group-in-
dict-order derivation leaked).  An ``InferenceEngine`` — or its bound
``generate_batch`` method — is detected and upgraded to the streaming
path automatically, where the per-job lanes are honoured exactly
(per-row sampling).

Draining a paged engine (``engine.paged``) lexicographically clusters the
expanded replicas by prompt before handing them to ``serve``, so jobs
sharing an instruction prefix are admitted into the same wave and hit the
engine's radix prefix index; results are still returned in submission
order and PRNG lanes travel with their jobs.

Mesh-sharded engines need no scheduler-side handling: ``serve`` itself
widens the ``max_batch`` slot pool to whole decode rows per data shard
(see :meth:`InferenceEngine.serve`), so the streaming path stays
row-aligned on any mesh.
"""
from __future__ import annotations

import dataclasses
from typing import (Callable, List, Optional, Sequence, Tuple, Union)

import jax
import jax.numpy as jnp

from .engine import InferenceEngine


class PoolSaturated(RuntimeError):
    """Backpressure signal: a bounded scheduler/gateway queue is full and
    the submission was REJECTED (nothing was queued).  Callers either
    shed the job or retry after a drain; without a bound the queue grows
    without limit and the caller gets no signal at all — this is the
    admission-control seam the fleet gateway plugs into."""


@dataclasses.dataclass
class ScheduledResult:
    """One (job, sample) replica's result.  ``error`` is set (and ``text``
    empty) when the replica's batch failed — a failed batch poisons only
    its own rows, never the rest of the drain."""
    job_index: int
    sample_index: int
    text: str
    error: Optional[Exception] = None


@dataclasses.dataclass
class _Pending:
    job_index: int
    prompt: str
    samples: int
    temperature: float
    max_new_tokens: int
    rng_id: Tuple[int, ...]


def job_lane(key, rng_id: Tuple[int, ...], sample_index: int):
    """Stable per-replica PRNG lane: fold the identity components, then
    the sample index.  Structurally collision-free across distinct
    identities (unlike a ``job_index * stride + sample`` flattening,
    which needs a uniform stride) and invariant to everything else in
    the drain."""
    lane = key
    for part in rng_id:
        lane = jax.random.fold_in(lane, int(part))
    return jax.random.fold_in(lane, int(sample_index))


def _replica_lanes(key, expanded):
    """Vectorized :func:`job_lane` over a drain's expanded replicas:
    identities of equal arity fold together with one vmapped ``fold_in``
    per component — O(arity) dispatches per arity group, not
    O(replicas · arity) scalar dispatches on the drain hot path."""
    out = [None] * len(expanded)
    by_arity = {}
    for ei, (_, si, p) in enumerate(expanded):
        by_arity.setdefault(len(p.rng_id), []).append(ei)
    for arity, idxs in by_arity.items():
        cols = jnp.asarray([[*expanded[ei][2].rng_id, expanded[ei][1]]
                            for ei in idxs], jnp.uint32)
        keys = jnp.broadcast_to(key, (len(idxs),) + jnp.shape(key))
        for c in range(arity + 1):
            keys = jax.vmap(jax.random.fold_in)(keys, cols[:, c])
        for ei, lane in zip(idxs, keys):
            out[ei] = lane
    return jnp.stack(out)


class JobScheduler:
    def __init__(self,
                 target: Union[InferenceEngine, Callable[..., List[str]]],
                 *, max_batch: int = 16, max_queue: Optional[int] = None):
        """``target``: an InferenceEngine (streaming serve pool of
        ``max_batch`` slots) or a plain ``(prompts, temperature=..., key=...,
        max_new_tokens=...) -> texts`` callable (legacy grouped batching).

        ``max_queue`` bounds the submission queue: once that many jobs
        are queued for the next drain, further :meth:`submit` calls raise
        :class:`PoolSaturated` (and :meth:`try_submit` reports
        ``"rejected"``) instead of growing the backlog without any
        backpressure signal.  ``None`` (default) keeps the historical
        unbounded behaviour."""
        engine = target if isinstance(target, InferenceEngine) else \
            getattr(target, "__self__", None)
        self.engine = engine if isinstance(engine, InferenceEngine) else None
        self.generate_fn = None if self.engine is not None else target
        self.max_batch = max_batch
        self.max_queue = max_queue
        #: (job_index, sample_index) pairs of the last drain, in the order
        #: they were handed to the engine/callable — the fleet gateway maps
        #: EngineUsage finish events back through this to stream results in
        #: freed-row order
        self.last_perm: Optional[List[Tuple[int, int]]] = None
        self._queue: List[_Pending] = []
        self._next_job = 0
        self._lane_ids = set()    # (rng_id, sample) identities queued
        # shared-pool observability: how many engine drains this scheduler
        # ran and how many (job, sample) replicas they served — a
        # concurrent multi-task runner shows fewer drains for the same
        # jobs_drained than task-serial execution
        self.drains = 0
        self.jobs_drained = 0

    # ------------------------------------------------------------------
    def submit(self, prompt: str, *, samples: int = 1,
               temperature: float = 0.2,
               max_new_tokens: int = 128,
               rng_id: Optional[Union[int, Tuple[int, ...]]] = None) -> int:
        """Queue one job (``samples`` stochastic repeats); returns its
        job index.  Nothing runs until :meth:`drain`.

        ``rng_id`` is the job's stable PRNG identity (an int or tuple of
        ints, e.g. the runner's ``(task_id, job_index)``); it defaults to
        the job index within the current queue, which preserves the
        single-caller behaviour but is NOT stable across different drain
        compositions — multi-waiter callers should pass their own.
        Submitting a replica whose ``(rng_id, sample)`` identity is
        already queued raises ``ValueError`` (its samples would be
        perfectly correlated with the earlier job's)."""
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            raise PoolSaturated(
                f"scheduler queue full ({len(self._queue)}/{self.max_queue} "
                "jobs pending); drain before submitting more")
        ji = self._next_job
        if rng_id is None:
            rng_id = (ji,)
        elif isinstance(rng_id, int):
            rng_id = (rng_id,)
        rng_id = tuple(rng_id)
        replicas = {(rng_id, si) for si in range(samples)}
        clash = replicas & self._lane_ids
        if clash:
            # two replicas sharing a lane would draw perfectly correlated
            # "independent" samples — always an identity misuse (e.g. an
            # explicit rng_id colliding with a default queue-position one,
            # or duplicate task_ids).  Rejecting HERE leaves the queue
            # valid, so the caller can resubmit with a fixed identity.
            raise ValueError(f"PRNG identity {min(clash)} already queued; "
                             "pass distinct rng_ids")
        self._next_job += 1
        self._lane_ids |= replicas
        self._queue.append(_Pending(ji, prompt, samples, temperature,
                                    max_new_tokens, rng_id))
        return ji

    def try_submit(self, prompt: str, **kw) -> Tuple[str, Optional[int]]:
        """Outcome-style admission: ``("queued", job_index)`` on success,
        ``("rejected", None)`` when the bounded queue is saturated —
        callers that shed load instead of unwinding (the fleet gateway,
        open-loop load generators) branch on the outcome rather than
        catching :class:`PoolSaturated`.  Identity errors (duplicate
        ``rng_id``) still raise: they are caller bugs, not load."""
        try:
            return "queued", self.submit(prompt, **kw)
        except PoolSaturated:
            return "rejected", None

    def drain(self, *, seed: int = 0, key=None,
              lanes=None) -> List[ScheduledResult]:
        """Run every queued job to completion and return results in
        submission order.  The queue is left empty and job numbering
        restarts at 0 (each drain is an independent batch, so
        ``job_index`` always indexes that batch's submission order).
        ``key`` overrides the PRNGKey derived from ``seed``; ``lanes``
        (advanced, (n_expanded, 2)) overrides the identity-derived
        per-replica lanes entirely — :meth:`InferenceEngine.serve` uses
        it to thread caller lanes through its non-slot fallback."""
        expanded = [(p.job_index, si, p)
                    for p in self._queue for si in range(p.samples)]
        self._queue, self._next_job = [], 0
        self._lane_ids = set()
        if not expanded:
            return []
        with jax.profiler.TraceAnnotation("scheduler.drain",
                                          jobs=len(expanded)):
            if lanes is not None and len(lanes) != len(expanded):
                raise ValueError(f"lanes has {len(lanes)} rows for "
                                 f"{len(expanded)} expanded replicas")
            if key is None:
                key = jax.random.PRNGKey(seed)
            self.drains += 1
            self.jobs_drained += len(expanded)
            if lanes is None:
                lanes = _replica_lanes(key, expanded)
            if self.engine is not None:
                # Paged engines admit from the serve queue in submission order,
                # so cluster prefix-sharing prompts ADJACENTLY here: jobs with
                # a common instruction prefix land in the same admission wave,
                # where the engine's planner shares their prefix pages.  Texts
                # are un-permuted below; lanes travel with their jobs, so the
                # reorder cannot perturb any replica's sample stream.
                order = list(range(len(expanded)))
                if getattr(self.engine, "paged", False):
                    order.sort(key=lambda ei: (expanded[ei][2].prompt, ei))
                perm = [expanded[ei] for ei in order]
                self.last_perm = [(ji, si) for ji, si, _ in perm]
                try:
                    texts = self.engine.serve(
                        [p.prompt for _, _, p in perm],
                        max_new_tokens=[p.max_new_tokens for _, _, p in perm],
                        temperature=[p.temperature for _, _, p in perm],
                        key=key, per_job_keys=lanes[jnp.asarray(order)],
                        slots=self.max_batch)
                except Exception as e:  # noqa: BLE001 — one SPMD program
                    # the pool is one program: a serve failure is every row's
                    # failure, reported per row instead of wedging the drain
                    results = [ScheduledResult(ji, si, "", e)
                               for ji, si, _ in expanded]
                else:
                    results = [ScheduledResult(ji, si, t)
                               for (ji, si, _), t in zip(perm, texts)]
            else:
                results = self._drain_grouped(expanded, lanes)
            results.sort(key=lambda r: (r.job_index, r.sample_index))
            return results

    def _drain_grouped(self, expanded, lanes) -> List[ScheduledResult]:
        """Legacy convoy batching for plain generate callables: jobs with
        identical sampling params batch together (a greedy job must never
        inherit a stochastic neighbour's temperature or budget), and within
        a param class length-alike jobs share a batch (stable on submission
        order for equal lengths) so a batch of uniformly-short jobs pads to
        a small bucket instead of the longest outlier's.

        Each batch's key is its first member's identity lane (plain
        callables accept one key per batch): deterministic, and — unlike
        the old one-``split``-per-group-in-dict-iteration-order scheme —
        independent of which other param classes coexist in the drain.
        Within-batch composition still influences stochastic draws (the
        callable samples the whole batch under one key); the engine
        streaming path has no such coupling (true per-row lanes)."""
        classes = {}
        for ei, item in enumerate(expanded):
            p = item[2]
            classes.setdefault((p.temperature, p.max_new_tokens),
                               []).append((ei, item))
        results: List[ScheduledResult] = []
        self.last_perm = None      # grouped path reports no finish order
        for (t, b), members in classes.items():
            members = sorted(members, key=lambda m: len(m[1][2].prompt))
            for off in range(0, len(members), self.max_batch):
                group = members[off:off + self.max_batch]
                sub = lanes[group[0][0]]
                try:
                    texts = self.generate_fn(
                        [p.prompt for _, (_, _, p) in group], temperature=t,
                        key=sub, max_new_tokens=b)
                except Exception as e:     # noqa: BLE001 — isolation wall
                    # the failed batch's rows carry the error; every other
                    # batch in the drain still runs
                    results += [ScheduledResult(ji, si, "", e)
                                for _, (ji, si, _) in group]
                    continue
                for (_, (ji, si, _)), text in zip(group, texts):
                    results.append(ScheduledResult(ji, si, text))
        return results

    # ------------------------------------------------------------------
    def run(self, prompts: Sequence[str], *, samples: int = 1,
            temperature: float = 0.2, seed: int = 0,
            max_new_tokens: int = 128) -> List[ScheduledResult]:
        """Submit-all-then-drain convenience wrapper."""
        for p in prompts:
            self.submit(p, samples=samples, temperature=temperature,
                        max_new_tokens=max_new_tokens)
        return self.drain(seed=seed)
