"""Resumable protocol runtime: protocols as action streams.

Protocols (Minion, MinionS, the baselines) are no longer blocking
functions that own their clients.  Each one is a *generator* that yields
typed actions — :class:`RemoteCall`, :class:`LocalBatch`, :class:`Final` —
and receives each action's result at the matching ``send``.  The
:class:`ProtocolRunner` drives **many tasks concurrently** over one shared
serve pool: every step it collects the pending ``LocalBatch`` actions from
all live tasks into one persistent :class:`~repro.serving.JobScheduler`
drain (cross-task continuous batching — the engine's slot pool fills with
worker jobs from *every* task, not one task's private batch) and services
independent ``RemoteCall`` actions as one batched remote request, then
resumes each task with its results.

Token accounting is uniform: the runner meters both sides of every task
through :class:`~repro.core.clients.UsageMeter` (the local side in
``free=True`` mode, §3 of the paper — tracked but not costed), so no
protocol hand-rolls ``approx_tokens`` sums.

Determinism: a local job's PRNG lane is derived from
``(task_id, job_index, sample_index)`` — stable identities the runner
assigns — never from where the job lands in a shared drain, so which
tasks happen to coexist in the pool cannot perturb stochastic sampling.

Single-task use stays one line via the compatibility wrappers
(``run_minion`` / ``run_minions`` / ...), which build a one-task runner
and return the identical :class:`ProtocolResult`.

Failure semantics
-----------------

The runner is a supervision layer: one task's fault never aborts its
siblings, and every fault is delivered *to the protocol*, which gets to
adapt before the runner gives up on it.

* **Task status lifecycle** (``ProtocolResult.status``): every task ends
  ``"ok"`` | ``"degraded"`` | ``"failed"``.  ``ok`` — completed with no
  fault delivered.  ``degraded`` — completed although at least one of its
  actions failed (the exception was thrown into the generator and caught,
  or a ``fallback="degrade"`` RemoteCall was resumed with a
  :class:`RemoteFailure`).  ``failed`` — the generator let an exception
  escape (or raised its own); the error is captured in
  ``ProtocolResult.error``, usage metered up to the failure is preserved,
  and the runner keeps driving every other live task.

* **Fault delivery**: ``_service_remote`` resolves each RemoteCall to a
  per-prompt outcome (:func:`~repro.core.clients.complete_outcomes_any`);
  an Exception outcome is thrown INTO the protocol generator at its yield
  point (``gen.throw``), so a protocol can ``try/except`` around a yield
  and recover mid-flight.  Failed local drain rows arrive the same way.

* **Degradation**: a ``RemoteCall(fallback="degrade")`` never throws — on
  failure the task is resumed with a :class:`RemoteFailure` value instead
  and chooses its own fallback (MinionS: local-only synthesis over the
  surviving worker extractions — the paper's cost/quality tradeoff
  enacted at runtime).  Degradation events are visible in the task's
  transcript/round records and in the runner's ``degradations`` counter.

* **Breaker states**: wrap the remote in a
  :class:`~repro.core.clients.ResilientClient` for retries/timeouts and a
  closed → open → half-open circuit breaker; every attempt (failed
  retries included) stays metered.  With a seeded fault schedule
  (:mod:`repro.core.faults`) and seeded retry jitter, two identical runs
  are bit-identical — statuses, answers and usage included.
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, Generator, List, Optional, Sequence,
                    Tuple, Union)

from .clients import UsageMeter, complete_batch_any, complete_outcomes_any
from .types import ProtocolResult, RoundRecord, Usage

# --------------------------------------------------------------------------
# typed actions
# --------------------------------------------------------------------------


@dataclasses.dataclass
class RemoteCall:
    """Ask the (costed) remote model for one completion.

    The runner batches RemoteCalls from different tasks that share
    sampling params into one ``complete_batch`` request per step.
    ``send`` value: the completion text (str).

    ``fallback`` is the call's failure policy: ``None`` (default) throws
    the failure into the generator at the yield (catchable); ``"degrade"``
    resumes the generator with a :class:`RemoteFailure` value instead, so
    the protocol can gracefully degrade (e.g. local-only synthesis)
    without exception plumbing."""
    prompt: str
    max_tokens: int = 256
    temperature: float = 0.0
    fallback: Optional[str] = None


@dataclasses.dataclass
class RemoteFailure:
    """Resume value delivered for a failed ``RemoteCall`` that carried
    ``fallback="degrade"``: falsy, carries the underlying exception.
    Receiving one marks the task ``degraded`` (if it completes)."""
    error: Exception

    def __bool__(self) -> bool:
        return False

    def __str__(self) -> str:
        return f"{type(self.error).__name__}: {self.error}"


class _Throw:
    """Runner-internal reply marker: deliver ``exc`` via ``gen.throw``."""
    __slots__ = ("exc",)

    def __init__(self, exc: Exception):
        self.exc = exc


@dataclasses.dataclass
class LocalBatch:
    """Fan a batch of prompts out to the (free) local worker pool.

    ``samples`` replicates each prompt for repeated test-time sampling
    (paper §6.3); results come back flat in ``(prompt, sample)`` order,
    ``len(prompts) * samples`` long.  All tasks' pending LocalBatches are
    merged into ONE scheduler drain per runner step.
    ``send`` value: List[str]."""
    prompts: List[str]
    temperature: float = 0.0
    max_tokens: int = 256
    samples: int = 1


@dataclasses.dataclass
class Final:
    """Terminal action: the task's answer plus its protocol-specific
    round records and transcript.  The runner folds in the metered
    usage to build the :class:`ProtocolResult`."""
    answer: Optional[str]
    rounds: List[RoundRecord] = dataclasses.field(default_factory=list)
    transcript: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)


Action = Union[RemoteCall, LocalBatch, Final]


# --------------------------------------------------------------------------
# protocol registry
# --------------------------------------------------------------------------

#: name -> generator function ``protocol(task: TaskContext)`` yielding
#: actions.  Protocol modules self-register at import time.
PROTOCOLS: Dict[str, Callable[["TaskContext"], Generator]] = {}


def register_protocol(name: str):
    def deco(fn):
        PROTOCOLS[name] = fn
        return fn
    return deco


def get_protocol(name: str):
    """Resolve a registered protocol, importing the built-in protocol
    modules on first use (they self-register)."""
    if name not in PROTOCOLS:
        from . import baselines, minion, minions, rag  # noqa: F401
    if name not in PROTOCOLS:
        raise KeyError(f"unknown protocol {name!r}; "
                       f"registered: {sorted(PROTOCOLS)}")
    return PROTOCOLS[name]


# --------------------------------------------------------------------------
# task state
# --------------------------------------------------------------------------


@dataclasses.dataclass
class TaskSpec:
    """One (protocol, document, query) unit of work for the runner.

    ``task_id`` seeds the task's PRNG identity (local jobs get
    ``(task_id, job_index)`` lanes); it defaults to the task's position
    in the ``run`` call.  Pass it explicitly when the same logical task
    must sample identically across different run compositions (e.g. a
    serial-vs-concurrent comparison over a stochastic engine)."""
    protocol: Union[str, Callable[["TaskContext"], Generator]]
    context: str
    query: str
    cfg: Any = None
    task_id: Optional[int] = None


@dataclasses.dataclass
class TaskContext:
    """What a protocol generator sees: its inputs plus live usage views.

    ``remote_usage`` / ``local_usage`` are the runner's per-task meters,
    updated *before* the generator is resumed after each action — so a
    protocol can diff them across a round to build per-round records,
    exactly like the old blocking loops did with their private meters."""
    task_id: int
    context: str
    query: str
    cfg: Any = None
    remote_meter: UsageMeter = None
    local_meter: UsageMeter = None

    @property
    def remote_usage(self) -> Usage:
        return self.remote_meter.usage

    @property
    def local_usage(self) -> Usage:
        return self.local_meter.usage


class _LiveTask:
    """Runner-internal: a protocol generator mid-flight."""

    def __init__(self, index: int, spec: TaskSpec):
        fn = (get_protocol(spec.protocol)
              if isinstance(spec.protocol, str) else spec.protocol)
        self.index = index
        tid = spec.task_id if spec.task_id is not None else index
        # record()-only meters: the runner executes all calls itself
        # (batched across tasks) and meters each task's share here
        self.ctx = TaskContext(task_id=tid, context=spec.context,
                               query=spec.query, cfg=spec.cfg,
                               remote_meter=UsageMeter(),
                               local_meter=UsageMeter(free=True))
        self.gen = fn(self.ctx)
        self.pending: Optional[Action] = None
        self.result: Optional[ProtocolResult] = None
        self.next_job = 0     # per-task job counter -> stable PRNG identity
        self.faults = 0       # failures delivered (thrown or RemoteFailure)

    def advance(self, value=None, *, first: bool = False,
                throw: bool = False) -> None:
        """Resume the generator until it yields its next awaitable action
        (or finishes).  ``Final`` terminates the task immediately.
        ``throw=True`` delivers ``value`` (an Exception) via
        ``gen.throw`` at the yield point; a protocol that doesn't catch
        it — or raises on its own — ends ``failed``, never aborting its
        sibling tasks."""
        try:
            if first:
                action = next(self.gen)
            elif throw:
                action = self.gen.throw(value)
            else:
                action = self.gen.send(value)
        except StopIteration:
            self._finish(Final(None))
            return
        except Exception as e:             # noqa: BLE001 — isolation wall
            self._fail(e)
            return
        if isinstance(action, Final):
            self._finish(action)
        elif isinstance(action, (RemoteCall, LocalBatch)):
            self.pending = action
        else:
            raise TypeError(f"protocol yielded {type(action).__name__}; "
                            "expected RemoteCall | LocalBatch | Final")

    def _finish(self, fin: Final) -> None:
        self.gen.close()
        self.pending = None
        self.result = ProtocolResult(
            answer=fin.answer,
            remote_usage=self.ctx.remote_meter.usage,
            local_prefill_tokens=self.ctx.local_meter.usage.prefill_tokens,
            local_decode_tokens=self.ctx.local_meter.usage.decode_tokens,
            rounds=fin.rounds, transcript=fin.transcript,
            status="degraded" if self.faults else "ok")

    def _fail(self, exc: Exception) -> None:
        self.gen.close()
        self.pending = None
        self.result = ProtocolResult(
            answer=None,
            remote_usage=self.ctx.remote_meter.usage,
            local_prefill_tokens=self.ctx.local_meter.usage.prefill_tokens,
            local_decode_tokens=self.ctx.local_meter.usage.decode_tokens,
            status="failed", error=f"{type(exc).__name__}: {exc}")


# --------------------------------------------------------------------------
# the runner
# --------------------------------------------------------------------------


class ProtocolRunner:
    """Drive many protocol tasks concurrently over one shared serve pool.

    ``local`` may be an :class:`~repro.core.clients.EngineClient` (its
    streaming scheduler is reused), an
    :class:`~repro.serving.InferenceEngine`, a plain ``LMClient``, or
    ``None`` (protocols that never yield a ``LocalBatch``).  ``remote``
    is any ``LMClient`` (or ``None`` for local-only work).  A
    pre-existing :class:`~repro.serving.JobScheduler` can be passed
    explicitly to share one pool across several runners (e.g. a serial
    baseline measured against the same engine).

    Each runner *step* services every live task's pending action:
    all ``LocalBatch`` prompts are submitted to the shared scheduler with
    ``(task_id, job_index)`` PRNG identities and run in ONE drain;
    ``RemoteCall`` prompts are grouped by sampling params and served by
    one ``complete_batch`` per group.  Tasks advance independently — a
    task blocked on the remote never stalls its siblings' worker jobs.
    """

    def __init__(self, local=None, remote=None, *, max_batch: int = 8,
                 seed: Optional[int] = None, scheduler=None):
        self.local = local
        self.remote = remote
        # default the drain seed from the local client (EngineClient
        # carries one), so wrapping a seeded client keeps its sampling
        self.seed = seed if seed is not None \
            else getattr(local, "seed", 0)
        self.scheduler = scheduler or self._build_scheduler(local, max_batch)
        # supervision observability: faults delivered into tasks and how
        # many of those took a fallback="degrade" path
        self.faults_delivered = 0
        self.degradations = 0

    @staticmethod
    def _build_scheduler(local, max_batch: int):
        if local is None:
            return None
        from repro.serving import InferenceEngine, JobScheduler
        from repro.serving.fleet import EnginePool
        if isinstance(local, EnginePool):
            # the pool IS a fleet-aware scheduler facade: one runner
            # spreads each merged LocalBatch drain across the replicas
            # (identity-derived RNG lanes travel with the jobs, so
            # placement cannot perturb any task's sampling)
            return local
        sched = getattr(local, "scheduler", None)    # EngineClient
        if sched is not None:
            return sched
        if isinstance(local, InferenceEngine) or \
                isinstance(getattr(local, "__self__", None),
                           InferenceEngine):
            return JobScheduler(local, max_batch=max_batch)

        def _complete(prompts, temperature=0.0, key=None,
                      max_new_tokens=128):
            # client objects batch via complete_batch/complete; a bare
            # callable (e.g. a bound complete_batch) takes the client
            # batch signature directly
            if hasattr(local, "complete") or hasattr(local, "complete_batch"):
                return complete_batch_any(local, prompts,
                                          temperature=temperature,
                                          max_tokens=max_new_tokens)
            return local(prompts, temperature=temperature,
                         max_tokens=max_new_tokens)

        return JobScheduler(_complete, max_batch=max_batch)

    # ------------------------------------------------------------------
    def run(self, specs: Sequence[TaskSpec]) -> List[ProtocolResult]:
        """Run every task to completion; results in ``specs`` order.

        Each step is a ``protocol.step`` span of the profiler's trace, its
        remote and local servicing ``protocol.remote`` and
        ``protocol.local`` spans inside it (jax is imported here, not at
        module import, so the protocol layer stays importable without
        it)."""
        from jax.profiler import TraceAnnotation
        tids = [s.task_id if s.task_id is not None else i
                for i, s in enumerate(specs)]
        if len(set(tids)) != len(tids):
            # duplicate identities would correlate two tasks' "independent"
            # stochastic sampling (or trip the drain's lane-collision check
            # far from the cause) — reject with the cause named
            dup = sorted(t for t in set(tids) if tids.count(t) > 1)
            raise ValueError(f"duplicate task_id(s) {dup} across specs "
                             "(explicit task_ids must not collide with "
                             "each other or with positional defaults)")
        tasks = [_LiveTask(i, s) for i, s in enumerate(specs)]
        for t in tasks:
            t.advance(first=True)
        while True:
            local_waiters = [t for t in tasks
                             if isinstance(t.pending, LocalBatch)]
            remote_waiters = [t for t in tasks
                              if isinstance(t.pending, RemoteCall)]
            if not local_waiters and not remote_waiters:
                break
            with TraceAnnotation("protocol.step",
                                 remote=len(remote_waiters),
                                 local=len(local_waiters)):
                replies: List[Tuple[_LiveTask, Any]] = []
                if remote_waiters:
                    with TraceAnnotation("protocol.remote",
                                         tasks=len(remote_waiters)):
                        replies += self._service_remote(remote_waiters)
                if local_waiters:
                    with TraceAnnotation("protocol.local",
                                         tasks=len(local_waiters)):
                        replies += self._service_local(local_waiters)
                # meters were updated during servicing; only now resume
                # the generators (so a task resumed early can't see a
                # step's drain half-dispatched)
                for t, value in replies:
                    t.pending = None
                    if isinstance(value, _Throw):
                        t.faults += 1
                        self.faults_delivered += 1
                        t.advance(value.exc, throw=True)
                    else:
                        if isinstance(value, RemoteFailure):
                            t.faults += 1
                            self.faults_delivered += 1
                            self.degradations += 1
                        t.advance(value)
        return [t.result for t in tasks]

    def run_one(self, protocol, context: str, query: str,
                cfg=None) -> ProtocolResult:
        """Single-task convenience (the compatibility wrappers' engine)."""
        return self.run([TaskSpec(protocol, context, query, cfg)])[0]

    # ------------------------------------------------------------------
    def _service_remote(self, waiters: List[_LiveTask]):
        """One batched remote request per (temperature, max_tokens) class
        across all waiting tasks; meter each completion into its task.

        Outcomes are per-prompt (``complete_outcomes_any``): a prompt
        whose call failed yields an Exception in its slot, which becomes
        a ``gen.throw`` into that task only — or a :class:`RemoteFailure`
        resume value if its RemoteCall carried ``fallback="degrade"``.
        Sibling tasks in the same batch are untouched."""
        if self.remote is None:
            raise RuntimeError("protocol yielded RemoteCall but the runner "
                               "has no remote client")
        groups: Dict[Tuple[float, int], List[int]] = {}
        for i, t in enumerate(waiters):
            a = t.pending
            groups.setdefault((a.temperature, a.max_tokens), []).append(i)
        outs: List[Any] = [None] * len(waiters)
        for (temp, mt), idxs in groups.items():
            results = complete_outcomes_any(
                self.remote, [waiters[i].pending.prompt for i in idxs],
                temperature=temp, max_tokens=mt)
            for i, res in zip(idxs, results):
                outs[i] = res
        replies: List[Tuple[_LiveTask, Any]] = []
        for t, res in zip(waiters, outs):
            if isinstance(res, Exception):
                if t.pending.fallback == "degrade":
                    replies.append((t, RemoteFailure(res)))
                else:
                    replies.append((t, _Throw(res)))
            else:
                t.ctx.remote_meter.record(t.pending.prompt, res)
                replies.append((t, res))
        return replies

    def _service_local(self, waiters: List[_LiveTask]):
        """Merge every task's LocalBatch into ONE shared scheduler drain.

        Each prompt is submitted with a ``(task_id, job_index)`` PRNG
        identity (the scheduler folds in the sample index), so a job's
        stochastic stream is a function of its own identity — not of
        which sibling tasks share the drain."""
        if self.scheduler is None:
            raise RuntimeError("protocol yielded LocalBatch but the runner "
                               "has no local client/scheduler")
        tickets: List[List[int]] = []
        for t in waiters:
            a = t.pending
            ids = []
            for prompt in a.prompts:
                ids.append(self.scheduler.submit(
                    prompt, samples=a.samples, temperature=a.temperature,
                    max_new_tokens=a.max_tokens,
                    rng_id=(t.ctx.task_id, t.next_job)))
                t.next_job += 1
            tickets.append(ids)
        try:
            drained = self.scheduler.drain(seed=self.seed)
        except Exception as e:             # noqa: BLE001 — isolation wall
            # a wholesale drain failure (engine crash) fails every waiter
            # as a task, not the run
            return [(t, _Throw(e)) for t in waiters]
        by_job: Dict[int, List[str]] = {}
        errors: Dict[int, Exception] = {}
        for r in drained:
            if getattr(r, "error", None) is not None:
                errors.setdefault(r.job_index, r.error)
            else:
                by_job.setdefault(r.job_index, []).append(r.text)
        replies = []
        for t, ids in zip(waiters, tickets):
            a = t.pending
            texts: List[str] = []
            err: Optional[Exception] = None
            for prompt, ji in zip(a.prompts, ids):
                if err is None and ji in errors:
                    err = errors[ji]
                for text in by_job.get(ji, []):
                    t.ctx.local_meter.record(prompt, text)
                    texts.append(text)
            # a failed row poisons only its owner's batch: the task gets
            # the first failure thrown at its yield, siblings their texts
            replies.append((t, _Throw(err)) if err is not None
                           else (t, texts))
        return replies


# --------------------------------------------------------------------------
# module-level convenience
# --------------------------------------------------------------------------


def run_protocol(protocol, *, local=None, remote=None, context: str,
                 query: str, cfg=None, **runner_kw) -> ProtocolResult:
    """Build a one-task runner and run ``protocol`` to completion —
    the engine behind the ``run_*`` compatibility wrappers."""
    return ProtocolRunner(local, remote, **runner_kw).run_one(
        protocol, context, query, cfg)
