"""Resumable protocol runtime: action-stream protocols, the concurrent
ProtocolRunner over one shared serve pool, stable PRNG identities, and
uniform UsageMeter accounting."""
import sys
from pathlib import Path

import jax
import pytest

from repro.core import (MinionSConfig, ProtocolRunner, TaskSpec, Usage,
                        run_minions)
from repro.core.clients import UsageMeter
from repro.core.runtime import Final, LocalBatch, RemoteCall, get_protocol
from repro.core.simulated import ScriptedRemote, SimulatedLocal
from repro.core.tasks import make_dataset
from repro.serving import JobScheduler

LOCAL = SimulatedLocal("llama-8b", seed=0)
REMOTE = ScriptedRemote(seed=0)


# ---------------------------------------------------------------------------
# concurrent runner == serial wrappers, with cross-task batching
# ---------------------------------------------------------------------------


def test_concurrent_runner_matches_serial_with_fewer_drains():
    """8 tasks run concurrently must produce answers, usage and round
    records identical to serial run_minions — while all tasks' worker
    jobs share drains: strictly fewer drains than task-serial execution
    over the same shared scheduler."""
    tasks = make_dataset(8, seed=11, n_pages=30)
    cfg = MinionSConfig()

    serial = [run_minions(LOCAL, REMOTE, t.context, t.query, cfg)
              for t in tasks]

    # serial over ONE shared persistent pool (what a sweep used to do)
    serial_runner = ProtocolRunner(LOCAL, REMOTE)
    for t in tasks:
        serial_runner.run([TaskSpec("minions", t.context, t.query, cfg)])
    serial_drains = serial_runner.scheduler.drains

    conc_runner = ProtocolRunner(LOCAL, REMOTE)
    conc = conc_runner.run([TaskSpec("minions", t.context, t.query, cfg)
                            for t in tasks])

    for s, c in zip(serial, conc):
        assert c.answer == s.answer
        assert c.remote_usage == s.remote_usage
        assert c.local_prefill_tokens == s.local_prefill_tokens
        assert c.local_decode_tokens == s.local_decode_tokens
        assert c.rounds == s.rounds
        assert c.transcript == s.transcript
    assert conc_runner.scheduler.drains < serial_drains
    assert conc_runner.scheduler.jobs_drained == \
        serial_runner.scheduler.jobs_drained


def test_mixed_protocols_share_one_runner():
    """Different protocols interleave in one run: each task's result
    matches its own single-task execution."""
    tasks = make_dataset(3, seed=5, n_pages=10)
    specs = [TaskSpec("minions", tasks[0].context, tasks[0].query),
             TaskSpec("remote_only", tasks[1].context, tasks[1].query),
             TaskSpec("local_only", tasks[2].context, tasks[2].query)]
    conc = ProtocolRunner(LOCAL, REMOTE).run(specs)
    for spec, got in zip(specs, conc):
        solo = ProtocolRunner(LOCAL, REMOTE).run_one(
            spec.protocol, spec.context, spec.query, spec.cfg)
        assert got.answer == solo.answer
        assert got.remote_usage == solo.remote_usage


def test_wrapper_equals_explicit_runner():
    t = make_dataset(1, seed=3, n_pages=10)[0]
    a = run_minions(LOCAL, REMOTE, t.context, t.query, MinionSConfig())
    b = ProtocolRunner(LOCAL, REMOTE).run_one(
        "minions", t.context, t.query, MinionSConfig())
    assert a.answer == b.answer and a.remote_usage == b.remote_usage


# ---------------------------------------------------------------------------
# action-stream mechanics
# ---------------------------------------------------------------------------


def test_registry_resolves_all_builtins():
    for name in ("minion", "minions", "remote_only", "local_only", "rag"):
        assert callable(get_protocol(name))
    with pytest.raises(KeyError):
        get_protocol("nope")


def test_protocol_without_final_yields_none_answer():
    def bare(task):
        _ = yield RemoteCall("hello", max_tokens=4)
        # falls off the end without Final

    r = ProtocolRunner(None, REMOTE).run_one(bare, "ctx", "q")
    assert r.answer is None
    assert r.remote_usage.prefill_tokens > 0      # the call was metered


def test_runner_errors_without_needed_client():
    def wants_local(task):
        yield LocalBatch(["p"])

    with pytest.raises(RuntimeError):
        ProtocolRunner(None, REMOTE).run_one(wants_local, "c", "q")

    def wants_remote(task):
        yield RemoteCall("p")

    with pytest.raises(RuntimeError):
        ProtocolRunner(LOCAL, None).run_one(wants_remote, "c", "q")


def test_local_batch_samples_expand_and_meter():
    """samples=k returns k replicas per prompt in (prompt, sample) order
    and meters every replica's prefill."""
    seen = {}

    def proto(task):
        outs = yield LocalBatch(["alpha", "beta"], samples=3, max_tokens=8)
        seen["outs"] = outs
        yield Final("done")

    r = ProtocolRunner(LOCAL, None).run_one(proto, "c", "q")
    assert len(seen["outs"]) == 6
    assert r.local_prefill_tokens > 0
    from repro.serving.tokenizer import approx_tokens
    assert r.local_prefill_tokens == 3 * (approx_tokens("alpha")
                                          + approx_tokens("beta"))


# ---------------------------------------------------------------------------
# stable PRNG identities (grouped path bugfix)
# ---------------------------------------------------------------------------


def _recording_generate(log):
    def fn(prompts, temperature=0.0, key=None, max_new_tokens=0):
        for p in prompts:
            log[p] = (tuple(int(x) for x in jax.device_get(key)),
                      temperature, max_new_tokens)
        return ["" for _ in prompts]
    return fn


def test_grouped_drain_key_independent_of_coexisting_classes():
    """Regression (PRNG split order): a stochastic batch's key must be a
    function of its members' identities, not of which OTHER param classes
    happen to share the drain (the old code split the base key once per
    group in dict-iteration order)."""
    stoch = [(f"stoch {i} " + "z" * i, (7, i)) for i in range(3)]

    def run(extra):
        log = {}
        sched = JobScheduler(_recording_generate(log), max_batch=4)
        for prompt, temp, rid in extra:
            sched.submit(prompt, temperature=temp, max_new_tokens=4,
                         rng_id=rid)
        for prompt, rid in stoch:
            sched.submit(prompt, temperature=0.9, max_new_tokens=4,
                         rng_id=rid)
        sched.drain(seed=0)
        return {p: log[p] for p, _ in stoch}

    alone = run([])
    with_greedy = run([("greedy filler", 0.0, (1, 0))])
    with_hot = run([("hot filler", 0.7, (2, 0)), ("hot 2", 0.7, (2, 1))])
    assert alone == with_greedy == with_hot


def test_grouped_drain_submission_order_invariance():
    """With caller-stable rng_ids and distinct prompt lengths, the keys
    each batch runs under are invariant to submission interleaving."""
    jobs = [(f"job {i} " + "y" * (3 * i), 0.9, (4, i)) for i in range(5)] \
        + [("greedy " + "g" * 9, 0.0, (5, 0))]

    def run(order):
        log = {}
        sched = JobScheduler(_recording_generate(log), max_batch=2)
        for idx in order:
            prompt, temp, rid = jobs[idx]
            sched.submit(prompt, temperature=temp, max_new_tokens=4,
                         rng_id=rid)
        sched.drain(seed=0)
        return log

    base = run(range(len(jobs)))
    assert run([5, 4, 3, 2, 1, 0]) == base
    assert run([2, 5, 0, 3, 1, 4]) == base


def test_replica_lanes_match_scalar_reference():
    """The vectorized drain lane derivation must equal the scalar
    job_lane reference fold chain, across mixed identity arities and
    sample indices — the two must never diverge."""
    import jax.numpy as jnp
    from repro.serving.scheduler import _Pending, _replica_lanes, job_lane
    key = jax.random.PRNGKey(3)
    expanded = [(ji, si, _Pending(ji, "p", 1, 0.9, 4, rid))
                for ji, (rid, samples) in enumerate(
                    [((3, 1), 2), ((7,), 1), ((0, 5, 2), 3)])
                for si in range(samples)]
    vec = _replica_lanes(key, expanded)
    ref = jnp.stack([job_lane(key, p.rng_id, si)
                     for _, si, p in expanded])
    assert (vec == ref).all()


def test_runner_rejects_duplicate_task_ids():
    with pytest.raises(ValueError, match="duplicate task_id"):
        ProtocolRunner(LOCAL, REMOTE).run(
            [TaskSpec("local_only", "c", "q", task_id=1),
             TaskSpec("local_only", "c", "q")])      # default id 1 collides


def test_submit_rejects_colliding_identity_without_wedging_queue():
    """A replica whose (rng_id, sample) lane is already queued is rejected
    AT SUBMIT (correlated samples are always identity misuse) and never
    enqueued — the queue stays valid and drains normally, and identities
    free up again after the drain."""
    sched = JobScheduler(lambda ps, **kw: ["" for _ in ps], max_batch=4)
    sched.submit("a", temperature=0.9)                 # default id (0,)
    with pytest.raises(ValueError, match="PRNG identity"):
        sched.submit("b", temperature=0.9, rng_id=0)   # collides with (0,)
    sched.submit("b", temperature=0.9, rng_id=(1, 0))  # fixed id queues fine
    assert len(sched.drain(seed=0)) == 2
    sched.submit("c", temperature=0.9, rng_id=0)       # fresh queue: ok now
    assert len(sched.drain(seed=0)) == 1


def test_runner_inherits_seed_from_local_client():
    """A seeded client (EngineClient carries .seed) keeps its sampling
    seed when wrapped by a runner; an explicit runner seed overrides."""
    class _Seeded:
        name, seed = "seeded", 7

        def complete_batch(self, prompts, **kw):
            return ["" for _ in prompts]

    assert ProtocolRunner(_Seeded(), None).seed == 7
    assert ProtocolRunner(_Seeded(), None, seed=3).seed == 3
    assert ProtocolRunner(None, REMOTE).seed == 0


def test_default_rng_id_is_queue_position():
    """Without explicit rng_ids the per-job identity defaults to the
    submission index — single-caller behaviour stays deterministic."""
    log1, log2 = {}, {}
    for log in (log1, log2):
        sched = JobScheduler(_recording_generate(log), max_batch=8)
        sched.submit("a", temperature=0.9, max_new_tokens=4)
        sched.submit("bb", temperature=0.9, max_new_tokens=4)
        sched.drain(seed=0)
    assert log1 == log2


# ---------------------------------------------------------------------------
# UsageMeter: free mode, record(), nesting regression
# ---------------------------------------------------------------------------


class _NoBatchClient:
    name = "nobatch"

    def complete(self, prompt, *, temperature=0.0, max_tokens=256):
        return prompt[::-1]


def test_usage_meter_nested_meters_count_once_each():
    """Regression: a meter wrapping another meter (whose client lacks
    complete_batch) must meter each prompt exactly once at EACH level —
    the per-prompt fallback goes through the wrapped client, never the
    outer metered ``complete``."""
    inner = UsageMeter(_NoBatchClient())
    outer = UsageMeter(inner)
    assert outer.nested and not inner.nested
    prompts = ["one", "two two", "three three three"]
    outs = outer.complete_batch(prompts, max_tokens=8)
    assert outs == [p[::-1] for p in prompts]
    assert len(inner.calls) == len(prompts)
    assert len(outer.calls) == len(prompts)
    assert inner.usage == outer.usage


def test_usage_meter_free_and_record():
    m = UsageMeter(free=True)
    assert m.free and m.name == "unmetered"
    m.record("abcd", "efgh")
    assert m.usage.prefill_tokens > 0
    assert m.usage == Usage(m.usage.prefill_tokens, m.usage.decode_tokens)
    assert len(m.calls) == 1


# ---------------------------------------------------------------------------
# fast benchmark variant: cross-task batching on a REAL engine pool
# (the smoke-set observable for the EngineUsage counters)
# ---------------------------------------------------------------------------


def test_protocol_bench_fast_variant_cross_task_batching():
    """``benchmarks/run.py:protocol_scenario`` in miniature: 3 MinionS
    tasks over one real engine pool — concurrent execution serves the
    same jobs in strictly fewer drains AND fewer engine serve calls
    (EngineUsage), with identical answers; slot occupancy comes from the
    decode loop's own step count (``EngineUsage.decode_steps``)."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        from benchmarks.run import protocol_scenario
    finally:
        sys.path.pop(0)
    res = protocol_scenario(3, n_pages=1, worker_max_tokens=4,
                            max_seq_len=1024, warm=False)
    assert res["concurrent"]["drains"] < res["serial"]["drains"]
    assert res["concurrent"]["engine_serve_calls"] < \
        res["serial"]["engine_serve_calls"]
    assert res["answers_identical"]
    assert 0.0 < res["concurrent"]["slot_occupancy"] <= 1.0
