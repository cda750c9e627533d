"""Fleet gateway battery: queue/routing properties, LRU response cache,
backpressure, the JobScheduler facade, and seeded replica-kill chaos.

Everything here runs against cheap fake replicas (plain generate
callables — no JAX compile), so the battery stays in the smoke loop; the
token-identity cells against real engines live in
tests/test_equivalence.py (`fleet` cells).
"""
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import CircuitBreaker, ProtocolRunner, TaskSpec
from repro.core.runtime import Final, LocalBatch
from repro.serving import (EnginePool, FleetUsage, GatewayQueue,
                           JobScheduler, LRUCache, NoHealthyReplica,
                           PoolSaturated, Replica, ReplicaSnapshot,
                           route_job)
from repro.serving.fleet import _QueuedJob

FROZEN = staticmethod(lambda: 0.0)      # deterministic clock for pools


def qjob(ji, priority=0, seq=None, prompt="p", samples=1):
    return _QueuedJob(ji, priority, seq if seq is not None else ji,
                      prompt, samples, 0.0, 8, (ji,))


def echo_gen(tag="e", log=None):
    def gen(prompts, temperature=0.0, key=None, max_new_tokens=128):
        if log is not None:
            log.append(list(prompts))
        return [f"{tag}:{p}" for p in prompts]
    return gen


class SeededKill:
    """FaultyClient-style seeded drain fault: kills the replica on the
    drain indices scheduled by (seed, drain index) — same seed, same
    kills, so chaos reruns are bit-identical."""

    def __init__(self, seed, rate=0.5, n=64):
        import random
        rng = random.Random(seed)
        self.kills = {i for i in range(n) if rng.random() < rate}

    def __call__(self, drain_index):
        if drain_index in self.kills:
            raise RuntimeError(f"replica killed at drain {drain_index}")


# ===========================================================================
# gateway queue: priority ordering, FIFO, bounded-bypass no-starvation
# ===========================================================================


@given(st.lists(st.integers(0, 3), max_size=40))
@settings(max_examples=50)
def test_queue_priority_order_and_fifo_within_class(priorities):
    """With no interleaved arrivals, pop order is exactly sorted by
    (priority, arrival) — priority classes in order, FIFO within."""
    q = GatewayQueue(max_bypass=10**9)
    for i, p in enumerate(priorities):
        q.push(qjob(i, priority=p, seq=i))
    popped = []
    while len(q):
        popped.append(q.pop())
    assert [(j.priority, j.seq) for j in popped] == \
        sorted((p, i) for i, p in enumerate(priorities))


@given(st.lists(st.tuples(st.booleans(), st.integers(0, 3)),
                min_size=1, max_size=60),
       st.integers(1, 6))
@settings(max_examples=50)
def test_queue_no_starvation_bounded_bypass(schedule, max_bypass):
    """Arbitrary interleavings of pushes and pops: NO popped job was ever
    overtaken more than max_bypass times — sustained higher-priority
    arrivals cannot starve a queued job indefinitely."""
    q = GatewayQueue(max_bypass=max_bypass)
    seq = 0
    for is_pop, priority in schedule:
        if is_pop:
            j = q.pop()
            if j is not None:
                assert j.bypassed <= max_bypass
        else:
            q.push(qjob(seq, priority=priority, seq=seq))
            seq += 1
    while len(q):
        assert q.pop().bypassed <= max_bypass


def test_queue_overdue_job_preempts_fresh_high_priority():
    """Concrete starvation scenario: one low-priority job vs a sustained
    stream of high-priority arrivals.  It must dispatch after at most
    max_bypass bypasses, ahead of fresher priority-0 work."""
    q = GatewayQueue(max_bypass=3)
    q.push(qjob(0, priority=5, seq=0))          # the would-be starved job
    seq, popped_at = 1, None
    for step in range(20):
        q.push(qjob(seq, priority=0, seq=seq))
        seq += 1
        j = q.pop()
        if j.priority == 5:
            popped_at = step
            break
    assert popped_at is not None and popped_at == 3


def test_queue_bounded_push_rejects():
    q = GatewayQueue(max_queue=2)
    assert q.push(qjob(0)) and q.push(qjob(1))
    assert not q.push(qjob(2))
    assert len(q) == 2


# ===========================================================================
# routing: pure function of (depths, health, cost weights)
# ===========================================================================


@given(st.lists(st.tuples(st.booleans(), st.integers(0, 512),
                          st.floats(1.0, 500.0), st.floats(0.1, 10.0)),
                min_size=1, max_size=8),
       st.integers(1, 256), st.floats(0.0, 2.0))
@settings(max_examples=80)
def test_routing_pure_and_argmin(reps, job_tokens, cost_weight):
    """Same snapshots -> same decision; the decision is healthy and is
    the score argmin (ties to the lowest index)."""
    snaps = [ReplicaSnapshot(i, h, d, t, c)
             for i, (h, d, t, c) in enumerate(reps)]
    if not any(s.healthy for s in snaps):
        with pytest.raises(NoHealthyReplica):
            route_job(snaps, job_tokens, cost_weight=cost_weight)
        return
    pick = route_job(snaps, job_tokens, cost_weight=cost_weight)
    assert pick == route_job(snaps, job_tokens, cost_weight=cost_weight)
    assert snaps[pick].healthy

    def score(s):
        return ((s.depth_tokens + job_tokens) / max(s.tok_per_s, 1e-9)
                + cost_weight * s.cost_per_token * job_tokens)
    best = min(((score(s), s.index) for s in snaps if s.healthy))
    assert pick == best[1]


def test_routing_prefers_cheap_tier_until_loaded():
    """The paper's local/remote tradeoff as a serving knob: cost routing
    keeps jobs on the cheap tier when idle, and spills to the expensive
    tier once the cheap queue's eta outweighs the cost gap."""
    def snaps(depth0):
        return [ReplicaSnapshot(0, True, depth0, 100.0, 1.0),   # local
                ReplicaSnapshot(1, True, 0, 100.0, 3.0)]        # remote
    assert route_job(snaps(0), 8, cost_weight=0.01) == 0
    assert route_job(snaps(100_000), 8, cost_weight=0.01) == 1
    # without the cost term the idle expensive replica wins immediately
    assert route_job(snaps(64), 8, cost_weight=0.0) == 1


def test_routing_skips_unhealthy():
    snaps = [ReplicaSnapshot(0, False, 0, 100.0, 1.0),
             ReplicaSnapshot(1, True, 10_000, 100.0, 9.0)]
    assert route_job(snaps, 8, cost_weight=1.0) == 1
    with pytest.raises(NoHealthyReplica):
        route_job([snaps[0]], 8)


def test_homogeneous_pool_spreads_load():
    pool = EnginePool([Replica(echo_gen()), Replica(echo_gen())],
                      route_by_cost=False, clock=FROZEN)
    for i in range(8):
        pool.submit(f"p{i}", temperature=0.0, max_new_tokens=8)
    res = pool.drain(seed=0)
    assert [r.error for r in res] == [None] * 8
    assert all(r.served_jobs == 4 for r in pool.replicas)
    routed = {e[2] for e in pool.usage.events if e[0] == "route"}
    assert routed == {0, 1}


# ===========================================================================
# LRU response cache
# ===========================================================================


def test_lru_capacity_eviction_order():
    evicted = []
    c = LRUCache(3, on_evict=lambda: evicted.append(1))
    for k in "abc":
        c.put(k, k.upper())
    assert c.get("a") == "A"          # refresh: a is now most recent
    c.put("d", "D")                   # evicts b (LRU), not a
    assert c.keys() == ["c", "a", "d"]
    assert c.get("b") is None and len(evicted) == 1
    c.put("e", "E")                   # evicts c
    assert c.keys() == ["a", "d", "e"]


def test_cache_hit_costs_zero_engine_calls():
    log = []
    pool = EnginePool([Replica(echo_gen(log=log))], clock=FROZEN)
    pool.submit("what is 2+2", temperature=0.0, max_new_tokens=8)
    first = pool.drain(seed=0)
    assert len(log) == 1 and pool.usage.cache_misses == 1
    pool.submit("what is 2+2", temperature=0.0, max_new_tokens=8)
    second = pool.drain(seed=0)
    # served from cache: no new calls reached the replica target
    assert len(log) == 1
    assert pool.usage.cache_hits == 1
    assert second[0].text == first[0].text
    assert pool.replicas[0].scheduler.drains == 1


def test_stochastic_requests_never_cache_served():
    log = []
    pool = EnginePool([Replica(echo_gen(log=log))], clock=FROZEN)
    for _ in range(2):
        pool.submit("sample me", temperature=0.9, max_new_tokens=8)
        pool.drain(seed=0)
    assert len(log) == 2                      # both hit the replica
    assert pool.usage.cache_hits == 0
    assert pool.usage.cache_misses == 0       # never even looked up
    assert pool.usage.cache_bypass == 2
    assert len(pool.cache) == 0               # and never cached
    # a deterministic twin of the same prompt is NOT served by anything
    # the stochastic runs produced
    pool.submit("sample me", temperature=0.0, max_new_tokens=8)
    pool.drain(seed=0)
    assert pool.usage.cache_hits == 0 and pool.usage.cache_misses == 1


def test_cache_key_includes_sampling_params():
    log = []
    pool = EnginePool([Replica(echo_gen(log=log))], clock=FROZEN)
    pool.submit("p", temperature=0.0, max_new_tokens=8)
    pool.drain(seed=0)
    pool.submit("p", temperature=0.0, max_new_tokens=16)   # different budget
    pool.drain(seed=0)
    assert pool.usage.cache_hits == 0 and len(log) == 2


def test_pool_eviction_accounting():
    pool = EnginePool([Replica(echo_gen())], cache_size=2, clock=FROZEN)
    for i in range(3):
        pool.submit(f"p{i}", temperature=0.0, max_new_tokens=8)
    pool.drain(seed=0)
    assert len(pool.cache) == 2
    assert pool.usage.cache_evictions == 1    # p0 evicted by p2


def test_fleet_usage_cumulative_and_reset():
    """FleetUsage counters are cumulative across drains (EngineUsage
    semantics) and reset() zeroes every field — regression-tested so
    later refactors keep the contract."""
    pool = EnginePool([Replica(echo_gen())], clock=FROZEN)
    for round_ in range(2):
        pool.submit("same prompt", temperature=0.0, max_new_tokens=8)
        pool.drain(seed=0)
    assert pool.usage.drains == 2 and pool.usage.submitted == 2
    assert pool.usage.cache_misses == 1 and pool.usage.cache_hits == 1
    assert pool.usage.events
    pool.usage.reset()
    assert pool.usage == FleetUsage()


# ===========================================================================
# backpressure: queued/rejected instead of unbounded growth
# ===========================================================================


def test_scheduler_submit_backpressure_regression():
    """JobScheduler with a bounded queue surfaces saturation instead of
    growing without limit; draining frees the capacity."""
    sched = JobScheduler(echo_gen(), max_batch=4, max_queue=2)
    assert sched.submit("a") == 0 and sched.submit("b") == 1
    with pytest.raises(PoolSaturated):
        sched.submit("c")
    assert sched.try_submit("c") == ("rejected", None)
    assert len(sched.drain(seed=0)) == 2      # rejected job was NOT queued
    outcome, ji = sched.try_submit("d")
    assert outcome == "queued" and ji == 0


def test_scheduler_default_queue_stays_unbounded():
    sched = JobScheduler(echo_gen(), max_batch=2)
    for i in range(64):
        sched.submit(f"p{i}")
    assert len(sched.drain(seed=0)) == 64


def test_pool_admission_rejects_and_counts():
    pool = EnginePool([Replica(echo_gen())], max_queue=2, clock=FROZEN)
    pool.submit("a"), pool.submit("b")
    with pytest.raises(PoolSaturated):
        pool.submit("c")
    assert pool.try_submit("c") == ("rejected", None)
    assert pool.usage.rejected == 2
    assert [e for e in pool.usage.events if e[0] == "reject"]
    res = pool.drain(seed=0)
    assert [r.job_index for r in res] == [0, 1]
    assert pool.try_submit("c")[0] == "queued"   # drain freed capacity


# ===========================================================================
# scheduler facade: submission order, samples, identities, streaming
# ===========================================================================


def test_drain_submission_order_with_samples_and_priorities():
    """Results come back in submission order (job_index, sample_index)
    regardless of priority-reordered dispatch — the JobScheduler facade
    contract the ProtocolRunner relies on."""
    pool = EnginePool([Replica(echo_gen()), Replica(echo_gen())],
                      route_by_cost=False, clock=FROZEN)
    pool.submit("low", temperature=0.0, priority=9)
    pool.submit("high", temperature=0.0, samples=2, priority=0)
    res = pool.drain(seed=0)
    assert [(r.job_index, r.sample_index) for r in res] == \
        [(0, 0), (1, 0), (1, 1)]
    assert res[0].text.endswith("low")


def test_duplicate_rng_identity_rejected():
    pool = EnginePool([Replica(echo_gen())], clock=FROZEN)
    pool.submit("a", rng_id=(3, 1))
    with pytest.raises(ValueError):
        pool.submit("b", rng_id=(3, 1))
    # queue still valid: resubmitting with a fixed identity works
    pool.submit("b", rng_id=(3, 2))
    assert len(pool.drain(seed=0)) == 2


def test_stream_yields_everything_drain_returns():
    pool = EnginePool([Replica(echo_gen()), Replica(echo_gen())],
                      route_by_cost=False, clock=FROZEN)
    jobs = [pool.submit(f"p{i}", temperature=0.0, samples=1 + i % 2)
            for i in range(5)]
    streamed = {(r.job_index, r.sample_index, r.text)
                for r in pool.stream(seed=0)}
    for i in range(5):
        pool.submit(f"p{i}", temperature=0.9, samples=1 + i % 2,
                    rng_id=(100 + i,))
    drained = {(r.job_index, r.sample_index) for r in pool.drain(seed=0)}
    assert len(streamed) == 7 and len(drained) == 7
    assert jobs == list(range(5))


def test_runner_spreads_local_batches_across_fleet():
    """One ProtocolRunner over an EnginePool: LocalBatch drains spread
    across replicas, results land with the right tasks, counters track
    gateway drains."""
    def proto(ctx):
        texts = yield LocalBatch(prompts=[f"t{ctx.task_id}-a",
                                          f"t{ctx.task_id}-b"],
                                 temperature=0.0, max_tokens=8)
        yield Final(answer="|".join(texts))

    pool = EnginePool([Replica(echo_gen("r0")), Replica(echo_gen("r1"))],
                      route_by_cost=False, clock=FROZEN)
    runner = ProtocolRunner(pool)
    assert runner.scheduler is pool           # the facade IS the pool
    results = runner.run([TaskSpec(proto, "", "", task_id=i)
                          for i in range(4)])
    for i, r in enumerate(results):
        assert r.status == "ok"
        parts = r.answer.split("|")
        assert [p.split(":", 1)[1] for p in parts] == \
            [f"t{i}-a", f"t{i}-b"]
    assert pool.drains == 1 and pool.jobs_drained == 8
    assert all(rep.served_jobs > 0 for rep in pool.replicas)


# ===========================================================================
# chaos: seeded replica kill mid-drain (marker: chaos, `make chaos`)
# ===========================================================================


def _chaos_pool(seed=13):
    return EnginePool(
        [Replica(echo_gen("healthy"), name="healthy"),
         Replica(echo_gen("victim"), name="victim",
                 fault=SeededKill(seed, rate=1.0, n=1))],
        route_by_cost=False, clock=FROZEN)


def _chaos_run(seed=13):
    def proto(ctx):
        texts = yield LocalBatch(prompts=[f"t{ctx.task_id} job"],
                                 temperature=0.0, max_tokens=8)
        yield Final(answer=texts[0])

    pool = _chaos_pool(seed)
    runner = ProtocolRunner(pool)
    results = runner.run([TaskSpec(proto, "", "", task_id=i)
                          for i in range(4)])
    fingerprint = tuple((r.status, r.answer) for r in results)
    return pool, fingerprint


@pytest.mark.chaos
def test_replica_kill_mid_drain_requeues_and_opens_breaker():
    """Kill one replica on its first drain: its breaker opens, the
    in-flight jobs are re-queued to the healthy replica, and every
    sibling task still finishes ok."""
    pool, fingerprint = _chaos_run()
    assert all(status == "ok" for status, _ in fingerprint)
    victim, healthy = pool.replicas[1], pool.replicas[0]
    assert victim.stats.state == "open"
    assert victim.stats.breaker_opens == 1
    assert pool.usage.replica_failures == 1
    assert pool.usage.requeues > 0
    # the requeued jobs were served by the healthy replica
    assert all(a.startswith("healthy:") for _, a in fingerprint)
    assert healthy.served_jobs == 4 and victim.served_jobs == 0


@pytest.mark.chaos
def test_replica_kill_rerun_bit_identical():
    """Same seed, same kills, same routing state: the rerun reproduces
    answers, statuses and fleet counters exactly."""
    pool_a, fp_a = _chaos_run(seed=13)
    pool_b, fp_b = _chaos_run(seed=13)
    assert fp_a == fp_b
    assert pool_a.usage == pool_b.usage
    assert [r.stats for r in pool_a.replicas] == \
        [r.stats for r in pool_b.replicas]


@pytest.mark.chaos
def test_breaker_cooldown_half_open_probe_recovers():
    """After the cooldown (counted in gateway drains), the victim goes
    half-open, serves a probe batch successfully, and closes."""
    pool = EnginePool(
        [Replica(echo_gen("healthy"), name="healthy"),
         Replica(echo_gen("victim"), name="victim",
                 fault=SeededKill(0, rate=1.0, n=1),
                 breaker_cooldown=2)],
        route_by_cost=False, clock=FROZEN)
    pool.run(["a", "b"], temperature=0.0)     # drain 1: kill -> open
    victim = pool.replicas[1]
    assert victim.stats.state == "open"
    pool.run(["c", "d"], temperature=0.0)     # drain 2: cooldown ticks
    pool.run(["e", "f"], temperature=0.0)     # drain 3: half-open probe
    assert victim.stats.state == "closed"
    assert victim.served_jobs > 0


@pytest.mark.chaos
def test_all_replicas_down_surfaces_errors_not_hang():
    pool = EnginePool(
        [Replica(echo_gen(), fault=SeededKill(0, rate=1.0))],
        route_by_cost=False, clock=FROZEN, max_requeues=2)
    pool.submit("doomed", temperature=0.0)
    res = pool.drain(seed=0)
    assert len(res) == 1 and res[0].error is not None
    # the runner turns those error rows into a failed task, siblings safe
    def proto(ctx):
        texts = yield LocalBatch(prompts=["x"], temperature=0.0)
        yield Final(answer=texts[0])
    runner = ProtocolRunner(EnginePool(
        [Replica(echo_gen(), fault=SeededKill(0, rate=1.0))],
        clock=FROZEN, max_requeues=1))
    out = runner.run([TaskSpec(proto, "", "")])
    assert out[0].status == "failed"


# ===========================================================================
# breaker state machine reuse (the core/clients.py machine, per replica)
# ===========================================================================


def test_circuit_breaker_machine_shared_semantics():
    """The fleet's per-replica breaker is the SAME machine
    ResilientClient runs: threshold consecutive failures open it,
    cooldown admissions later a half-open probe closes on success."""
    b = CircuitBreaker(threshold=2, cooldown=2)
    b.on_failure()
    assert b.state == "closed"
    b.on_failure()
    assert b.state == "open" and b.stats.breaker_opens == 1
    assert not b.admit()              # cooldown 1
    assert b.admit()                  # cooldown spent -> half-open probe
    assert b.state == "half_open"
    b.on_failure()                    # failed probe reopens
    assert b.state == "open" and b.stats.breaker_opens == 2
    assert not b.admit() and b.admit()
    b.on_success()
    assert b.state == "closed" and b.stats.consecutive_failures == 0
