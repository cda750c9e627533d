"""Serving launcher: batched generation with the inference engine.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        [--checkpoint out/ckpt.npz] --prompts "hello" "world"

``--smoke`` (the default) serves the family's reduced smoke config;
``--no-smoke`` serves the registry config at its published widths and
vocabulary (random weights from ``--seed`` unless ``--checkpoint``).

Mesh-sharded serving: ``--sharded`` builds the host mesh
(:func:`repro.launch.mesh.make_host_mesh`) and shards the engine over it
(``--model-parallel N`` splits attention heads over a "model" axis; the
remaining devices form the "data" axis that decode rows shard over).  Run
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to smoke-test
an 8-device layout on CPU.  ``--serve`` routes the prompts through the
continuously-batched slot pool instead of one convoy ``generate_batch``.
``--paged`` (with ``--page-size`` / ``--num-pages``) switches the KV
cache to the shared page pool with radix prefix reuse — prompts sharing
an instruction prefix prefill only their novel suffix.

``--minions N`` runs N synthetic MinionS requests CONCURRENTLY through a
:class:`repro.core.ProtocolRunner` over this engine (simulated remote):
every runner step drains one shared slot-pool batch holding worker jobs
from all N requests — the full protocol tier on top of the LocalLM this
launcher builds.  Without it, the launcher stays the bare LocalLM side
and the protocol drivers in examples/ compose it with a remote client.

Fleet serving: ``--replicas N`` puts N engine replicas behind one
cost-routed :class:`repro.serving.EnginePool` gateway; each repeatable
``--replica-config "cost=3.0,paged,slots=8"`` spec customises one
replica (keys: ``cost`` per-token weight, ``paged``/``dense``,
``page_size``, ``num_pages``, ``slots``, ``arch``, ``name``), so a
cheap dense tier and a costly paged tier can serve one workload.
``--route-by-cost`` (with ``--cost-weight``) enables the routing score's
dollar term — the gateway keeps jobs on the cheap tier until its queue
eta outweighs the cost gap; off, routing is pure least-loaded.  With
``--minions`` the ProtocolRunner drives the whole fleet through the
pool's JobScheduler facade; otherwise the raw prompts are served
through the gateway.

Fault tolerance (with ``--minions``): ``--chaos RATE`` injects a seeded
fault schedule into the remote (:class:`repro.core.faults.FaultyClient` —
errors, stalls, malformed completions), and ``--remote-timeout`` /
``--retries`` wrap it in a :class:`repro.core.clients.ResilientClient`
(deadline, backoff retries, circuit breaker).  Per-task status
(ok/degraded/failed) and reliability counters are printed after the run.
"""
from __future__ import annotations

import argparse
import sys

import jax

from repro.configs import get_config, get_smoke_config, list_archs
from repro.launch import compile_cache
from repro.models import transformer as T
from repro.serving import InferenceEngine
from repro.training import load


def build_engine(arch: str, *, smoke: bool = True, checkpoint=None,
                 max_seq_len: int = 4096, seed: int = 0, mesh=None,
                 truncate_long: bool = False, paged: bool = False,
                 page_size: int = 64, num_pages: int = 512) -> InferenceEngine:
    """``mesh``: None (single device), a ``jax.sharding.Mesh``, or
    ``"auto"`` for the host mesh — passed straight through to the engine.
    ``truncate_long`` clips over-long prompts instead of raising (useful
    when protocol-generated worker chunks can exceed the window).
    ``paged`` switches the KV cache to the shared page pool with radix
    prefix reuse (``page_size`` tokens per page, ``num_pages`` total)."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    if checkpoint:
        params, meta = load(checkpoint, params)
        print(f"loaded checkpoint ({meta})")
    return InferenceEngine(cfg, params, max_seq_len=max_seq_len, mesh=mesh,
                           truncate_long=truncate_long, paged=paged,
                           page_size=page_size, num_pages=num_pages)


def minions_tasks(n: int):
    """The ``n`` synthetic MinionS requests that ``--minions n`` serves:
    one round, one task per round, one page per chunk (two worker jobs
    per request), 32-token worker answers."""
    from repro.core import MinionSConfig, TaskSpec
    from repro.core.tasks import make_task
    cfg = MinionSConfig(max_rounds=1, num_tasks_per_round=1,
                        pages_per_chunk=1, worker_max_tokens=32)
    return [TaskSpec("minions", t.context, t.query, cfg)
            for t in (make_task(700 + i, n_pages=2, kind="extract")
                      for i in range(n))]


def parse_replica_spec(spec: str) -> dict:
    """``"cost=3.0,paged,slots=8"`` -> {"cost": "3.0", "paged": True,
    "slots": "8"} — one ``--replica-config`` occurrence."""
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            k, v = part.split("=", 1)
            out[k.strip()] = v.strip()
        else:
            out[part] = True
    return out


def build_fleet(args, mesh):
    """Build the ``EnginePool`` for ``--replicas``/``--replica-config``:
    one engine per replica (spec keys override the base engine flags),
    wrapped with its cost weight behind the cost-routed gateway."""
    from repro.serving import EnginePool, Replica
    specs = [parse_replica_spec(s) for s in (args.replica_config or [])]
    while len(specs) < args.replicas:
        specs.append({})
    replicas = []
    for i, spec in enumerate(specs):
        paged = args.paged
        if spec.get("paged"):
            paged = True
        if spec.get("dense"):
            paged = False
        eng = build_engine(
            spec.get("arch", args.arch), smoke=args.smoke,
            checkpoint=args.checkpoint, seed=args.seed, mesh=mesh,
            truncate_long=bool(args.minions), paged=paged,
            page_size=int(spec.get("page_size", args.page_size)),
            num_pages=int(spec.get("num_pages", args.num_pages)))
        replicas.append(Replica(
            eng, name=spec.get("name", f"r{i}"),
            cost_per_token=float(spec.get("cost", 1.0)),
            max_batch=int(spec.get("slots", args.slots))))
    return EnginePool(replicas, route_by_cost=args.route_by_cost,
                      cost_weight=args.cost_weight)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=list_archs())
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced smoke config (--no-smoke: published "
                         "widths and vocabulary)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights (without "
                         "--checkpoint)")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--max-new-tokens", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.2)
    ap.add_argument("--sharded", action="store_true",
                    help="shard the engine over the local host mesh")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="'model' axis size of the host mesh (with "
                         "--sharded); must divide the device count")
    ap.add_argument("--serve", action="store_true",
                    help="continuously-batched slot pool instead of one "
                         "convoy generate_batch")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode rows in the serve pool (with --serve)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: fixed-size page pool + radix "
                         "prefix index, reusing shared prompt prefixes "
                         "across jobs and calls")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per KV page (with --paged)")
    ap.add_argument("--num-pages", type=int, default=512,
                    help="page-pool capacity in pages (with --paged)")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="serve through an EnginePool of N replicas "
                         "behind the cost-routed fleet gateway")
    ap.add_argument("--replica-config", action="append", metavar="SPEC",
                    help="per-replica spec, repeatable — e.g. "
                         "'cost=3.0,paged,slots=8' (keys: cost, paged, "
                         "dense, page_size, num_pages, slots, arch, name)")
    ap.add_argument("--route-by-cost", action="store_true",
                    help="enable the routing score's per-token dollar "
                         "term: jobs stay on the cheap tier until its "
                         "queue eta outweighs the cost gap")
    ap.add_argument("--cost-weight", type=float, default=0.001,
                    help="weight of the cost term vs queue eta seconds "
                         "(with --route-by-cost)")
    ap.add_argument("--minions", type=int, default=0, metavar="N",
                    help="run N concurrent MinionS requests through a "
                         "ProtocolRunner over this engine (simulated "
                         "remote) instead of raw prompts")
    ap.add_argument("--remote-timeout", type=float, default=None,
                    metavar="S", help="per-call remote deadline in "
                    "seconds (with --minions); enforced by the "
                    "ResilientClient wrapper")
    ap.add_argument("--retries", type=int, default=2,
                    help="bounded remote retries with exponential "
                         "backoff + seeded jitter (with --minions)")
    ap.add_argument("--chaos", type=float, default=0.0, metavar="RATE",
                    help="inject a seeded fault schedule into the remote "
                         "(with --minions): RATE splits 50%% errors / "
                         "30%% stalls / 20%% malformed completions")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--prompts", nargs="+",
                    default=["The total revenue for fiscal year 2015 was"])
    args = ap.parse_args()
    compile_cache.configure()

    mesh = None
    if args.sharded:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(args.model_parallel)
        print(f"mesh: {dict(mesh.shape)}")
    pool = None
    n_replicas = max(args.replicas, len(args.replica_config or []))
    if n_replicas > 1:
        pool = build_fleet(args, mesh)
        engine = pool.replicas[0].engine
        tiers = ", ".join(f"{r.name}(cost={r.cost_per_token:g})"
                          for r in pool.replicas)
        print(f"fleet: {len(pool.replicas)} replicas [{tiers}] "
              f"cost_weight={pool.cost_weight:g}")
    else:
        engine = build_engine(args.arch, smoke=args.smoke,
                              checkpoint=args.checkpoint, seed=args.seed,
                              mesh=mesh,
                              truncate_long=bool(args.minions),
                              paged=args.paged, page_size=args.page_size,
                              num_pages=args.num_pages)
    if args.minions:
        from repro.core import ProtocolRunner
        from repro.core.clients import EngineClient, ResilientClient
        from repro.core.faults import FaultyClient
        from repro.core.simulated import ScriptedRemote
        remote = ScriptedRemote(seed=0)
        faulty = None
        if args.chaos:
            faulty = remote = FaultyClient(
                remote, seed=args.chaos_seed,
                error_rate=args.chaos * 0.5, timeout_rate=args.chaos * 0.3,
                malform_rate=args.chaos * 0.2)
        resilient = None
        if args.chaos or args.remote_timeout is not None:
            # chaos without a timeout would let stalls pass silently —
            # default the deadline just above the latency model's range
            timeout = args.remote_timeout
            if timeout is None:
                timeout = 10.0
            resilient = remote = ResilientClient(
                remote, timeout_s=timeout, max_retries=args.retries,
                seed=args.chaos_seed)
        local = pool if pool is not None else \
            EngineClient(engine, max_batch=args.slots)
        runner = ProtocolRunner(local, remote)
        results = runner.run(minions_tasks(args.minions))
        for i, r in enumerate(results):
            err = f" error={r.error!r}" if r.error else ""
            print(f"task {i}: status={r.status} answer={r.answer!r} "
                  f"remote_tok={r.remote_usage.prefill_tokens}+"
                  f"{r.remote_usage.decode_tokens}{err}")
        print(f"pool: {runner.scheduler.drains} drains / "
              f"{runner.scheduler.jobs_drained} worker jobs")
        if faulty is not None:
            print(f"chaos: {faulty.calls} calls, {faulty.errors} errors, "
                  f"{faulty.stalls} stalls, {faulty.malformed} malformed "
                  f"(simulated {faulty.simulated_s:.1f}s)")
        if resilient is not None:
            print(f"resilience: {resilient.stats} | metered attempts: "
                  f"{resilient.meter.usage}")
        if runner.faults_delivered:
            print(f"supervision: {runner.faults_delivered} faults "
                  f"delivered, {runner.degradations} degradations")
        if pool is not None:
            _print_fleet(pool)
        else:
            print(f"usage: {engine.usage}")
        # injected chaos may legitimately fail tasks; otherwise a failed
        # task is a failed run
        if not args.chaos and any(r.status != "ok" for r in results):
            sys.exit(1)
        return
    if pool is not None:
        res = pool.run(args.prompts, temperature=args.temperature,
                       max_new_tokens=args.max_new_tokens)
        outs = [r.text if r.error is None else f"<error: {r.error}>"
                for r in res]
    elif args.serve:
        outs = engine.serve(args.prompts,
                            max_new_tokens=args.max_new_tokens,
                            temperature=args.temperature, slots=args.slots)
    else:
        outs = engine.generate_batch(args.prompts,
                                     max_new_tokens=args.max_new_tokens,
                                     temperature=args.temperature)
    for p, o in zip(args.prompts, outs):
        print(f">>> {p!r}\n{o!r}\n")
    if pool is not None:
        _print_fleet(pool)
        if any(r.error is not None for r in res):
            sys.exit(1)
    else:
        print(f"usage: {engine.usage}")


def _print_fleet(pool) -> None:
    u = pool.usage
    print(f"fleet: {u.drains} drains / {u.jobs_drained} jobs | cache "
          f"{u.cache_hits}h/{u.cache_misses}m/{u.cache_evictions}e | "
          f"{u.requeues} requeues, {u.replica_failures} replica failures")
    for r in pool.replicas:
        print(f"  {r.name}: served={r.served_jobs} "
              f"tokens={r.decode_tokens} cost={r.cost_per_token:g} "
              f"breaker={r.stats.state}")


if __name__ == "__main__":
    main()
