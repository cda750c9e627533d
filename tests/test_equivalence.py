"""Cross-backend differential equivalence harness.

PRs 1-3 grew several numerically-equivalent execution paths through the
serving engine: {reference, pallas-interpret} attention backends x
{generate_batch, serve} x {packed, unpacked} prefill x {single-device,
8-device host mesh} — and PR 7 added the {dense, paged} cache axis
(page-pool KV with radix prefix reuse).  Rather than ad-hoc pairwise
spot checks, every cell
of that grid is pinned to ONE oracle — the single-device, reference
backend, unpacked ``generate_batch`` output — so all cells are
transitively token-identical for identical seeds.

The full cross-product is marked ``slow``; a 2-cell smoke subset (the two
most load-bearing diagonals: sharded serve, and pallas packed prefill)
stays unmarked so `pytest -m "not slow"` still exercises the harness.

Engines and oracles are cached per cell so each compiled executable is
built once per session.
"""
import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.serving import InferenceEngine

# serve-capable configs from the registry (pure-attention decoders): the
# packed/serve cells require a slot-addressable cache
ARCHS = ["llama3.2-1b", "qwen1.5-32b"]

# 8 ragged prompts: divisible by the 8-way data axis so mesh cells shard
# whole rows (row-aligned pools are the bit-identity guarantee; the
# sequence-sharded fallback reorders float reductions)
PROMPTS = [f"equivalence job {i}: " + "data " * (3 * i) for i in range(8)]
MAX_NEW = 8

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

_params = {}
_engines = {}
_oracles = {}


def _cfg_params(arch):
    if arch not in _params:
        cfg = get_smoke_config(arch)
        _params[arch] = (cfg, T.init_params(cfg, jax.random.PRNGKey(0)))
    return _params[arch]


def _engine(arch, backend, mesh_devices, pack):
    key = (arch, backend, mesh_devices, pack)
    if key not in _engines:
        cfg, params = _cfg_params(arch)
        if backend == "pallas":
            cfg = cfg.replace(attention_backend="pallas")
        mesh = make_host_mesh(1) if mesh_devices > 1 else None
        _engines[key] = InferenceEngine(cfg, params, max_seq_len=1024,
                                        pack_jobs=pack, mesh=mesh)
    return _engines[key]


def _paged_engine(arch, backend, mesh_devices=1):
    """Paged engines are cached per (arch, backend, mesh) ONLY — repeated
    grid cells reuse one engine, so its persistent radix index serves
    later cells from cached prefix pages.  Token identity must survive
    that reuse (a cached prefix must be bit-equal to a fresh prefill)."""
    key = (arch, backend, mesh_devices, "paged")
    if key not in _engines:
        cfg, params = _cfg_params(arch)
        if backend == "pallas":
            cfg = cfg.replace(attention_backend="pallas")
        mesh = make_host_mesh(1) if mesh_devices > 1 else None
        _engines[key] = InferenceEngine(cfg, params, max_seq_len=1024,
                                        mesh=mesh, paged=True,
                                        page_size=16, num_pages=512)
    return _engines[key]


def _oracle(arch):
    """Single-device / reference backend / unpacked generate_batch."""
    if arch not in _oracles:
        eng = _engine(arch, "reference", 1, pack=False)
        _oracles[arch] = eng.generate_batch(PROMPTS, max_new_tokens=MAX_NEW)
    return _oracles[arch]


def _run_cell(arch, backend, path, pack, mesh_devices):
    eng = _engine(arch, backend, mesh_devices, pack)
    if path == "serve":
        out = eng.serve(PROMPTS, max_new_tokens=MAX_NEW, slots=8)
        # served rows keep one contiguous window: the Pallas decode never
        # takes its reference fallback
        assert eng.usage.decode_fallback_steps == 0
        return out
    return eng.generate_batch(PROMPTS, max_new_tokens=MAX_NEW)


# ---------------------------------------------------------------------------
# the full grid (slow) and the unmarked smoke subset
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("mesh_devices", [1, 8])
@pytest.mark.parametrize("pack", [True, False], ids=["packed", "unpacked"])
@pytest.mark.parametrize("path", ["generate_batch", "serve"])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("arch", ARCHS)
def test_equivalence_grid(arch, backend, path, pack, mesh_devices):
    if mesh_devices > len(jax.devices()):
        pytest.skip("not enough devices for the mesh cell")
    assert _run_cell(arch, backend, path, pack, mesh_devices) == \
        _oracle(arch)


@needs_mesh
def test_smoke_sharded_serve_matches_oracle():
    """Smoke cell 1: 8-device mesh-sharded packed serve == oracle."""
    arch = "llama3.2-1b"
    assert _run_cell(arch, "reference", "serve", True, 8) == _oracle(arch)


def test_smoke_pallas_packed_matches_oracle():
    """Smoke cell 2: pallas-interpret packed generate_batch == oracle."""
    arch = "llama3.2-1b"
    assert _run_cell(arch, "pallas", "generate_batch", True, 1) == \
        _oracle(arch)


# ---------------------------------------------------------------------------
# paged KV cells: page pool + radix prefix reuse must be token-identical
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("path", ["generate_batch", "serve"])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("arch", ARCHS)
def test_equivalence_paged_grid(arch, backend, path, mesh_devices=1):
    """Every paged cell == the dense oracle.  The engine is shared across
    cells, so later cells admit against a radix populated by earlier ones
    — prefix reuse under strict token identity."""
    eng = _paged_engine(arch, backend, mesh_devices)
    if path == "serve":
        out = eng.serve(PROMPTS, max_new_tokens=MAX_NEW, slots=8)
    else:
        out = eng.generate_batch(PROMPTS, max_new_tokens=MAX_NEW)
    assert out == _oracle(arch)


def test_smoke_paged_serve_matches_oracle():
    """Smoke cell 3: paged serve (reference) == dense oracle, twice — the
    second call must be identical while prefilling only novel suffixes."""
    eng = _paged_engine("llama3.2-1b", "reference")
    assert eng.serve(PROMPTS, max_new_tokens=MAX_NEW, slots=8) == \
        _oracle("llama3.2-1b")
    before = eng.usage.prefill_tokens
    assert eng.serve(PROMPTS, max_new_tokens=MAX_NEW, slots=8) == \
        _oracle("llama3.2-1b")
    again = eng.usage.prefill_tokens - before
    assert again < before, "radix reuse did not reduce prefill work"
    assert eng.usage.prefix_hit_tokens > 0


@pytest.mark.slow
@needs_mesh
def test_sharded_paged_serve_matches_oracle():
    """8-device mesh: the page pool shards pages over "data" (page_table /
    row_len shard like row lanes) and must stay token-identical."""
    eng = _paged_engine("llama3.2-1b", "reference", 8)
    assert eng.serve(PROMPTS, max_new_tokens=MAX_NEW, slots=8) == \
        _oracle("llama3.2-1b")
    assert eng.generate_batch(PROMPTS, max_new_tokens=MAX_NEW) == \
        _oracle("llama3.2-1b")


# ---------------------------------------------------------------------------
# seeded stochastic equivalence: sharding must not perturb sampling
# ---------------------------------------------------------------------------


@pytest.mark.slow
@needs_mesh
def test_stochastic_serve_mesh_invariant():
    """Per-job RNG lanes are a function of the serve key and job index
    only, so stochastic serve is token-identical across meshes."""
    kw = dict(max_new_tokens=MAX_NEW, temperature=0.9,
              key=jax.random.PRNGKey(7), slots=8)
    a = _engine("llama3.2-1b", "reference", 1, True).serve(PROMPTS, **kw)
    b = _engine("llama3.2-1b", "reference", 8, True).serve(PROMPTS, **kw)
    assert a == b


@pytest.mark.slow
@needs_mesh
def test_tensor_parallel_serve_matches_oracle():
    """data=4 x model=2 host mesh: kv heads shard over "model".  Identity
    here is empirical (head-concat matmul reductions are reordered under
    TP), asserted because it holds for the smoke configs; the guaranteed
    cells are the data-parallel ones above."""
    cfg, params = _cfg_params("llama3.2-1b")
    eng = InferenceEngine(cfg, params, max_seq_len=1024,
                          mesh=make_host_mesh(2))
    assert eng.serve(PROMPTS, max_new_tokens=MAX_NEW, slots=8) == \
        _oracle("llama3.2-1b")
    assert eng.generate_batch(PROMPTS, max_new_tokens=MAX_NEW) == \
        _oracle("llama3.2-1b")


# ---------------------------------------------------------------------------
# acceptance: sharded slot admission stays O(admissions), not O(tokens)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@needs_mesh
def test_sharded_serve_transfers_o_admissions():
    """Ragged budgets force mid-epoch admissions into the live SHARDED
    cache.  Outputs must match single-device serve token-for-token, and
    EngineUsage.host_transfers must (a) equal the single-device count —
    sharding adds zero host traffic, the primed KV scatter happens on
    device — and (b) stay constant when every budget is quadrupled —
    O(admissions), not O(tokens)."""
    cfg, params = _cfg_params("llama3.2-1b")
    prompts = [f"ragged {i} " + "y" * (3 * i) for i in range(12)]
    budgets = [4, 4, 4, 32, 4, 4, 4, 32, 4, 4, 4, 32]

    single = InferenceEngine(cfg, params, max_seq_len=1024)
    sharded = InferenceEngine(cfg, params, max_seq_len=1024,
                              mesh=make_host_mesh(1))

    t0 = single.usage.host_transfers
    ref = single.serve(prompts, max_new_tokens=budgets, slots=8)
    single_transfers = single.usage.host_transfers - t0

    t0 = sharded.usage.host_transfers
    out = sharded.serve(prompts, max_new_tokens=budgets, slots=8)
    sharded_transfers = sharded.usage.host_transfers - t0

    assert out == ref
    assert sharded_transfers == single_transfers
    # every yield harvests at least one finished job
    assert sharded_transfers <= 4 * len(prompts)
    assert sharded.usage.admitted_jobs == len(prompts)

    # token budget x4: same admission pattern, same host traffic
    t0 = sharded.usage.host_transfers
    sharded.serve(prompts, max_new_tokens=[b * 4 for b in budgets],
                  slots=8)
    assert sharded.usage.host_transfers - t0 == sharded_transfers


# ---------------------------------------------------------------------------
# fleet cells: EnginePool routing changes PLACEMENT, never tokens
# ---------------------------------------------------------------------------


def _fleet_engine(tag):
    """Distinct engine INSTANCES over the same smoke weights — a real
    2-replica pool, cached per tag so each replica compiles once."""
    key = ("fleet", tag)
    if key not in _engines:
        cfg, params = _cfg_params("llama3.2-1b")
        _engines[key] = InferenceEngine(cfg, params, max_seq_len=1024)
    return _engines[key]


def test_fleet_pool_greedy_matches_oracle():
    """Smoke fleet cell: a 2-replica homogeneous pool serving the mixed
    job set is token-identical to the single-engine oracle — and the
    work is genuinely spread (both replicas serve)."""
    from repro.serving import EnginePool, Replica
    pool = EnginePool([Replica(_fleet_engine("a")),
                       Replica(_fleet_engine("b"))],
                      route_by_cost=False, clock=lambda: 0.0)
    for p in PROMPTS:
        pool.submit(p, temperature=0.0, max_new_tokens=MAX_NEW)
    res = pool.drain(seed=0)
    assert [r.error for r in res] == [None] * len(PROMPTS)
    assert [r.text for r in res] == _oracle("llama3.2-1b")
    assert all(rep.served_jobs > 0 for rep in pool.replicas)


def test_fleet_pool_stochastic_matches_single_scheduler():
    """Seeded-stochastic fleet cell: per-job PRNG lanes derive from the
    drain key and the job's rng_id — not from placement — so a 2-replica
    pool samples token-identically to one JobScheduler over one engine."""
    from repro.serving import EnginePool, JobScheduler, Replica
    sched = JobScheduler(_fleet_engine("a"))
    pool = EnginePool([Replica(_fleet_engine("a")),
                       Replica(_fleet_engine("b"))],
                      route_by_cost=False, clock=lambda: 0.0)
    for i, p in enumerate(PROMPTS):
        sched.submit(p, temperature=0.9, max_new_tokens=MAX_NEW,
                     rng_id=(i,))
        pool.submit(p, temperature=0.9, max_new_tokens=MAX_NEW,
                    rng_id=(i,))
    want = [(r.job_index, r.sample_index, r.text)
            for r in sched.drain(seed=11)]
    got = [(r.job_index, r.sample_index, r.text)
           for r in pool.drain(seed=11)]
    assert got == want
    assert all(rep.served_jobs > 0 for rep in pool.replicas)


def test_fleet_heterogeneous_paged_dense_matches_oracle():
    """Heterogeneous fleet cell: a paged replica (prefix-clustered
    drains, radix reuse) and a dense replica in ONE pool.  Greedy output
    equals the dense oracle; seeded-stochastic output equals a
    single-scheduler run — cache layout is invisible to tokens."""
    from repro.serving import EnginePool, JobScheduler, Replica
    pool = EnginePool(
        [Replica(_paged_engine("llama3.2-1b", "reference"),
                 cost_per_token=3.0),
         Replica(_fleet_engine("a"), cost_per_token=1.0)],
        route_by_cost=False, clock=lambda: 0.0)
    for p in PROMPTS:
        pool.submit(p, temperature=0.0, max_new_tokens=MAX_NEW)
    res = pool.drain(seed=0)
    assert [r.text for r in res] == _oracle("llama3.2-1b")
    assert all(rep.served_jobs > 0 for rep in pool.replicas)

    sched = JobScheduler(_fleet_engine("a"))
    for i, p in enumerate(PROMPTS):
        sched.submit(p, temperature=0.9, max_new_tokens=MAX_NEW,
                     rng_id=(i,))
        pool.submit(p, temperature=0.9, max_new_tokens=MAX_NEW,
                    rng_id=(i,))
    want = [r.text for r in sched.drain(seed=23)]
    got = [r.text for r in pool.drain(seed=23)]
    assert got == want
