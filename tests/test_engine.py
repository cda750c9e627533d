"""Inference engine + scheduler behaviour with a real (untrained) model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.serving import ByteTokenizer, InferenceEngine, JobScheduler
from repro.serving.engine import _bucket, _pack_plan
from repro.serving.sampler import sample

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("llama3.2-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(cfg, params, max_seq_len=1024)


@pytest.fixture(scope="module")
def engine_nopack(engine):
    return InferenceEngine(engine.cfg, engine.params, max_seq_len=1024,
                           pack_jobs=False)


def _reference_generate(engine, prompts, max_new_tokens, stop="\n###"):
    """The pre-fusion per-token host loop: the decode-loop oracle."""
    prompt_ids = [engine.tokenizer.encode(p) for p in prompts]
    batch, s = engine._prepare_batch(prompt_ids)
    capacity = _bucket(s + max_new_tokens + engine.decode_margin)
    logits, cache = engine._prefill(engine.params, batch=batch,
                                    capacity=capacity)
    b = len(prompts)
    done = np.zeros(b, bool)
    outputs = [[] for _ in range(b)]
    key = jax.random.PRNGKey(0)
    key, sk = jax.random.split(key)
    tok = sample(logits[:, -1], sk, temperature=0.0)
    for step in range(max_new_tokens):
        tok_np = np.asarray(tok)
        for i in range(b):
            if not done[i]:
                t = int(tok_np[i])
                if t == ByteTokenizer.EOS:
                    done[i] = True
                else:
                    outputs[i].append(t)
        if done.all() or step == max_new_tokens - 1:
            break
        logits, cache = engine._decode(engine.params, tok[:, None], cache)
        key, sk = jax.random.split(key)
        tok = sample(logits[:, -1], sk, temperature=0.0)
    texts = [engine.tokenizer.decode(o) for o in outputs]
    return [t.split(stop)[0] for t in texts]


def test_ragged_batch(engine):
    outs = engine.generate_batch(["a", "bb" * 30, "c" * 100],
                                 max_new_tokens=4)
    assert len(outs) == 3
    assert all(isinstance(o, str) for o in outs)


def test_usage_counts(engine):
    before = engine.usage.prefill_tokens
    engine.generate_batch(["hello world"], max_new_tokens=4)
    assert engine.usage.prefill_tokens > before
    assert engine.usage.decode_tokens >= 1


def test_deterministic_greedy(engine):
    a = engine.generate("determinism", max_new_tokens=8, temperature=0.0)
    b = engine.generate("determinism", max_new_tokens=8, temperature=0.0)
    assert a == b


def test_too_long_prompt_raises(engine):
    with pytest.raises(ValueError):
        engine.generate_batch(["x" * 5000], max_new_tokens=2)


def test_truncate_long_with_non_power_of_two_max_seq_len(engine):
    """Regression: truncate_long capped prompts at max_seq_len but the
    bucket then rounded UP past it (cap 200 -> bucket 256 -> ValueError),
    so graceful degradation raised anyway.  The bucket must clamp."""
    eng = InferenceEngine(engine.cfg, engine.params, max_seq_len=200,
                          truncate_long=True)
    outs = eng.generate_batch(["z" * 500, "short"], max_new_tokens=2)
    assert len(outs) == 2
    # and untruncated engines still reject over-long prompts
    strict = InferenceEngine(engine.cfg, engine.params, max_seq_len=200)
    with pytest.raises(ValueError):
        strict.generate_batch(["z" * 500], max_new_tokens=2)


def test_scheduler_order_and_samples(engine):
    sched = JobScheduler(engine.generate_batch, max_batch=4)
    res = sched.run([f"job {i}" for i in range(5)], samples=2,
                    max_new_tokens=2)
    assert len(res) == 10
    assert [(r.job_index, r.sample_index) for r in res] == \
        [(j, s) for j in range(5) for s in range(2)]


def test_tokenizer_roundtrip():
    tok = ByteTokenizer()
    for s in ["hello", "üñïçôdé", "", "a\nb\tc", "数字123"]:
        assert tok.decode(tok.encode(s)) == s


# ---------------------------------------------------------------------------
# fused decode loop
# ---------------------------------------------------------------------------


def test_fused_loop_matches_reference_loop(engine, engine_nopack):
    """Greedy fused while_loop decode == the old per-token host loop."""
    prompts = ["fused decode", "a" * 50, "short"]
    want = _reference_generate(engine, prompts, max_new_tokens=12)
    assert engine_nopack.generate_batch(prompts, max_new_tokens=12) == want
    # and the packed path agrees too
    assert engine.generate_batch(prompts, max_new_tokens=12) == want


def test_fused_loop_per_row_eos_early_stop(engine):
    """A row whose first sampled token is EOS emits nothing; live rows
    keep decoding."""
    batch, s = engine._prepare_batch(
        [engine.tokenizer.encode(p) for p in ["stop now", "continue"]])
    logits, cache = engine._prefill(engine.params, batch=batch,
                                    capacity=_bucket(s + 16 + 256))
    v = logits.shape[-1]
    first = np.full((2, v), -1e9, np.float32)
    first[0, ByteTokenizer.EOS] = 0.0   # row 0 terminates immediately
    first[1, ord("A")] = 0.0            # row 1 emits 'A' then free-runs
    out, n = engine._decode_loop(
        engine.params, jnp.asarray(first), cache, jax.random.PRNGKey(0),
        jnp.zeros((0,), jnp.int32), 8, 0.0, buf_len=8, greedy=True)
    out = np.asarray(out)
    assert (out[0] == ByteTokenizer.PAD).all()
    assert out[1, 0] == ord("A")
    assert int(n) >= 1


def test_fused_loop_all_eos_exits_immediately(engine):
    batch, s = engine._prepare_batch(
        [engine.tokenizer.encode("x"), engine.tokenizer.encode("y")])
    logits, cache = engine._prefill(engine.params, batch=batch,
                                    capacity=_bucket(s + 16 + 256))
    v = logits.shape[-1]
    first = np.full((2, v), -1e9, np.float32)
    first[:, ByteTokenizer.EOS] = 0.0
    out, n = engine._decode_loop(
        engine.params, jnp.asarray(first), cache, jax.random.PRNGKey(0),
        jnp.zeros((0,), jnp.int32), 8, 0.0, buf_len=8, greedy=True)
    assert int(n) == 0
    assert (np.asarray(out) == ByteTokenizer.PAD).all()


def test_decode_transfers_constant_in_tokens(engine):
    """O(1) host<->device transfers per generate_batch call, independent
    of max_new_tokens (the acceptance-criterion counter)."""
    t0 = engine.usage.host_transfers
    engine.generate_batch(["count transfers"], max_new_tokens=4)
    t_short = engine.usage.host_transfers - t0
    t1 = engine.usage.host_transfers
    engine.generate_batch(["count transfers"], max_new_tokens=48)
    t_long = engine.usage.host_transfers - t1
    assert t_short == t_long
    assert t_long <= 4  # constant, small


def test_on_device_stop_sequence_halts_decode(engine):
    """The fused loop must stop DECODING at the stop marker, not just trim
    text on the host: force the first token to equal a one-byte stop
    sequence and check the loop emits nothing further."""
    batch, s = engine._prepare_batch([engine.tokenizer.encode("marker")])
    logits, cache = engine._prefill(engine.params, batch=batch,
                                    capacity=_bucket(s + 16 + 256))
    v = logits.shape[-1]
    first = np.full((1, v), -1e9, np.float32)
    first[0, ord("A")] = 0.0
    # free-running (no stop): the model emits more than one token
    out_free, n_free = engine._decode_loop(
        engine.params, jnp.asarray(first), cache, jax.random.PRNGKey(0),
        jnp.zeros((0,), jnp.int32), 16, 0.0, buf_len=16, greedy=True)
    assert int(n_free) > 1
    # stop marker == the forced first token: decode halts on device
    out_stop, n_stop = engine._decode_loop(
        engine.params, jnp.asarray(first), cache, jax.random.PRNGKey(0),
        jnp.asarray([ord("A")], jnp.int32), 16, 0.0, buf_len=16,
        greedy=True)
    out_stop = np.asarray(out_stop)
    assert int(n_stop) == 1                              # only the marker
    assert out_stop[0, 0] == ord("A")
    assert (out_stop[0, 1:] == ByteTokenizer.PAD).all()  # nothing after
    # and the public API trims the marker off the returned text
    a = engine.generate("stop marker", max_new_tokens=24, stop="\n###")
    b = engine.generate("stop marker", max_new_tokens=24, stop="")
    assert b.split("\n###")[0] == a


# ---------------------------------------------------------------------------
# packed prefill
# ---------------------------------------------------------------------------


def test_packed_prefill_matches_one_job_per_row(engine, engine_nopack):
    prompts = ["pack me", "b" * 40, "the quick brown fox " * 4, "x",
               "hello world, hello"]
    packed = engine.generate_batch(prompts, max_new_tokens=16)
    unpacked = engine_nopack.generate_batch(prompts, max_new_tokens=16)
    assert packed == unpacked


def test_packing_reduces_prefill_slots(engine, engine_nopack):
    prompts = ["a" * 20, "b" * 30, "c" * 25, "d" * 10, "e" * 15, "f" * 28]
    s0 = engine.usage.prefill_slots
    engine.generate_batch(prompts, max_new_tokens=2)
    packed_slots = engine.usage.prefill_slots - s0
    s1 = engine_nopack.usage.prefill_slots
    engine_nopack.generate_batch(prompts, max_new_tokens=2)
    unpacked_slots = engine_nopack.usage.prefill_slots - s1
    assert packed_slots < unpacked_slots


def test_pack_plan_first_fit():
    plan = _pack_plan([20, 30, 25, 10], 64)
    assert sorted(i for row in plan for i in row) == [0, 1, 2, 3]
    assert len(plan) < 4
    for row in plan:
        assert sum([20, 30, 25, 10][i] for i in row) <= 64


def test_single_prompt_never_packs(engine):
    # generate() goes through the unpacked path (plan has nothing to gain)
    assert isinstance(engine.generate("solo", max_new_tokens=2), str)


def test_moe_configs_never_pack():
    """Expert-capacity routing depends on batch layout, so packing would
    change MoE outputs — can_pack must refuse."""
    cfg = get_smoke_config("olmoe-1b-7b")
    assert cfg.is_moe
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, max_seq_len=256, pack_jobs=True)
    assert not eng.can_pack


def test_temperature_sweep_shares_executable(engine):
    """Distinct positive temperatures must not recompile the fused loop
    (temperature is a traced scalar; only greedy-ness is static)."""
    engine.generate_batch(["warm"], max_new_tokens=4, temperature=0.5)
    n0 = engine._decode_loop._cache_size()
    for t in (0.7, 0.9, 1.3):
        engine.generate_batch(["warm"], max_new_tokens=4, temperature=t)
    assert engine._decode_loop._cache_size() == n0


# ---------------------------------------------------------------------------
# scheduler batching
# ---------------------------------------------------------------------------


def test_scheduler_length_sorts_batches():
    """Same-batch prompts must be length-neighbours, and results must
    still come back in submission order."""
    batches = []

    def fake_generate(prompts, temperature=0.0, key=None,
                      max_new_tokens=0):
        batches.append(list(prompts))
        return [p[::-1] for p in prompts]

    prompts = ["a" * n for n in (500, 3, 480, 5, 490, 7, 470, 9)]
    res = JobScheduler(fake_generate, max_batch=4).run(prompts)
    assert [r.text for r in res] == [p[::-1] for p in prompts]
    assert len(batches) == 2
    lens = [sorted(len(p) for p in b) for b in batches]
    assert lens[0] == [3, 5, 7, 9]          # shorts together
    assert lens[1] == [470, 480, 490, 500]  # longs together


# ---------------------------------------------------------------------------
# fused-loop stop-sequence edges
# ---------------------------------------------------------------------------


def _forced_first(engine, prompt, vocab_token):
    """Prefill one row and force its first sampled token."""
    batch, s = engine._prepare_batch([engine.tokenizer.encode(prompt)])
    logits, cache = engine._prefill(engine.params, batch=batch,
                                    capacity=_bucket(s + 16 + 256))
    first = np.full((1, logits.shape[-1]), -1e9, np.float32)
    first[0, vocab_token] = 0.0
    return jnp.asarray(first), cache


def test_stop_window_clamp_no_false_match_on_early_steps(engine):
    """At step < n_stop - 1 the rolling window clamps to the buffer start
    and reads unwritten PAD columns — which must never complete a match.
    Stop "AA" with first token 'A': the clamped window is [A, PAD], so
    decode must NOT halt after one token."""
    first, cache = _forced_first(engine, "clamp edge", ord("A"))
    out, n = engine._decode_loop(
        engine.params, first, cache, jax.random.PRNGKey(0),
        jnp.asarray([ord("A"), ord("A")], jnp.int32), 16, 0.0,
        buf_len=16, greedy=True)
    assert int(n) > 1                       # survived the clamped window
    assert np.asarray(out)[0, 0] == ord("A")


def test_stop_longer_than_buffer_is_skipped(engine):
    """n_stop > buf_len: the on-device check is structurally impossible
    (fewer emitted tokens than the stop is long), so the loop skips it and
    decodes to the budget.  Intended divergence from host-side
    ``text.split(stop)``: a PARTIAL stop prefix at the end of a tiny
    generation is kept, since split() can't match it either."""
    first, cache = _forced_first(engine, "tiny budget", ord("A"))
    stop3 = jnp.asarray([ord("A"), ord("B"), ord("C")], jnp.int32)
    out, n = engine._decode_loop(
        engine.params, first, cache, jax.random.PRNGKey(0),
        stop3, 2, 0.0, buf_len=2, greedy=True)
    assert int(n) == 2                      # ran to the budget, no stop
    assert (np.asarray(out)[0, :2] != ByteTokenizer.PAD).all()


# ---------------------------------------------------------------------------
# continuous batching (serve): slot pool + admission
# ---------------------------------------------------------------------------


def test_serve_first_wave_matches_generate_batch(engine):
    """Jobs admitted at a fresh epoch occupy the same left-padded layout
    as a generate_batch call, so greedy outputs are identical."""
    prompts = ["alpha", "beta gamma", "delta epsilon zeta"]
    assert engine.serve(prompts, max_new_tokens=8, slots=3) == \
        engine.generate_batch(prompts, max_new_tokens=8)


def test_serve_admits_queued_jobs_before_long_job_finishes(engine):
    """Acceptance: ragged budgets [8, 8, 8, 256] — the short rows free up,
    queued jobs are admitted into them, and all of that happens while the
    256-budget job is still decoding (observed via EngineUsage.events)."""
    e0 = len(engine.usage.events)
    prompts = [f"ragged job {i}" for i in range(7)]
    budgets = [8, 8, 8, 256, 8, 8, 8]
    outs = engine.serve(prompts, max_new_tokens=budgets, slots=4)
    assert len(outs) == 7 and all(isinstance(o, str) for o in outs)
    ev = engine.usage.events[e0:]
    long_finish = next(p for (kind, j, p, _r) in ev
                       if kind == "finish" and j == 3)
    late_admits = [p for (kind, j, p, _r) in ev
                   if kind == "admit" and j >= 4]
    assert len(late_admits) == 3
    assert all(p < long_finish for p in late_admits)
    # and the long job really decoded past the shorts' admission point
    assert long_finish > max(late_admits)


def test_serve_deterministic_and_complete(engine):
    prompts = [f"determinism {i} " + "x" * (3 * i) for i in range(9)]
    a = engine.serve(prompts, max_new_tokens=6, slots=4)
    b = engine.serve(prompts, max_new_tokens=6, slots=4)
    assert a == b
    assert len(a) == 9


def test_serve_per_row_temperature_lanes(engine):
    """Greedy rows stay deterministic even when admitted next to
    stochastic neighbours (per-row temperature + RNG lanes)."""
    prompts = ["greedy row", "hot row", "another greedy"]
    temps = [0.0, 1.3, 0.0]
    mixed = engine.serve(prompts, max_new_tokens=8, temperature=temps,
                         slots=3)
    pure = engine.serve(prompts, max_new_tokens=8, temperature=0.0,
                        slots=3)
    assert mixed[0] == pure[0]
    assert mixed[2] == pure[2]


def test_serve_counts_usage(engine):
    adm0, fin0 = engine.usage.admitted_jobs, engine.usage.finished_jobs
    d0 = engine.usage.decode_tokens
    engine.serve(["usage a", "usage b"], max_new_tokens=4, slots=2)
    assert engine.usage.admitted_jobs - adm0 == 2
    assert engine.usage.finished_jobs - fin0 == 2
    assert engine.usage.decode_tokens >= d0


def test_serve_epoch_reset_when_nothing_fits(engine):
    """More jobs than one epoch's cache can absorb still all complete —
    the pool retires the cache and starts a new epoch."""
    eng = InferenceEngine(engine.cfg, engine.params, max_seq_len=1024,
                          decode_margin=0)
    ep0 = eng.usage.serve_epochs
    outs = eng.serve([f"epoch job {i}" for i in range(6)],
                     max_new_tokens=128, slots=2)
    assert len(outs) == 6
    assert eng.usage.serve_epochs > ep0


def test_serve_unservable_config_degrades_to_convoy():
    """MoE caches have no admissible slot layout: serve falls back to
    convoy groups but still returns every result."""
    cfg = get_smoke_config("olmoe-1b-7b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, max_seq_len=256)
    assert not eng.can_serve
    outs = eng.serve([f"moe {i}" for i in range(3)], max_new_tokens=2,
                     slots=2)
    assert len(outs) == 3


# ---------------------------------------------------------------------------
# streaming scheduler + EngineClient routing
# ---------------------------------------------------------------------------


def test_scheduler_upgrades_engine_to_streaming(engine):
    sched = JobScheduler(engine.generate_batch, max_batch=4)
    assert sched.engine is engine
    sched = JobScheduler(engine, max_batch=4)
    assert sched.engine is engine


def test_scheduler_submit_drain(engine):
    sched = JobScheduler(engine, max_batch=4)
    ids = [sched.submit(f"stream {i}", samples=1, max_new_tokens=2)
           for i in range(3)]
    assert ids == [0, 1, 2]
    res = sched.drain()
    assert [(r.job_index, r.sample_index) for r in res] == \
        [(0, 0), (1, 0), (2, 0)]
    assert sched.drain() == []              # queue is left empty
    # job numbering restarts per drain: a reused scheduler (EngineClient
    # keeps one for its lifetime) must index each batch from 0
    sched.submit("next batch", max_new_tokens=2)
    assert [r.job_index for r in sched.drain()] == [0]


def test_scheduler_drain_independent_of_submission_order(engine):
    """Length-aware admission must not make results depend on the order
    jobs were interleaved into the queue: for a fixed seed and greedy
    sampling, each prompt's text is identical under any submission
    permutation (and results always return in that permutation's
    submission order)."""
    prompts = [f"order job {i} " + "z" * (7 * i % 23) for i in range(9)]
    prompts[4] = prompts[2]                 # equal lengths tie-break too
    budgets = [6, 6, 24, 6, 24, 6, 6, 6, 6]

    def run(order):
        sched = JobScheduler(engine, max_batch=4)
        for j in order:
            sched.submit(prompts[j], max_new_tokens=budgets[j],
                         temperature=0.0)
        res = sched.drain(seed=0)
        return {order[r.job_index]: r.text for r in res}

    base = run(list(range(9)))
    for order in ([8, 7, 6, 5, 4, 3, 2, 1, 0],
                  [3, 0, 7, 1, 8, 2, 5, 6, 4]):
        assert run(order) == base


def test_scheduler_stochastic_lanes_invariant_to_drain_composition(engine):
    """Streaming path: a stochastic job's sampled TEXT is a function of
    its stable rng_id — draining it alongside different companion jobs
    (other param classes, other tasks) must not perturb it.  This is
    what lets one shared pool serve many concurrent protocol tasks."""
    stoch = [(f"stochastic job {i}", (7, i)) for i in range(3)]

    def run(extra):
        sched = JobScheduler(engine, max_batch=4)
        ids = {}
        for prompt, temp, rid in extra:
            sched.submit(prompt, temperature=temp, max_new_tokens=8,
                         rng_id=rid)
        for prompt, rid in stoch:
            ids[rid] = sched.submit(prompt, temperature=0.9,
                                    max_new_tokens=8, rng_id=rid)
        res = {r.job_index: r.text for r in sched.drain(seed=0)}
        return {rid: res[ji] for rid, ji in ids.items()}

    alone = run([])
    assert run([("greedy filler", 0.0, (9, 0))]) == alone
    assert run([("hot filler " + "x" * 20, 0.7, (5, 0)),
                ("hot 2", 0.7, (5, 1))]) == alone


def test_serve_rounds_slots_up_to_mesh_data_axis(engine):
    """A sharded engine's slot pool must place whole rows on every data
    shard: serve widens a 4-slot request to the 8-way data axis (visible
    in the admit events: the first wave fills rows 0..7), and the output
    still matches the single-device engine."""
    from repro.launch.mesh import make_host_mesh
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    sharded = InferenceEngine(engine.cfg, engine.params, max_seq_len=1024,
                              mesh=make_host_mesh(1))
    prompts = [f"round up {i}" for i in range(9)]
    e0 = len(sharded.usage.events)
    out = sharded.serve(prompts, max_new_tokens=4, slots=4)
    first_wave = [r for (kind, _j, _p, r) in sharded.usage.events[e0:e0 + 8]
                  if kind == "admit"]
    assert sorted(first_wave) == list(range(8))   # pool widened 4 -> 8
    assert out == engine.serve(prompts, max_new_tokens=4, slots=4)


# ---------------------------------------------------------------------------
# EngineUsage lifetime semantics
# ---------------------------------------------------------------------------


def test_usage_accumulates_across_serve_calls_and_resets(engine):
    """Regression for the reused-engine accounting surprise: counters are
    CUMULATIVE across serve calls (documented billing-meter semantics, a
    second serve must not silently restart them at zero), and reset()
    starts a fresh billing period including the event log."""
    eng = InferenceEngine(engine.cfg, engine.params, max_seq_len=1024)
    eng.serve(["usage one", "usage two"], max_new_tokens=4, slots=2)
    first = (eng.usage.admitted_jobs, eng.usage.finished_jobs,
             eng.usage.prefill_tokens, eng.usage.host_transfers)
    assert first[0] == 2 and first[1] == 2
    eng.serve(["usage three", "usage four"], max_new_tokens=4, slots=2)
    assert eng.usage.admitted_jobs == 4          # accumulated, not reset
    assert eng.usage.finished_jobs == 4
    assert eng.usage.prefill_tokens > first[2]
    assert eng.usage.host_transfers > first[3]
    assert eng.usage.decode_steps > 0
    assert len(eng.usage.events) == 8            # 4 admits + 4 finishes

    eng.usage.reset()
    assert eng.usage.admitted_jobs == 0
    assert eng.usage.finished_jobs == 0
    assert eng.usage.prefill_tokens == 0
    assert eng.usage.decode_tokens == 0
    assert eng.usage.host_transfers == 0
    assert eng.usage.decode_steps == 0
    assert eng.usage.serve_epochs == 0
    assert eng.usage.calls == 0
    assert eng.usage.events == []
    # and the engine keeps metering correctly after the reset
    eng.serve(["after reset"], max_new_tokens=4, slots=1)
    assert eng.usage.admitted_jobs == 1


def test_drain_grouped_isolates_sampling_params():
    """Plain-callable fallback: jobs batch only with param-identical
    neighbours — a greedy job must not inherit a stochastic sibling's
    temperature or token budget."""
    seen = []

    def fake_generate(prompts, temperature=0.0, key=None, max_new_tokens=0):
        seen.append((temperature, max_new_tokens, list(prompts)))
        return ["" for _ in prompts]

    sched = JobScheduler(fake_generate, max_batch=8)
    sched.submit("greedy", temperature=0.0, max_new_tokens=4)
    sched.submit("hot", temperature=0.9, max_new_tokens=64)
    sched.submit("greedy 2", temperature=0.0, max_new_tokens=4)
    sched.drain()
    assert sorted(seen) == [
        (0.0, 4, ["greedy", "greedy 2"]), (0.9, 64, ["hot"])]


def test_engine_client_ragged_batch_cuts_prefill_padding(engine):
    """EngineClient now streams through the scheduler: a ragged MinionS
    round must burn fewer padded prefill slots than the old fixed
    submission-order slices (EngineUsage.prefill_slots)."""
    from repro.core.clients import EngineClient
    prompts = ["a" * 10] * 7 + ["b" * 300]
    eng_s = InferenceEngine(engine.cfg, engine.params, max_seq_len=1024,
                            pack_jobs=False)
    eng_c = InferenceEngine(engine.cfg, engine.params, max_seq_len=1024,
                            pack_jobs=False)
    EngineClient(eng_s, max_batch=4).complete_batch(prompts, max_tokens=4)
    for off in range(0, len(prompts), 4):    # the deleted convoy slicing
        eng_c.generate_batch(prompts[off:off + 4], max_new_tokens=4)
    assert eng_s.usage.prefill_slots < eng_c.usage.prefill_slots
