"""The serve path names its own work for the profiler.

- Host spans at each layer boundary (``protocol.step`` > ``protocol.local``
  > ``scheduler.drain`` > ``engine.serve`` > ``engine.plan`` / ``admit`` /
  ``decode`` / ``harvest``), with their counts as stats; the CPU profiler
  records them like the TPU one does.
- Named jitted programs: dispatch spans and compiled modules carry
  ``serve_decode``, ``paged_prefill``, ... instead of ``lambda``.
- Named scopes (``attn``, ``kv_write``, ``mlp``, ``lm_head``, ``sample``)
  are metadata only: greedy tokens are identical with them and without.
- ``EngineUsage.decode_steps`` counts the serve loop's real iterations.
"""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core import MinionSConfig, ProtocolRunner, TaskSpec
from repro.core.clients import EngineClient
from repro.core.simulated import ScriptedRemote
from repro.models import transformer as T
from repro.serving import InferenceEngine

SCOPES = ("attn", "kv_write", "mlp", "lm_head", "sample")
PROGRAM_SPANS = ("protocol.", "scheduler.", "engine.")

_params = {}


def _cfg_params():
    if not _params:
        cfg = get_smoke_config("llama3.2-1b")
        _params["p"] = (cfg, T.init_params(cfg, jax.random.PRNGKey(0)))
    return _params["p"]


def _paged(cfg, params, **kw):
    return InferenceEngine(cfg, params, max_seq_len=4096, paged=True,
                           page_size=16, num_pages=1024, **kw)


def _spans(trace_dir, marker):
    """[(name, start, end, stats)] of the host thread holding ``marker``."""
    f = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    for plane in ProfileData.from_file(f).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats) if e.name.startswith(PROGRAM_SPANS)
                    else {}) for e in line.events]
            if any(n == marker for n, *_ in evs):
                return evs
    raise AssertionError(f"no host line holds a {marker!r} span")


def _inside(span, parents):
    _, s, e, _ = span
    return any(ps <= s and e <= pe for _, ps, pe, _ in parents)


def test_serve_spans_nest_in_the_profiler_trace(tmp_path):
    """A traced MinionS round through the paged engine holds every program
    span, each inside its layer's parent, with its counts as stats; the
    named programs' dispatches sit inside the spans that issue them."""
    cfg, params = _cfg_params()
    engine = _paged(cfg, params)
    runner = ProtocolRunner(EngineClient(engine, max_batch=2),
                            ScriptedRemote(seed=0))
    context = "\f".join(f"Acme page {i}. Costs rose." for i in range(3))
    spec = TaskSpec("minions", context + " The total revenue for fiscal "
                    "year 2015 was $12.5 million.",
                    "What was the total revenue for FY2015 (in millions "
                    "of USD)?",
                    MinionSConfig(max_rounds=1, num_tasks_per_round=2,
                                  pages_per_chunk=1, worker_max_tokens=4),
                    task_id=0)
    runner.run([spec])                   # compile outside the trace
    jobs = engine.usage.finished_jobs
    jax.profiler.start_trace(str(tmp_path))
    (result,) = runner.run([spec])
    jax.profiler.stop_trace()
    assert result.status == "ok"
    assert engine.usage.finished_jobs == 2 * jobs > 2

    spans = _spans(tmp_path, "protocol.step")
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    nesting = {
        "protocol.local": "protocol.step",
        "protocol.remote": "protocol.step",
        "scheduler.drain": "protocol.local",
        "engine.serve": "scheduler.drain",
        "engine.plan": "engine.serve",
        "engine.admit": "engine.serve",
        "engine.decode": "engine.serve",
        "engine.harvest": "engine.serve",
        "PjitFunction(paged_prefill)": "engine.admit",
        "PjitFunction(serve_decode)": "engine.decode",
    }
    for child, parent in nesting.items():
        assert by.get(child), f"no {child!r} span"
        for sp in by[child]:
            assert _inside(sp, by[parent]), (child, parent)
    (serve,) = by["engine.serve"]
    assert serve[3] == {"jobs": jobs, "slots": 2}
    assert by["scheduler.drain"][0][3] == {"jobs": jobs}
    assert set(by["engine.plan"][0][3]) == {"candidates", "planned",
                                             "deferred"}
    assert set(by["engine.admit"][0][3]) == {"rows", "levels",
                                              "suffix_tokens"}
    assert sum(sp[3]["rows"] for sp in by["engine.admit"]) == jobs
    assert sum(sp[3]["rows"] for sp in by["engine.harvest"]) == jobs
    # no anonymous program is dispatched from the serve path any more
    assert not any(n == "PjitFunction(<lambda>)" for n, *_ in spans)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
@pytest.mark.parametrize("slots", [4, 2])
def test_decode_steps_count_loop_iterations(paged, slots):
    """With equal budgets B and no row stopping early (no stop string, no
    EOS drawn), every wave of ``slots`` rows runs exactly B loop
    iterations, and each iteration serves one token per live row."""
    cfg, params = _cfg_params()
    engine = (_paged(cfg, params) if paged
              else InferenceEngine(cfg, params, max_seq_len=512))
    budget, prompts = 5, [f"steps job {i}" for i in range(4)]
    engine.serve(prompts, max_new_tokens=budget, stop="", slots=slots)
    u = engine.usage
    assert u.decode_tokens == len(prompts) * budget    # nobody stopped
    waves = len(prompts) // slots
    assert u.decode_steps == waves * budget
    assert u.decode_tokens == slots * u.decode_steps   # rows always full
    if paged:
        # 3 reads per harvest (one per wave) + the meter, once per call
        assert u.host_transfers == 3 * waves + 1


def test_jitted_programs_carry_stable_names():
    cfg, params = _cfg_params()
    engine = _paged(cfg, params)
    assert {f.__name__ for f in (
        engine._prefill, engine._prefill_hidden, engine._decode,
        engine._decode_loop, engine._serve_loop,
        engine._paged_prefill_fn)} == {
        "prefill", "prefill_hidden", "decode_step", "fused_decode",
        "serve_decode", "paged_prefill"}


def _scopes_in(lowered):
    names = set(re.findall(r'loc\("([^"]*)"',
                           lowered.as_text(debug_info=True)))
    return {s for s in SCOPES if any(f"/{s}/" in n for n in names)}


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_named_scopes_change_no_tokens(monkeypatch, backend):
    """Greedy tokens and first logits of the paged serve path are identical
    with the named scopes and with every ``jax.named_scope`` made a no-op,
    and the scopes are present in the compiled program only in the first
    case."""
    cfg, params = _cfg_params()
    cfg = cfg.replace(attention_backend=backend)
    prompts = [f"scope job {i}: " + "w " * (5 * i) for i in range(6)]

    def run():
        engine = _paged(cfg, params)
        toks = engine.serve(prompts, max_new_tokens=6, slots=4)
        engine._ensure_pool()
        t = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
        z = jnp.zeros((1, 1), jnp.int32)
        args = (params, t, jnp.arange(4)[None], z + 1, t * 0 + 1, t % 16,
                engine._kv_pool)
        logits, _ = engine._paged_prefill_fn(*args)
        loop_args = (params, jnp.zeros((2,), jnp.int32),
                     jnp.zeros((2,), bool), jnp.zeros((2, 8), jnp.int32),
                     jnp.zeros((2,), jnp.int32),
                     {"layers": engine._kv_pool,
                      "page_table": jnp.zeros((2, 2), jnp.int32),
                      "row_len": jnp.zeros((2,), jnp.int32)},
                     jnp.zeros((2, 2), jnp.uint32), jnp.ones((2,), bool),
                     jnp.ones((2,), jnp.int32), jnp.zeros((2,)),
                     jnp.zeros((0,), jnp.int32), jnp.zeros((2,), jnp.int32))
        scopes = (_scopes_in(engine._paged_prefill_fn.lower(*args))
                  | _scopes_in(engine._serve_loop.lower(*loop_args,
                                                        buf_len=8)))
        return toks, logits, scopes

    toks, logits, scopes = run()
    assert scopes == set(SCOPES)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    toks_off, logits_off, scopes_off = run()
    assert scopes_off == set()
    assert toks == toks_off
    assert jnp.array_equal(logits, logits_off)


def _pallas_names(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                yield from _pallas_names(v.jaxpr)


@pytest.mark.parametrize("kernel,args", [
    ("chunked_prefill", lambda: (jnp.zeros((1, 8, 4, 16)),
                                 jnp.zeros((1, 8, 1, 16)),
                                 jnp.zeros((1, 8, 1, 16)),
                                 jnp.zeros((1, 8), jnp.int32))),
    ("paged_prefill", lambda: (jnp.zeros((1, 8, 4, 16)),
                               jnp.zeros((4, 8, 1, 16)),
                               jnp.zeros((4, 8, 1, 16)),
                               jnp.zeros((1, 2), jnp.int32),
                               jnp.zeros((1, 8), jnp.int32))),
    ("gqa_decode", lambda: (jnp.zeros((1, 4, 16)),
                            jnp.zeros((1, 256, 1, 16)),
                            jnp.zeros((1, 256, 1, 16)),
                            jnp.asarray([3], jnp.int32))),
    ("paged_gqa_decode", lambda: (jnp.zeros((1, 4, 16)),
                                  jnp.zeros((4, 8, 1, 16)),
                                  jnp.zeros((4, 8, 1, 16)),
                                  jnp.zeros((1, 2), jnp.int32),
                                  jnp.asarray([3], jnp.int32))),
], ids=lambda x: x if isinstance(x, str) else "")
def test_pallas_calls_carry_their_trace_names(kernel, args):
    """Each ``pallas_call`` is named as the device trace names its op (its
    jitted wrapper's name), which the roofline readers match."""
    from repro.kernels import ops
    jaxpr = jax.make_jaxpr(getattr(ops, kernel))(*args()).jaxpr
    wrapper = {"chunked_prefill": "chunked_prefill_attention",
               "paged_prefill": "paged_prefill_attention",
               "gqa_decode": "gqa_decode_attention",
               "paged_gqa_decode": "paged_gqa_decode_attention"}[kernel]
    assert list(_pallas_names(jaxpr)) == [wrapper]
