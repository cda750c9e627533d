"""A whole run of the harness on the CPU at a tiny size, and the same run
with the timed path broken underneath: ``correct`` has to come out false
for each fault a serving cell can have."""
import time

import numpy as np
import pytest

from bench import harness

CELL = "yi-6b.fanout"


def _run(rehearse, **kw):
    return harness.run(CELL, 20260101, 0.01, False, t_start=time.perf_counter(),
                       rehearse=rehearse, **kw)


def test_sound_run_is_correct(tiny_traffic, rehearse):
    out = _run(rehearse)
    assert out["correct"], out["check"]
    assert out["check"]["max_logit_gap"]["value"] < 1e-3
    assert out["check"]["jobs_unanswered"]["value"] == 0
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {"tasks_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"


def test_traced_run_reports_per_layer_counters(tiny_traffic, rehearse,
                                               tmp_path):
    keep = tmp_path / "trace.json"
    out = harness.run(CELL, 7, 0.01, True, t_start=time.perf_counter(),
                      rehearse=rehearse, keep_trace=str(keep))
    assert out["correct"]
    # the CPU has no device plane: only the program's counters report
    assert set(out["metrics"]) == {"prefix_hit_share",
                                   "host_transfers_per_job",
                                   "window_compiles"}
    assert 0 < out["metrics"]["prefix_hit_share"]["value"] < 100
    import json
    tr = json.loads(keep.read_text())
    assert any(n == "protocol round" for n, _, _ in tr["host"])
    assert any(n == "local batch" for n, _, _ in tr["host"])


def test_control_is_not_correct(tiny_traffic, rehearse, monkeypatch):
    """The control (the reference at a lower precision, in the program's
    place) goes through the same check and has to fail it, while the
    program on the same run passes.  The chip's cells serve bfloat16, whose
    control is float8; the tiny float32 model here takes the same one,
    since one step down (bfloat16) moves its few logits too little."""
    monkeypatch.setitem(harness.CONTROL_QUANT, "float32", "fp8")
    out = _run(rehearse, control=True)
    assert out["correct"], out["check"]
    ctl = out["control"]
    assert not ctl["correct"], ctl["check"]
    assert ctl["check"]["max_logit_gap"]["value"] > \
        ctl["check"]["max_logit_gap"]["limit"]
    assert list(out)[-1] == "check"


def test_documents_follow_the_seed_and_the_stream():
    """Every seed, stream and call reads documents of its own; the sizes
    (page bytes, queries, knobs) are the mix's, whatever the seed."""
    from bench.spec import Spec
    from bench.traffic import call_specs
    mix = Spec().traffic("fanout")
    a = call_specs(mix, 11, "window", 0)
    contexts = {s.context for s in a}
    for other in (call_specs(mix, 12, "window", 0),
                  call_specs(mix, 11, "warmup", 0),
                  call_specs(mix, 11, "window", 1)):
        assert not contexts & {s.context for s in other}
        assert [s.cfg for s in other] == [s.cfg for s in a]
        assert [s.query for s in other] == [s.query for s in a]
        assert [len(s.context.encode()) for s in other] == \
            [len(s.context.encode()) for s in a]
    assert [s.context for s in call_specs(mix, 11, "window", 0)] == \
        [s.context for s in a]
    big = call_specs(mix, 2**31 + 5, "window", 0)
    assert len(big) == mix["concurrent_tasks"]


def _altered_token(monkeypatch):
    """A served token altered where the engine samples it."""
    from repro.serving import engine
    orig = engine.sample_rows

    def sample_rows(logits, keys, temperature):
        tok = orig(logits, keys, temperature)
        return (tok + 7) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_rows", sample_rows)


def _stale_decode_state(monkeypatch):
    """A decode step that returns the page pool unchanged (its K/V never
    written)."""
    from repro.models import transformer as T
    orig = T._paged_decode_layer

    def layer(lp, lc, *a, **kw):
        x, _ = orig(lp, lc, *a, **kw)
        return x, lc

    monkeypatch.setattr(T, "_paged_decode_layer", layer)


def _half_the_jobs(monkeypatch):
    """Half of each batch left out: the engine answers only its first
    half of the jobs."""
    from repro.serving.engine import InferenceEngine
    orig = InferenceEngine._serve_paged

    def serve_paged(self, prompt_ids, n, budgets, *a, **kw):
        half = max(n // 2, 1)
        out = orig(self, prompt_ids[:half], half, budgets[:half], *a, **kw)
        return out + [""] * (n - half)

    monkeypatch.setattr(InferenceEngine, "_serve_paged", serve_paged)


@pytest.mark.parametrize("fault", [_altered_token, _stale_decode_state,
                                   _half_the_jobs])
def test_broken_path_is_not_correct(tiny_traffic, rehearse, monkeypatch,
                                    fault):
    fault(monkeypatch)
    out = _run(rehearse)
    assert not out["correct"], out["check"]
    assert np.isfinite(out["check"]["max_logit_gap"]["value"]) or \
        out["check"]["jobs_unanswered"]["value"] > 0


def test_pages_have_the_mix_size_and_their_own_letter():
    from repro.core.chunking import PAGE_SEP
    from bench.traffic import fixed_pages
    doc = PAGE_SEP.join(["Acme Annual Report \u2014 page 1. Short.",
                         "Acme Annual Report \u2014 page 2. " + "x" * 500])
    pages = fixed_pages(doc, 120, 3).split(PAGE_SEP)
    assert [len(p.encode()) for p in pages] == [120, 120]
    assert [p[0] for p in pages] == ["D", "E"]
    assert "Short. Short." in pages[0]
