"""The plain reference against the engine's paged prefill and decode, on the
CPU at a small size in float32, where the two agree to rounding.  One job of
the second serve call is admitted on pages the radix cache kept from the
first."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, model, reference
from bench.spec import Spec

LLAMA = Spec().arch_module("llama")
DIMS = LLAMA.Dims(name="ref-test", layers=2, d_model=64, heads=4,
                  kv_heads=2, head_dim=16, d_ff=96, vocab=300,
                  rope_theta=10000.0, norm_eps=1e-5, dtype="float32",
                  page_size=8, num_pages=64, slots=3, max_seq_len=256)


@pytest.fixture(scope="module")
def params():
    return model.make_weights(LLAMA, DIMS, 11)


def test_reference_matches_program_forward(params):
    """Full-sequence logits at every position: the program's dense forward
    (reference attention) and the plain reference."""
    from repro.models import transformer as T
    cfg = LLAMA.program_config(DIMS, "reference")
    toks = np.random.default_rng(0).integers(0, 256, size=37)
    prog = np.asarray(T.forward(params, cfg, {"tokens": jnp.asarray(toks)[None]})[0])
    with jax.default_matmul_precision("highest"):
        ref = LLAMA.logits(params, DIMS, toks, list(range(37)))
    np.testing.assert_allclose(prog, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_reference_matches_served_paged_tokens(params, backend):
    """Greedy jobs served by the paged engine (prefill of the novel suffix,
    decode through the page pool), the second call reusing the first's
    pages: every served token is the reference's best, to rounding."""
    from repro.serving import InferenceEngine
    eng = InferenceEngine(LLAMA.program_config(DIMS, backend), params,
                          tokenizer=harness.recording_tokenizer(),
                          max_seq_len=DIMS.max_seq_len, paged=True,
                          page_size=DIMS.page_size,
                          num_pages=DIMS.num_pages)
    jobs, state = harness.attach_recorder(eng, jax.profiler.TraceAnnotation)
    shared = "instructions shared by every worker job: " * 2
    eng.serve([shared + "chunk one", shared + "chunk two"],
              max_new_tokens=6, temperature=0.0, slots=DIMS.slots)
    hits = eng.usage.prefix_hit_tokens
    state["call"] = 1
    eng.serve([shared + "chunk three", "unshared prompt"],
              max_new_tokens=6, temperature=0.0, slots=DIMS.slots)
    assert eng.usage.prefix_hit_tokens > hits        # radix pages reused
    assert len(jobs) == 4 and all(len(j.served) == 6 for j in jobs)
    for j in jobs:
        rows = reference.served_rows(len(j.prompt), len(j.served))
        ref = LLAMA.logits(params, DIMS, j.prompt + j.served, rows)
        assert reference.served_gaps(ref, j.served).max() < 1e-4


def test_gap_reads_the_served_token():
    ref = np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 2.5]])
    np.testing.assert_allclose(reference.served_gaps(ref, [1, 2]), [0.0, 0.5])
    assert reference.served_rows(5, 3) == [4, 5, 6]


def test_fp8_control_reads_wider_gaps(params):
    """The control (every matrix-product operand rounded to float8) moves
    the reference's choices: the gap of the token it puts first is well
    above the float32 program's, which is rounding."""
    dims = dataclasses.replace(DIMS)
    toks = np.random.default_rng(1).integers(0, 256, size=48)
    rows = list(range(8, 47))
    ref = LLAMA.logits(params, dims, toks, rows)
    ctl = LLAMA.logits(params, dims, toks, rows, quant="fp8")
    gaps = reference.served_gaps(ref, ctl.argmax(-1))
    assert gaps.max() > 1e-2
    assert np.abs(ctl - ref).max() > 10 * np.abs(ref).max() * 1e-4
