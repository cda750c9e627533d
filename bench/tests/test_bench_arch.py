"""The architecture seam: ``bench/arch/llama.py`` reproduces what the
benchmark made and counted before its code moved there, and a configuration
whose ``model_type`` names a module that exists only as a new file is run
through that module's weights, reference and work counts."""
import hashlib
import json
import shutil
import time
import types

import jax
import numpy as np

from bench import harness, model, work
from bench.spec import ROOT, Spec
from bench.tests.test_bench_reference import DIMS as REF_DIMS
from bench.tests.test_bench_work import DIMS as WORK_DIMS

LLAMA = Spec().arch_module("llama")

# Recorded on the CPU from the tree before the move (bench/model.py,
# bench/reference.py and bench/work.py), with the digest and the inputs
# below: the weights of REF_DIMS from seed 11, reference logits at three
# rows of a fixed sequence, and the work of two sets of counts.
WEIGHTS_SHA256 = \
    "e1d55af83aedf21e033f90a1f273f2aff0501a1430d4cbe294a852a7fe6da484"
TOKENS = [(7 * i + 3) % 256 for i in range(40)]
ROWS, COLS = [3, 17, 39], [0, 1, 150, 299]
LOGITS = [[-0.35119879245758057, -0.7947986721992493, 1.2807177305221558,
           -0.44225841760635376],
          [-1.701078176498413, -0.28591105341911316, 0.5446161031723022,
           0.20005959272384644],
          [0.09154503792524338, -0.5585034489631653, 1.7220189571380615,
           -0.5618120431900024]]
ARGMAX = [233, 246, 108]
HAND_COUNTS = work.Counts(prompt_tokens=6, prompt_ctx=19, decode_forwards=3,
                          decode_ctx=17, logit_rows=5, decode_kv_reads=14)
HAND_WORK = (23840, (1216, 576), (1088, 640))
YI_COUNTS = work.Counts(prompt_tokens=186328, prompt_ctx=271000000,
                        decode_forwards=4032, decode_ctx=11900000,
                        logit_rows=4096, decode_kv_reads=3100000)
YI_WORK = (1130232597708800, (71041024000000, 54950363136),
           (3119513600000, 102637764608))


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_weights_are_the_recorded_ones():
    assert _digest(model.make_weights(LLAMA, REF_DIMS, 11)) == WEIGHTS_SHA256


def test_reference_logits_are_the_recorded_ones():
    """Bit for bit on the CPU they were recorded on; the tolerance only
    allows another CPU's order of float32 accumulation."""
    params = model.make_weights(LLAMA, REF_DIMS, 11)
    out = LLAMA.logits(params, REF_DIMS, TOKENS, ROWS)
    assert out.argmax(-1).tolist() == ARGMAX
    np.testing.assert_allclose(out[:, COLS], LOGITS, rtol=1e-6, atol=1e-6)


def test_work_is_the_recorded_one():
    config = Spec().config("yi-6b")
    for dims, counts, want in (
            (WORK_DIMS, HAND_COUNTS, HAND_WORK),
            (LLAMA.load_dims(config), YI_COUNTS, YI_WORK)):
        ctx = types.SimpleNamespace(dims=dims, counts=counts)
        assert (LLAMA.step_flops(ctx), LLAMA.prefill_attn_work(ctx),
                LLAMA.decode_attn_work(ctx)) == want


TOY_TAIL = '''

_LLAMA_LOGITS = logits
CALLS = []


def logits(params, dims, tokens, rows, *, quant=None):
    CALLS.append(len(tokens))
    return _LLAMA_LOGITS(params, dims, tokens, rows, quant=quant)
'''


def test_new_architecture_runs_from_new_files(tiny_traffic, rehearse,
                                              tmp_path, monkeypatch):
    """A root that adds ``bench/arch/toy.py`` (llama's module with a
    reference that counts its calls), a config of ``model_type`` "toy", a
    cell and its limits: the harness takes the toy's module, and the toy's
    reference judges the run."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = tmp_path / "bench"
    (bench / "arch/toy.py").write_text(
        (ROOT / "bench/arch/llama.py").read_text() + TOY_TAIL)
    cfg = json.loads((ROOT / "bench/configs/yi-6b.json").read_text())
    (bench / "configs/toy.json").write_text(json.dumps(
        dict(cfg, name="toy", model_type="toy")))
    shutil.copy(ROOT / "bench/limits/yi-6b.fanout.json",
                bench / "limits/toy.fanout.json")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["configs"].append({"name": "toy", "source": "x",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "x"})
    data["workloads"].append({"name": "toy.fanout", "config": "toy",
                              "traffic": "fanout", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    spec = Spec(tmp_path)
    loaded = {}
    arch_module = spec.arch_module

    def recording_arch_module(model_type):
        loaded[model_type] = arch_module(model_type)
        return loaded[model_type]

    monkeypatch.setattr(spec, "arch_module", recording_arch_module)
    out = harness.run("toy.fanout", 20260101, 0.01, False,
                      t_start=time.perf_counter(), spec=spec,
                      rehearse=rehearse)
    assert out["correct"], out["check"]
    assert list(loaded) == ["toy"]
    assert loaded["toy"].__file__ == str(bench / "arch/toy.py")
    assert loaded["toy"].CALLS, "the toy's reference was never called"
