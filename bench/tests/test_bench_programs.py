"""The readers of the serve loop, on a hand-made trace: the decode loop's
``while`` over its own body, its iterations counted by the paged decode
kernel."""
import types

import pytest

from bench import programs, trace, work
from bench.spec import Spec

PLANE = "/device:TPU:0"
LAYERS = 2


def _op(family, start, dur):
    """An op event as the TPU trace names it: the HLO instruction's text."""
    return [f"%{family}.3 = bf16[16,4096]{{1,0}} {family}()", start, dur]


def _loop(start, iterations):
    """A ``while`` op of 100 ns per iteration, and inside each iteration
    one kernel op and one fusion per layer (counted again under their own
    names, as the op line does)."""
    ops = [_op("while", start, 100 * iterations)]
    for i in range(iterations):
        for layer in range(LAYERS):
            t = start + 100 * i + 40 * layer
            ops.append(_op("paged_gqa_decode_attention", t, 20))
            ops.append(_op("fusion", t + 20, 20))
    return ops


# window [0, 2000]: prefill ops, a copy, the loop twice (3 + 1
# iterations), and prefill ops running past the window's end
HAND = {
    "device": {PLANE: [
        _op("fusion", 200, 600),
        _op("paged_prefill_attention", 500, 400),
        _op("copy", 900, 50),
        *_loop(1000, 3),
        *_loop(1500, 1),
        _op("fusion", 1900, 300),
    ]},
    "host": [["protocol round", 0, 2000]],
}


def _ctx(tr):
    counts = work.Counts(prompt_tokens=10, prompt_ctx=55,
                         decode_forwards=14, decode_ctx=0, logit_rows=0,
                         decode_kv_reads=0)
    return types.SimpleNamespace(
        trace=tr, trace_summary=trace.summary(tr),
        dims=types.SimpleNamespace(attn_layers=LAYERS, slots=4),
        counts=counts,
        log=lambda msg: None)


def _read(name, ctx):
    return Spec().metric_module(name).read(ctx)


def test_loop_iterations_and_while_union():
    ops = HAND["device"][PLANE]
    assert programs.decode_steps(ops, 0, 2000, LAYERS) == 4
    # the while ops alone, body ops not counted a second time
    assert programs.decode_loop_seconds(ops, 0, 2000) == pytest.approx(
        400e-9)
    # a window cut inside the first loop: only iterations started in it
    assert programs.decode_steps(ops, 1150, 2000, LAYERS) == 2


def test_decode_step_ms_reads_loop_time_per_iteration():
    assert _read("decode_step_ms", _ctx(HAND)) == pytest.approx(
        1e3 * 400e-9 / 4)


def test_decode_row_occupancy_reads_served_forwards_over_rows():
    # 14 served forwards over 4 slots x 4 iterations
    assert _read("decode_row_occupancy", _ctx(HAND)) == pytest.approx(
        100 * 14 / 16)


@pytest.mark.parametrize("name", ["decode_step_ms", "decode_row_occupancy"])
def test_readers_stay_silent_without_their_ops(name):
    assert _read(name, types.SimpleNamespace(trace_summary=None)) is None
    bare = {"device": {PLANE: [_op("copy", 10, 10)]},
            "host": [["protocol round", 0, 100]]}
    assert _read(name, _ctx(bare)) is None
