"""The trace reduction on a hand-made trace and on a small recorded one."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parents[1] / "fixtures" / "trace_small.json"
PLANE = "/device:TPU:0"

# times in ns; the window is the first "protocol round" to the end of the
# last: [100, 1100]
HAND = {
    "device": {PLANE: [
        ["fusion.1", 50, 100],            # clipped to [100, 150]
        ["fusion.2", 200, 100],           # [200, 300]
        ["custom-call.7", 250, 150],      # overlaps: union [200, 400]
        ["fusion.1", 600, 200],           # [600, 800]
        ["copy.3", 1050, 100],            # clipped to [1050, 1100]
    ]},
    "host": [
        ["protocol round", 100, 500],     # [100, 600]
        ["local batch", 120, 400],        # [120, 520]
        ["protocol round", 600, 500],     # [600, 1100]
        ["PjitFunction(step)", 850, 100],  # [850, 950]
    ],
}


def test_window_busy_and_gaps_by_hand():
    lo, hi = trace.window(HAND)
    assert (lo, hi) == (100, 1100)
    ops = HAND["device"][PLANE]
    assert trace.busy_ns(ops, lo, hi) == 50 + 200 + 200 + 50
    assert trace.idle_gaps(ops, lo, hi) == [(150, 200), (400, 600),
                                            (800, 1050)]
    s = trace.summary(HAND)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(500e-9)


def test_idle_gaps_named_by_innermost_host_span():
    lo, hi = trace.window(HAND)
    # gap middles: 175 (local batch), 500 (local batch), 925 (the pjit
    # dispatch inside the second round)
    assert trace.host_labels(HAND["host"], [175, 500, 925, 1200]) == [
        "local batch", "local batch", "PjitFunction(step)", "no host span"]
    got = dict(trace.idle_by_host_span(HAND, PLANE, lo, hi))
    assert got == pytest.approx({"local batch": 250e-9,
                                 "PjitFunction(step)": 250e-9})


def test_top_ops_add_clones_and_clip():
    lo, hi = trace.window(HAND)
    got = dict(trace.top_ops(HAND["device"][PLANE], lo, hi))
    assert got == pytest.approx({"fusion": 350e-9, "custom-call": 150e-9,
                                 "copy": 50e-9})
    assert trace.kernel_seconds(HAND["device"][PLANE], r"^custom-call",
                                lo, hi) == pytest.approx(150e-9)


def test_no_device_plane_gives_no_summary():
    assert trace.summary({"device": {}, "host": HAND["host"]}) is None


def _brute_busy(ops, lo, hi, step):
    grid = np.zeros(int((hi - lo) // step) + 1, bool)
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) // step):int((b - lo) // step)] = True
    return grid.sum() * step


def test_recorded_trace_reduces_consistently():
    """A slice of a real one-chip trace (yi-6b.fanout on one TPU v5 lite,
    its first 1500 ops, op names cut to the HLO instruction name): busy
    time matches a brute-force
    bitmap, busy plus idle gaps is the window, the breakdown's idle seconds
    add up to the idle time, and every kernel time is inside busy time."""
    tr = trace.load(FIXTURE)
    s = trace.summary(tr)
    lo, hi = s["lo"], s["hi"]
    ops = tr["device"][s["planes"][0]]
    busy = trace.busy_ns(ops, lo, hi)
    assert busy == pytest.approx(_brute_busy(ops, lo, hi, 1000), rel=2e-2)
    idle = sum(e - a for a, e in trace.idle_gaps(ops, lo, hi))
    assert busy + idle == pytest.approx(hi - lo)
    named = sum(v for _, v in trace.idle_by_host_span(tr, s["planes"][0],
                                                      lo, hi, top=10**6))
    assert named == pytest.approx(idle / 1e9)
    assert 0 < s["busy_s"] <= s["window_s"]
    top = s["breakdown"]["device_ops"]
    assert len(top) <= 10 and top == sorted(top, key=lambda x: -x[1])
    assert sum(v for _, v in top) <= sum(
        min(st + d, hi) - max(st, lo) for _, st, d in ops
        if st < hi and st + d > lo) / 1e9 + 1e-9
