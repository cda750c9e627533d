"""Reduction of a profiler trace to the numbers the benchmark reports.

A trace is read into a plain dict (``read_xplane``), the same shape as the
small recorded trace under ``fixtures/`` that the tests reduce:

    {"device": {plane name: [[op name, start_ns, dur_ns], ...]},
     "host": [[span name, start_ns, dur_ns], ...]}

``device`` holds each chip's ops (the profiler's "XLA Ops" line of every
``/device:TPU:<n>`` plane) and ``host`` the spans of the host thread that
drove the window (the one that holds its "protocol round" spans), on the
same clock.  Everything below is arithmetic on
those lists.
"""
from __future__ import annotations

import glob
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_LINE = "python"
WINDOW_SPAN = "protocol round"


def read_xplane(trace_dir: str) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir`` as the plain dict."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = {"device": {}, "host": [], "lines": {}}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = out["device"].setdefault(plane.name, [])
            for line in plane.lines:
                out["lines"][f"{plane.name} | {line.name}"] = len(
                    list(line.events))
                if line.name == OPS_LINE:
                    ops += [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
        elif plane.name == HOST_PLANE:
            # the thread that opened the window's spans (named "python"
            # where the profiler knows it)
            lines = {line.name: [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events] for line in plane.lines}
            drivers = [n for n, evs in lines.items()
                       if any(e[0] == WINDOW_SPAN for e in evs)]
            for n in (drivers or [HOST_LINE]):
                out["host"] += lines.get(n, [])
            out["lines"].update({f"{plane.name} | {n}": len(lines[n])
                                 for n in drivers})
    return out


def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def window(trace: dict, span: str = WINDOW_SPAN) -> Tuple[float, float]:
    """(start_ns, end_ns) from the first ``span`` to the end of the last."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == span]
    if not spans:
        raise ValueError(f"trace holds no {span!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clip(ops: Sequence, lo: float, hi: float) -> List[Tuple[float, float]]:
    return sorted((max(s, lo), min(s + d, hi)) for _, s, d in ops
                  if s < hi and s + d > lo)


def _union(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Sequence, lo: float, hi: float) -> float:
    """Length of the union of the ops' intervals inside [lo, hi]."""
    return sum(e - s for s, e in _union(_clip(ops, lo, hi)))


def idle_gaps(ops: Sequence, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The intervals of [lo, hi] in which no op ran."""
    gaps, t = [], lo
    for s, e in _union(_clip(ops, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def host_labels(host: Sequence, times: Sequence[float]) -> List[str]:
    """For each time, the innermost host span open then (spans of one thread
    nest, so it is the latest-starting one that covers it), or "no host
    span".  One sweep over spans and times in time order."""
    spans = sorted(host, key=lambda e: (e[1], -e[2]))
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = ["no host span"] * len(times)
    stack: List[Tuple[float, str]] = []      # (end, name) of open spans
    k = 0
    for i in order:
        t = times[i]
        while k < len(spans) and spans[k][1] <= t:
            n, s, d = spans[k]
            while stack and stack[-1][0] < s:
                stack.pop()
            stack.append((s + d, n))
            k += 1
        while stack and stack[-1][0] < t:
            stack.pop()
        if stack:
            out[i] = stack[-1][1]
    return out


def idle_by_host_span(trace: dict, plane: str, lo: float, hi: float,
                      top: int = 10) -> List[List]:
    """Idle device seconds summed by the host span open in each gap's
    middle, longest first."""
    gaps = idle_gaps(trace["device"][plane], lo, hi)
    names = host_labels(trace["host"], [(s + e) / 2 for s, e in gaps])
    by: Dict[str, float] = {}
    for (s, e), name in zip(gaps, names):
        by[name] = by.get(name, 0.0) + (e - s) / 1e9
    return [[n, v] for n, v in sorted(by.items(), key=lambda kv: -kv[1])][:top]


def op_family(name: str) -> str:
    """An op's HLO instruction name without XLA's numeric suffix, so that
    the clones of one op add up.  The TPU profiler names an op by its HLO
    text (``%paged_prefill_attention.3 = f32[...] custom-call(...)``, a
    Pallas kernel under the name of its jitted wrapper); a bare name
    (``fusion.12``) is taken as it is."""
    m = re.match(r"%?([^\s=]+?)(?:\.\d+)*(?:\s*=|$)", name)
    return m.group(1) if m else name


def top_ops(ops: Sequence, lo: float, hi: float, top: int = 10
            ) -> List[List]:
    """Device seconds summed by op family inside [lo, hi], largest first."""
    by: Dict[str, float] = {}
    for n, s, e in ((n, max(s, lo), min(s + d, hi)) for n, s, d in ops):
        if e > s:
            f = op_family(n)
            by[f] = by.get(f, 0.0) + (e - s) / 1e9
    return [[n, v] for n, v in sorted(by.items(), key=lambda kv: -kv[1])][:top]


def kernel_seconds(ops: Sequence, pattern: str, lo: float, hi: float
                   ) -> float:
    """Device seconds of the ops whose family (``op_family``) matches
    ``pattern`` (a regular expression) inside [lo, hi]."""
    rx = re.compile(pattern)
    return sum(max(0.0, min(s + d, hi) - max(s, lo))
               for n, s, d in ops if rx.search(op_family(n))) / 1e9


def summary(trace: dict) -> Optional[dict]:
    """Window, busy time averaged over the chips, and the breakdown (None
    for a trace that holds no TPU plane)."""
    planes = sorted(trace["device"])
    if not planes:
        return None          # a CPU rehearsal: no chip, no device numbers
    lo, hi = window(trace)
    busy = sum(busy_ns(trace["device"][p], lo, hi) for p in planes)
    first = planes[0]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / len(planes) / 1e9,
        "breakdown": {
            "device_ops": top_ops(trace["device"][first], lo, hi),
            "idle_gaps": idle_by_host_span(trace, first, lo, hi),
        },
        "lo": lo, "hi": hi, "planes": planes,
    }


def head(trace: dict, n: int) -> dict:
    """The first ``n`` device ops of the window on the first chip and the
    host spans that overlap them, the window span cut to that stretch: a
    small fixture with the shape of a whole trace."""
    if not trace["device"]:
        return {"device": {}, "host": trace["host"]}
    plane = sorted(trace["device"])[0]
    lo, _ = window(trace)
    ops = sorted((o for o in trace["device"][plane] if o[1] >= lo),
                 key=lambda o: o[1])[:n]
    if not ops:
        return {"device": {plane: []}, "host": []}
    hi = max(s + d for _, s, d in ops)
    host = []
    for name, s, d in trace["host"]:
        if s < hi and s + d > lo:
            if name == WINDOW_SPAN:
                s, d = max(s, lo), min(s + d, hi) - max(s, lo)
            host.append([name, s, d])
    return {"device": {plane: ops}, "host": host}
