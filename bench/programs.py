"""The serve loop on a device trace's op line.

The ops of ``trace.read_xplane`` (each chip's "XLA Ops" line) name the
HLO instruction, not the program it belongs to, but two of them mark the
loop of ``serve_decode``:

- the loop is one ``while`` op per run, over its body's ops (which the op
  line holds too, under their own names);
- each iteration runs the paged decode kernel once per attention layer
  (the Pallas call is named ``paged_gqa_decode_attention``), so the
  kernel's ops over ``dims.attn_layers`` are the loop's iterations.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from . import trace as trace_lib

DECODE_LOOP = "while"
DECODE_KERNEL = "paged_gqa_decode_attention"


def window_ops(ctx) -> Optional[Tuple[Sequence, float, float]]:
    """(ops of the first chip, window start, window end), or None for a run
    without a device trace."""
    s = ctx.trace_summary
    if s is None:
        return None
    return ctx.trace["device"][s["planes"][0]], s["lo"], s["hi"]


def _family_ops(ops: Sequence, family: str, lo: float, hi: float) -> List:
    return [o for o in ops if o[1] < hi and o[1] + o[2] > lo
            and trace_lib.op_family(o[0]) == family]


def decode_steps(ops: Sequence, lo: float, hi: float,
                 attn_layers: int) -> int:
    """Iterations of the serve loop that started inside [lo, hi]."""
    n = sum(1 for o in _family_ops(ops, DECODE_KERNEL, lo, hi)
            if o[1] >= lo)
    return n // attn_layers


def decode_loop_seconds(ops: Sequence, lo: float, hi: float) -> float:
    """Device seconds inside [lo, hi] covered by a serve loop's ``while``
    op (the union, so its body's ops are not counted again)."""
    return trace_lib.busy_ns(_family_ops(ops, DECODE_LOOP, lo, hi),
                             lo, hi) / 1e9
