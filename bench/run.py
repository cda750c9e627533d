#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload yi-6b.fanout --seed 7 --seconds 30 --trace 0

Loads, warms up, measures for ``--seconds`` (whole closed-loop calls), checks
the served tokens against the plain reference, and prints one JSON object.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="also write the head of the traced window here")
    args = ap.parse_args()
    from bench.harness import run
    from bench.spec import Spec
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 t_start=T_START, spec=Spec(ROOT), keep_trace=args.keep_trace)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
