#!/usr/bin/env python3
"""Readings behind the output limit: the program on many seeds and the
control on the same served tokens, in one process on the chip.

    python3 bench/control.py --workload yi-6b.fanout --seconds 1 \
        --seeds 1 2 3 --out chiprun_out/control.jsonl

For each seed it runs the cell as ``bench/run.py`` would (set-up, warm-up,
a short window at the cell's own load, the output check) and also judges
the control: the plain reference at the next precision below the served
one (float8_e4m3fn for bfloat16), teacher-forced on the same prompts and
served tokens, its first-choice token at each position put through the
same check in the program's place.  The program's ``max_logit_gap`` over
the seeds is the limit's lower reading, the control's the upper, and the
control has to come out not correct.  The benchmark's own runs never run
this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from bench.harness import run
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = run(args.workload, seed, args.seconds, False, t_start=t0,
                control=True)
        rec = {"workload": args.workload, "seed": seed,
               "correct": r["correct"], "check": r["check"],
               "control": r["control"],
               "memory_peak_bytes": r["device"]["memory_peak_bytes"],
               "wall_s": time.perf_counter() - t0}
        with out.open("a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
