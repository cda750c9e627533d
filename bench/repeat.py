#!/usr/bin/env python3
"""Run one cell several times, one process after another, and keep what
each run printed: the way the bounds and the output limits are measured.

    python3 bench/repeat.py --workload yi-6b.fanout --seconds 30 \
        --seeds 11 12 13 --trace 0 0 1 --out chiprun_out/yi.jsonl \
        --keep-trace-dir chiprun_out

Each run is ``bench/run.py`` in a process of its own (a chip belongs to one
process at a time); this script never imports JAX.  One JSON line per run
goes to ``--out``: the seed, the exit code, the wall time, the result line
and the end of standard error.
"""
import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, nargs="*", default=[],
                    help="0 or 1 per seed (default 0)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep-trace-dir", metavar="DIR",
                    help="traced runs also write their reduced trace here")
    args = ap.parse_args()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    traces = args.trace + [0] * (len(args.seeds) - len(args.trace))
    worst = 0
    for seed, tr in zip(args.seeds, traces):
        cmd = [sys.executable, str(RUN), "--workload", args.workload,
               "--seed", str(seed), "--seconds", str(args.seconds),
               "--trace", str(tr)]
        if tr and args.keep_trace_dir:
            cmd += ["--keep-trace",
                    f"{args.keep_trace_dir}/{args.workload}.{seed}.json"]
        t0 = time.perf_counter()
        p = subprocess.run(cmd, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        lines = p.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except json.JSONDecodeError:
            result = None
        rec = {"workload": args.workload, "seed": seed, "trace": tr,
               "rc": p.returncode, "wall_s": wall, "result": result,
               "stderr": p.stderr[-6000:]}
        with out.open("a") as f:
            f.write(json.dumps(rec) + "\n")
        brief = {k: result.get(k) for k in ("correct", "metrics", "check")} \
            if result else None
        print(f"seed {seed} trace {tr} rc {p.returncode} wall {wall:.1f}s "
              f"{json.dumps(brief)}", flush=True)
        if p.returncode or not (result or {}).get("correct"):
            print(p.stderr[-1500:], flush=True)
        worst = max(worst, p.returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main())
