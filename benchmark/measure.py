#!/usr/bin/env python3
"""Run a list of cells one after another, each in a process of its own, and
keep every result line: the builder's tool for the sets of runs that a
bound is set from. This parent never touches JAX, so each child gets the chip.

    python3 benchmark/measure.py --tag set1 --seconds 30 \\
        basic-5k.backlog:2100000001:0 basic-5k.backlog:2100000002:1 ...

Each item is workload:seed:trace. Lines go to chiprun_out/results/<tag>.jsonl
(with workload, seed, trace, exit code and wall seconds added), the end of
each run's standard error to chiprun_out/results/<tag>.err, and one short
summary line per run to standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--control", default=None,
                    help="run control.py with this fault instead of run.py")
    ap.add_argument("items", nargs="+")
    args = ap.parse_args()
    out_dir = os.path.join(REPO, "chiprun_out", "results")
    os.makedirs(out_dir, exist_ok=True)
    worst = 0
    for item in args.items:
        workload, seed, trace = item.split(":")
        if args.control:
            cmd = [sys.executable, os.path.join(REPO, "benchmark/control.py"),
                   "--workload", workload, "--seed", seed, "--seconds",
                   str(args.seconds), "--fault", args.control]
        else:
            cmd = [sys.executable, os.path.join(REPO, "benchmark/run.py"),
                   "--workload", workload, "--seed", seed, "--seconds",
                   str(args.seconds), "--trace", trace]
        t = time.time()
        p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
        wall = time.time() - t
        lines = p.stdout.strip().splitlines()
        try:
            rec = json.loads(lines[-1]) if lines else {}
        except ValueError:
            rec = {"unparsed": lines[-1][:500]}
        diag = [ln for ln in p.stderr.splitlines()
                if ln.startswith("[bench] diag ")]
        if diag:
            rec["diag"] = json.loads(diag[-1][len("[bench] diag "):])
        rec.update(workload=workload, seed=int(seed), trace=int(trace),
                   rc=p.returncode, wall_s=round(wall, 1))
        with open(os.path.join(out_dir, f"{args.tag}.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        with open(os.path.join(out_dir, f"{args.tag}.err"), "a") as f:
            f.write(f"==== {item} rc={p.returncode}\n{p.stderr[-3000:]}\n")
        short = {k: round(v["value"], 3)
                 for k, v in rec.get("metrics", {}).items()}
        print(json.dumps({"item": item, "rc": p.returncode,
                          "wall_s": round(wall, 1),
                          "correct": rec.get("correct"),
                          "failed_by": rec.get("failed_by"),
                          "metrics": short}), flush=True)
        if not args.control:
            worst = max(worst, p.returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main())
