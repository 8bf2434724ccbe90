#!/usr/bin/env python3
"""The control: one run of a cell with one guarantee broken underneath.

    python3 benchmark/control.py --workload <name> --seed <n> --seconds <s> --fault <fault>

The same path as run.py with one fault, a file of benchmark/faults/, planted
under it: between the scheduler and the hub, in its queue, or in the traffic.
The run has to come out as NOT correct: exit 0 if it did, 1 if the
comparison let the fault through. The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str] | None = None) -> int:
    sys.path.insert(0, REPO)
    from benchmark import cell, compare

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=5)
    ap.add_argument("--fault", required=True, choices=compare.names_in("faults"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    try:
        result = cell.run_cell(args.workload, args.seed, args.seconds, False,
                               rehearse=args.rehearse, fault=args.fault)
    except cell.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    failed_by = {k: c["value"] for k, c in result["compared"].items()
                 if c["value"] > c["limit"]}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "fault": args.fault, "correct": result["correct"],
                      "failed_by": failed_by, "device": result["device"]}),
          flush=True)
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
