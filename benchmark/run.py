#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, and print the result.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with the keys `correct`,
`attempted`, `failed`, `metrics`, `device` (with --trace 1 also `breakdown`)
and, last, `compared`: every number the comparison with the plain reference
looked at, beside its limit. The same numbers close standard error, after a
`[bench] diag` line of counts for whoever reads the run. Without a TPU (or with fewer chips than
the cell asks for) it exits 2 and prints no result; it never falls back to the
CPU. `--rehearse` runs the same path at a tiny size on whatever JAX finds,
and names every metric `<name>.cpu_rehearsal_not_a_device_number`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T_IMPORT = time.perf_counter()


def _process_age_s() -> float:
    """Seconds since this process started, from /proc; 0 where unreadable."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_T_PROCESS = _T_IMPORT - _process_age_s()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size, any platform, metrics renamed")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the traced slice into")
    args = ap.parse_args(argv)
    if not 0 <= args.seed <= 1 << 33:
        ap.error("--seed is a whole number from 0 to a little over 2**31")
    sys.path.insert(0, REPO)
    from benchmark import cell

    seconds = args.seconds
    if seconds is None:
        seconds = int(cell.load_manifest(REPO)["run_seconds"])
    if seconds < 1:
        ap.error("--seconds must be at least 1")
    try:
        result = cell.run_cell(
            args.workload, args.seed, seconds, bool(args.trace),
            rehearse=args.rehearse, t_process=_T_PROCESS, repo=REPO,
            keep_trace=args.keep_trace)
    except cell.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
