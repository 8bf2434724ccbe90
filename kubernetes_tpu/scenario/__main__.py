"""CLI for the scenario engine.

    python -m kubernetes_tpu.scenario list
    python -m kubernetes_tpu.scenario generate zone_outage --seed 3 \
        --out /tmp/zo.jsonl [--param outage_len=8]
    python -m kubernetes_tpu.scenario replay /tmp/zo.jsonl --speed 3
    python -m kubernetes_tpu.scenario replay zone_outage --speed 3
    python -m kubernetes_tpu.scenario fuzz --budget 120 --seed 0 \
        --file-to tests/regression_traces
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from kubernetes_tpu.scenario.fuzz import fuzz
from kubernetes_tpu.scenario.generators import GENERATORS, generate
from kubernetes_tpu.scenario.replay import replay_trace
from kubernetes_tpu.scenario.trace import load_trace, save_trace


def _params(kvs: list[str]) -> dict:
    out = {}
    for kv in kvs or []:
        k, _, v = kv.partition("=")
        try:
            out[k] = json.loads(v)
        except ValueError:
            out[k] = v
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="kubernetes_tpu.scenario")
    sub = ap.add_subparsers(dest="cmd", required=True)

    sub.add_parser("list", help="catalog the named regimes")

    g = sub.add_parser("generate", help="params+seed -> trace file")
    g.add_argument("regime", choices=sorted(GENERATORS))
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--out", required=True)
    g.add_argument("--param", action="append", default=[],
                   help="override, e.g. --param outage_len=8")

    r = sub.add_parser("replay", help="replay a trace file or regime")
    r.add_argument("trace", help="path to a trace, or a regime name")
    r.add_argument("--seed", type=int, default=0)
    r.add_argument("--speed", type=float, default=3.0)
    r.add_argument("--timeout", type=float, default=180.0)
    r.add_argument("--param", action="append", default=[])

    f = sub.add_parser("fuzz", help="adversarial parameter search")
    f.add_argument("--budget", type=float, default=120.0,
                   help="wall-clock seconds")
    f.add_argument("--seed", type=int, default=0)
    f.add_argument("--speed", type=float, default=3.0)
    f.add_argument("--regime", action="append", default=[],
                   help="restrict to these regimes (default: all)")
    f.add_argument("--file-to", default=None,
                   help="directory to file SLO-breaching traces into")

    args = ap.parse_args(argv)
    if args.cmd == "list":
        for name in sorted(GENERATORS):
            reg = GENERATORS[name]
            print(f"{name}: defaults={reg.defaults} "
                  f"fuzz_bounds={reg.bounds}")
        return 0
    if args.cmd == "generate":
        tr = generate(args.regime, _params(args.param), seed=args.seed)
        save_trace(tr, args.out)
        print(f"{args.out}: {len(tr.events)} events, "
              f"{tr.duration():.1f} trace-s, counts={tr.counts()}")
        return 0
    if args.cmd == "replay":
        if os.path.exists(args.trace):
            tr = load_trace(args.trace)
        else:
            tr = generate(args.trace, _params(args.param),
                          seed=args.seed)
        rep = replay_trace(tr, speed=args.speed,
                           timeout_s=args.timeout)
        print(json.dumps(rep, indent=1, default=str))
        return 0 if rep["ok"] else 1
    if args.cmd == "fuzz":
        rep = fuzz(regimes=args.regime or None, budget_s=args.budget,
                   seed=args.seed, speed=args.speed, out_dir=args.file_to,
                   log=lambda s: print(s, flush=True))
        print(json.dumps({k: v for k, v in rep.items() if k != "rows"},
                         indent=1, default=str))
        return 0
    return 2


if __name__ == "__main__":
    sys.exit(main())
