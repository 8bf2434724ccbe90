"""Adversarial workload fuzzer: search generator parameter space for
the trace that maximizes trace-time p99 time-to-bind, and auto-file
SLO-breaching traces as permanent regression gates.

Search shape: seeded random sampling over each regime's declared
parameter bounds, then coordinate-descent refinement around the worst
cell found — perturb one parameter at a time toward whichever direction
worsens the objective, keep improvements, stop on the wall-clock
budget. Every candidate replays against the SAME jit shapes
(generators.REPLAY_CONFIG), so a whole search pays XLA compilation
once.

Filing: a candidate whose trace-time stats breach its regime's intent
SLO is written to ``tests/regression_traces/`` as git-diffable
JSON-lines. The filed trace keeps the violated ``slo`` (the evidence —
replaying it reproduces the breach) and gains a ``gate``: the enforced
ratchet bound, set to the observed value × headroom, which replays
GREEN today and trips only if the scheduler regresses past it. The
replay speed the verdict was judged at is recorded in ``meta`` and
reused by the regression runner, because compute latency does not
compress with speed even though engineered waits do.
"""

from __future__ import annotations

import os
import random
import time
from typing import Callable, Optional

from kubernetes_tpu.scenario.generators import GENERATORS
from kubernetes_tpu.scenario.replay import replay_trace
from kubernetes_tpu.scenario.trace import Trace, save_trace

# gate headroom: the ratchet bound a filed trace enforces afterwards.
# Generous on purpose — the gate exists to catch regressions, not to
# re-litigate the breach on a noisy CI box
GATE_FACTOR = 2.0
GATE_PAD_MS = 1000.0

GATED_METRICS = ("time_to_bind_p50_ms", "time_to_bind_p99_ms",
                 "time_to_bind_max_ms")


def _sample(rng: random.Random, bounds: dict, defaults: dict) -> dict:
    p = dict(defaults)
    for k, (lo, hi) in bounds.items():
        if isinstance(lo, int) and isinstance(hi, int):
            p[k] = rng.randint(lo, hi)
        else:
            p[k] = round(rng.uniform(float(lo), float(hi)), 3)
    return p


def _perturb(rng: random.Random, params: dict, bounds: dict,
             key: str, direction: int) -> dict:
    """One coordinate-descent move: push ``key`` a quarter-range step in
    ``direction``, clamped to bounds."""
    lo, hi = bounds[key]
    step = (float(hi) - float(lo)) * 0.25 * direction
    v = float(params[key]) + step
    v = min(max(v, float(lo)), float(hi))
    if isinstance(lo, int) and isinstance(hi, int):
        v = int(round(v))
    else:
        v = round(v, 3)
    out = dict(params)
    out[key] = v
    return out


def _score(report: dict) -> float:
    if not report.get("completed"):
        # a wedged trace is the worst outcome there is — but it can't be
        # filed as a gate (it never produces a stable verdict), so rank
        # it high without letting it win over real completed tails
        return float(report["stats"]["time_to_bind_p99_ms"]) + 1.0
    return float(report["stats"]["time_to_bind_p99_ms"])


def _gate_from(stats: dict) -> dict:
    return {m: round(float(stats[m]) * GATE_FACTOR + GATE_PAD_MS, 2)
            for m in GATED_METRICS if m in stats}


def file_regression_trace(trace: Trace, report: dict, out_dir: str) -> str:
    """Re-stamp the losing trace with its ratchet gate + provenance and
    write it as JSON-lines under ``out_dir``."""
    trace.gate = _gate_from(report["stats"])
    trace.meta = {
        **trace.meta,
        "filed_by": "scenario.fuzz",
        "filed_speed": report["speed"],
        "objective": "p99",
        "observed": dict(report["stats"]),
        "violated_slo": dict(report["slo"]["target"]),
        "breaches": report["slo"]["breaches"],
    }
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{trace.generator}-s{trace.seed}.jsonl"
    path = os.path.join(out_dir, fname)
    save_trace(trace, path, fmt="jsonl")
    return path


def fuzz(regimes: Optional[list[str]] = None, budget_s: float = 120.0,
         seed: int = 0, speed: float = 3.0,
         out_dir: Optional[str] = None, refine_rounds: int = 2,
         replay_timeout_s: float = 60.0,
         log: Callable[[str], None] = lambda s: None) -> dict:
    """Run the adversarial search; returns the summary report.

    Phase 1 (random): round-robin the regimes, sampling params inside
    their bounds, until ~60% of the budget is gone. Phase 2 (descent):
    around the worst cell, perturb one parameter at a time both ways
    and recurse on improvements until the budget runs out. Candidates
    that breach their regime SLO are filed to ``out_dir`` (set it to
    tests/regression_traces/ to arm the ratchet); only the WORST
    breaching candidate per regime is filed, so a long search doesn't
    dump dozens of near-duplicate traces.
    """
    names = list(regimes or GENERATORS)
    rng = random.Random(seed)
    t0 = time.time()
    candidates = []
    worst = None            # (score, trace, report)
    filed_best: dict[str, tuple] = {}   # regime -> (score, trace, report)

    def run_candidate(regime: str, params: dict, cand_seed: int):
        nonlocal worst
        trace = GENERATORS[regime].generate(params, seed=cand_seed)
        try:
            report = replay_trace(trace, speed=speed,
                                  timeout_s=replay_timeout_s)
        except Exception as exc:  # noqa: BLE001 — a crashing candidate
            log(f"  {regime} seed={cand_seed} CRASHED: {exc!r}")
            return None           # is logged, not fatal to the search
        score = _score(report)
        row = {"regime": regime, "seed": cand_seed, "params": params,
               "score": round(score, 2),
               "slo_ok": report["slo"]["ok"],
               "completed": report["completed"],
               "audit_ok": report["audit"]["ok"]}
        candidates.append(row)
        log(f"  {regime} seed={cand_seed} score={score:.0f} "
            f"slo_ok={report['slo']['ok']} params={params}")
        if worst is None or score > worst[0]:
            worst = (score, trace, report)
        if not report["slo"]["ok"] and report["completed"]:
            prev = filed_best.get(regime)
            if prev is None or score > prev[0]:
                filed_best[regime] = (score, trace, report)
        return score

    def remaining() -> float:
        return budget_s - (time.time() - t0)

    # phase 1: seeded random sweep, round-robin across regimes
    i = 0
    while remaining() > budget_s * 0.4 or not candidates:
        regime = names[i % len(names)]
        i += 1
        reg = GENERATORS[regime]
        params = _sample(rng, reg.bounds, reg.defaults)
        run_candidate(regime, params, cand_seed=rng.randrange(1 << 16))
        if remaining() <= 0:
            break

    # phase 2: coordinate descent around the worst cell
    rounds = 0
    while worst is not None and remaining() > 0 and rounds < refine_rounds:
        rounds += 1
        _, wtrace, _ = worst
        regime = wtrace.generator
        bounds = GENERATORS[regime].bounds
        base = dict(wtrace.params)
        improved = False
        for key in bounds:
            if remaining() <= 0:
                break
            for direction in (+1, -1):
                if remaining() <= 0:
                    break
                cand = _perturb(rng, base, bounds, key, direction)
                if cand == base:
                    continue
                before = worst[0]
                s = run_candidate(regime, cand, cand_seed=wtrace.seed)
                if s is not None and s > before:
                    improved = True
                    break     # re-center on the new worst cell
            if improved:
                break
        if not improved:
            break

    filed = []
    if out_dir:
        for regime, (_, trace, report) in sorted(filed_best.items()):
            filed.append(file_regression_trace(trace, report, out_dir))
            log(f"  filed {filed[-1]}")
    return {
        "speed": speed,
        "budget_s": budget_s,
        "elapsed_s": round(time.time() - t0, 1),
        "candidates": len(candidates),
        "rows": candidates,
        "worst": None if worst is None else {
            "score": round(worst[0], 2),
            "regime": worst[1].generator,
            "seed": worst[1].seed,
            "params": worst[1].params,
            "slo": worst[2]["slo"],
        },
        "filed": filed,
    }
