"""Metric arithmetic of the benchmark: percentiles and rates.

Rates are all pods over all of the window and tails are over every pod due
in the window, so one stall moves both (perf/collector.py's percentiles are
over 1-second chunks, which a stall does not move; only its nearest-rank
rule is copied here).
"""

from __future__ import annotations

import math


def percentile(sorted_vals: list[float], q: float) -> float | None:
    """Nearest-rank percentile of an ascending list; None when empty."""
    if not sorted_vals:
        return None
    k = max(0, min(len(sorted_vals) - 1,
                   math.ceil(q / 100.0 * len(sorted_vals)) - 1))
    return sorted_vals[k]


def rate_in_window(times: list[float], t0: float, seconds: float) -> float:
    """Events with t0 <= t < t0 + seconds, over the whole window."""
    n = sum(1 for t in times if t0 <= t < t0 + seconds)
    return n / seconds


def wait_samples_ms(due: dict[str, float], bound: dict[str, float],
                    t0: float, seconds: float,
                    t_end: float) -> tuple[list[float], int]:
    """Milliseconds from the instant each pod was due to its bind event, for
    every pod due inside the window, ascending. A pod still unbound at
    ``t_end`` (the end of the grace drain) counts as the longest wait there
    is, its own from due to ``t_end``, and as failed."""
    samples, failed = [], 0
    for uid, t_due in due.items():
        if not t0 <= t_due < t0 + seconds:
            continue
        t_bind = bound.get(uid)
        if t_bind is None:
            failed += 1
            t_bind = t_end
        samples.append((t_bind - t_due) * 1e3)
    samples.sort()
    if failed:
        worst = samples[-1]
        samples = samples[:len(samples) - failed] + [worst] * failed
        samples.sort()
    return samples, failed
