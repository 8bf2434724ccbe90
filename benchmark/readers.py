"""What the per-layer metric readers under benchmark/layer_metrics/ share.

A reader gets `obs`, what one traced run observed, and returns a number or
None. None means there was nothing to read, and the harness then leaves the
metric out of the line: a reader never returns 0 for lack of data.

obs keys: seconds, bound_in_window, phase_s {flight-recorder phase: seconds
in the window}, launches, launch_cache_delta, compiles [(program, secs)],
gc_pauses_ms [(ms, generation)], trace (None or busy_s, window_s, program_s
{program: device seconds}, pods_bound, in the traced slice); arrivals cells
add bind_ms and late_ms (ascending samples) and pending.
"""

from __future__ import annotations

from benchmark.stats import percentile  # noqa: F401 — for the readers


def phase_ms_per_kpod(obs: dict, phases: tuple[str, ...]) -> float | None:
    """Host seconds in the named phases per 1,000 pods bound in the window."""
    if not obs["bound_in_window"]:
        return None
    if not any(p in obs["phase_s"] for p in phases):
        return None
    secs = sum(obs["phase_s"].get(p, 0.0) for p in phases)
    return secs * 1e3 / (obs["bound_in_window"] / 1000.0)


def pods_per_launch(obs: dict) -> float | None:
    return obs["bound_in_window"] / obs["launches"] if obs["launches"] else None


def program_ms_per_kpod(obs: dict, program: str) -> float | None:
    """Device milliseconds of one jitted program per 1,000 pods bound,
    both in the traced slice."""
    tr = obs.get("trace")
    if not tr or not tr["pods_bound"] or program not in tr["program_s"]:
        return None
    return tr["program_s"][program] * 1e3 / (tr["pods_bound"] / 1000.0)


def compiles_in_window(obs: dict) -> float:
    """A true count: 0 compiles is a reading, not a lack of one."""
    return float(len(obs["compiles"]))
