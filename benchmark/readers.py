"""What the per-layer metric readers under benchmark/layer_metrics/ share.

A reader gets `obs`, what one traced run observed, and returns a number or
None. None means there was nothing to read, and the harness then leaves the
metric out of the line: a reader never returns 0 for lack of data.

obs keys: seconds, bound_in_window, phase_s {flight-recorder phase: seconds
in the window}, launches, launch_cache_delta, compiles [(program, secs)],
gc_pauses_ms [(ms, generation)], trace (None or busy_s, window_s, program_s
{program: device seconds}, program_launch_s {program: the device seconds of
each launch that lies whole inside}, pods_bound, in the traced slice),
pod_table (capacity, in_use_at_close, in_use_at_end: the mirror's pod
table at the window's close and after the grace drain; absent where the
program keeps no free-slot list); arrivals cells add bind_ms and late_ms
(ascending samples) and pending.
"""

from __future__ import annotations

from benchmark.stats import percentile  # noqa: F401 — for the readers too


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


def launch_ms(obs: dict, program: str) -> float | None:
    """Device milliseconds of one launch of a jitted program: the median
    (nearest rank, as every percentile here) over its launches that lie
    whole inside the traced slice (a slice
    holds four or five of a scan cell's, and one that carried half a batch
    runs a shorter scan: the mean read 204.4 where four other runs read
    218.9 to 219.6, PR 37). Unlike program_ms_per_kpod it has no pods in
    it, so it does not move with which binds fell into the slice."""
    tr = obs.get("trace")
    launches = (tr or {}).get("program_launch_s", {}).get(program)
    if not launches:
        return None
    return percentile(sorted(launches), 50) * 1e3


def pod_table_fill(obs: dict) -> float | None:
    """The fullest the run made the mirror's pod table, as a share of its
    capacity: 1.0 is a CapacityError, a _grow and a compile."""
    table = obs.get("pod_table")
    if not table or not table["capacity"]:
        return None
    return max(table["in_use_at_close"], table["in_use_at_end"]) \
        / table["capacity"]


def compiles_in_window(obs: dict) -> float:
    """A true count: 0 compiles is a reading, not a lack of one."""
    return float(len(obs["compiles"]))
