"""Reduction of a profiler trace (.xplane.pb) to the benchmark's numbers.

busy/idle of the device, device time per program, the longest device
operations, and the idle gaps attributed to what the scheduler's loop
thread was doing. Split in two so that the arithmetic is checked on a small
recorded trace (tests/benchmark): `read_xplane` turns the file into plain
event lists with nothing but JAX, `reduce_events` does the rest.
"""

from __future__ import annotations

import bisect
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_EVENT = "bench_clock_sync"
UNATTRIBUTED = "scheduler_loop_unattributed"
NAMES_KEPT = 30     # a list: after a cure the passes that are left stand
#                     eleventh to thirtieth (PR 35); cell.py cuts the
#                     breakdown's lists to the contract's ten


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str) -> dict:
    """{"devices": {plane: {"ops": [(name, start_ns, dur_ns)],
    "modules": [...]}}, "start_wall_ns": the wall-clock instant of the
    trace's time 0 (the profiler's own profile_start_time) or None,
    "sync_ns": the start of the clock-sync annotation on the host plane,
    looked for only where the profiler gave no start time}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    start_wall_ns = sync_ns = None
    hosts = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") \
                and "SparseCore" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    dev[key] = [(e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
            devices[plane.name] = dev
        elif plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
            start_wall_ns = int(start) if start else None
        elif plane.name.startswith("/host:"):
            hosts.append(plane)
    if start_wall_ns is None:
        sync_ns = next((float(e.start_ns) for plane in hosts
                        for line in plane.lines for e in line.events
                        if e.name == SYNC_EVENT), None)
    return {"devices": devices, "start_wall_ns": start_wall_ns,
            "sync_ns": sync_ns}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op_name(op_event: str) -> str:
    """An operation's event carries its whole HLO line; its name is what
    stands before ' = ': '%while.5 = (f32[...' -> 'while.5'."""
    return op_event.split(" = ", 1)[0].lstrip("%")[:80]


def _program_of(module_event: str) -> str:
    """'jit_schedule_batch_jit(1234567)' -> 'schedule_batch_jit'."""
    name = module_event.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def reduce_events(trace: dict, t0_ns: float, t1_ns: float,
                  host_spans: list[tuple[str, float, float]]) -> dict:
    """Numbers of the slice [t0_ns, t1_ns) of the trace's own clock.

    ``host_spans``: (phase, start_ns, end_ns) of the loop thread's
    flight-recorder phases on the same clock. Returns busy_s (mean over
    device planes of the union of operation intervals), window_s,
    program_s {program: device seconds of its module events, each clipped
    to the slice}, program_launch_s {program: the seconds of each of its
    module events that lie whole inside the slice, a launch each, in the
    order they started}, device_ops and idle_gaps (the NAMES_KEPT that
    took most time of each). A module event that
    the slice's edge cuts is in program_s with the part inside and is no
    launch: its length is not known (the profiler may have cut it where
    the trace starts or stops)."""
    window_s = (t1_ns - t0_ns) / 1e9
    busy, program_s, op_s = [], {}, {}
    program_launch_s: dict[str, list[float]] = {}
    gaps: list[tuple[float, float]] = []
    for dev in trace["devices"].values():
        clip = [(max(s, t0_ns), min(s + d, t1_ns)) for _n, s, d in dev["ops"]
                if s + d > t0_ns and s < t1_ns and d > 0]
        merged = _union(clip)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        edges = [t0_ns] + [x for ab in merged for x in ab] + [t1_ns]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        mods = sorted((s, s + d, _program_of(n))
                      for n, s, d in dev["modules"]
                      if s + d > t0_ns and s < t1_ns)
        for a, b, prog in mods:
            program_s[prog] = program_s.get(prog, 0.0) + (
                min(b, t1_ns) - max(a, t0_ns)) / 1e9
            if a >= t0_ns and b <= t1_ns:
                program_launch_s.setdefault(prog, []).append((b - a) / 1e9)
        starts = [a for a, _b, _p in mods]
        for n, s, d in dev["ops"]:
            if s + d <= t0_ns or s >= t1_ns or d <= 0:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "no_module"
            key = f"{prog}/{_op_name(n)}"
            op_s[key] = op_s.get(key, 0.0) + d / 1e9
    n_dev = max(1, len(trace["devices"]))
    idle: dict[str, float] = {}
    # the loop thread's phases as disjoint segments (where two overlap, as
    # device_launch does with the next cycle's phases, the earlier keeps
    # its part), then one sweep over gaps and segments together
    segs: list[tuple[float, float, str]] = []
    end = float("-inf")
    for a, b, phase in sorted((a, b, p) for p, a, b in host_spans):
        a = max(a, end)
        if b > a:
            segs.append((a, b, phase))
            end = b
    i = 0
    for a, b in sorted(gaps):
        left = b - a
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            ov = min(b, segs[j][1]) - max(a, segs[j][0])
            if ov > 0:
                idle[segs[j][2]] = idle.get(segs[j][2], 0.0) \
                    + ov / 1e9 / n_dev
                left -= ov
            j += 1
        if left > 0:
            idle[UNATTRIBUTED] = idle.get(UNATTRIBUTED, 0.0) \
                + left / 1e9 / n_dev

    def top(d: dict) -> list[list]:
        return [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:NAMES_KEPT]]

    return {"busy_s": sum(busy) / n_dev if busy else 0.0,
            "window_s": window_s, "program_s": program_s,
            "program_launch_s": program_launch_s,
            "device_ops": top(op_s), "idle_gaps": top(idle)}
