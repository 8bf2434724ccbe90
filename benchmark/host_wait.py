"""The loop thread's waiting share, which the two `loop.host_wait_share.*`
readers under benchmark/layer_metrics/ share.

Since PR 36 every flight-recorder span reads its thread's CPU clock beside
the wall clock, and a phase's CPU seconds stand in `obs["phase_s"]` under
`"<phase>.cpu"` (the same histogram, `scheduling_phase_duration_seconds`).
A phase's wall seconds less its CPU seconds are what its thread stood off
the interpreter inside it: waiting for the GIL or a lock that in-process
clients and the program's other threads hold.
"""

from __future__ import annotations

CPU = ".cpu"

# the loop thread's phases that block by design, each a wait with a phase
# and a reader of its own (gang_device is device_launch's twin for a gang
# pack): left out of the share on purpose
DESIGNED_WAITS = ("idle_wait", "lock_wait", "device_launch", "d2h_pull",
                  "gang_device")

# the loop thread's phases that are host work and do not block by design:
# every exclusive phase of the program's recorder (its CYCLE_PHASES and
# LOOP_PHASES less the views, whose time is inside one of these, and the
# overlap phases, which other threads run) that is no designed wait.
# tests/benchmark/test_bench_cpu_readers.py holds the two lists to that
# partition, so a phase the program gains cannot drop out unseen. The last
# three run in no cell today (preemption, a device fault, a gang commit).
HOST_WORK_PHASES = (
    "queue_pop", "chain_patch", "snapshot_sync", "host_plugins",
    "learned_score", "pack", "device_dispatch", "commit", "failure_handling",
    "binder_drain", "maintenance", "event_intake", "gc_sweep", "drain_tail",
    "eviction_flush", "host_fallback", "gang_commit")


def host_wait_share(obs: dict) -> float | None:
    """Share of the window the loop thread stood off the CPU inside host
    work: the sum over HOST_WORK_PHASES of max(0, phase - phase.cpu), over
    the window's seconds. None where the program reports no `.cpu` series
    at all (a program from before PR 36): never 0 for lack of data."""
    phase_s = obs["phase_s"]
    if not obs["seconds"] or not any(k.endswith(CPU) for k in phase_s):
        return None
    waited = sum(max(0.0, phase_s[p] - phase_s[p + CPU])
                 for p in HOST_WORK_PHASES
                 if p in phase_s and p + CPU in phase_s)
    return waited / obs["seconds"]
