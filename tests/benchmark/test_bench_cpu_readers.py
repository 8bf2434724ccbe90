"""The five per-layer readers that read the flight recorder's `.cpu` series
(PR 36: a span's CPU seconds on its own thread, beside its wall seconds in
`obs["phase_s"]` under `"<phase>.cpu"`), on a toy `obs`: the value, None
without their series (a program from before PR 36), None without binds for
the per-kpod ones, and the clamp of the waiting share."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cell, host_wait  # noqa: E402

DRAIN = ["basic-5k.backlog", "topology-5k.required", "topology-5k.preferred",
         "affinity-5k.required"]
ARRIVE = ["basic-5k.arrivals", "anti-affinity-5k.required"]
NEW = {
    # name -> (layer, unit, moves, cells), in the manifest's order
    "queue.pop_cpu_ms_per_kpod.drain": (
        "queues", "ms/kpod", "pods_per_s", DRAIN),
    "loop.commit_cpu_ms_per_kpod.drain": (
        "scheduling loop", "ms/kpod", "pods_per_s", DRAIN),
    "loop.binder_cpu_ms_per_kpod.drain": (
        "scheduling loop", "ms/kpod", "pods_per_s", DRAIN),
    "loop.host_wait_share.drain": (
        "scheduling loop", "share", "pods_per_s", DRAIN),
    "loop.host_wait_share.arrive": (
        "scheduling loop", "share", "bind_p50_ms", ARRIVE),
}
SHARES = ["loop.host_wait_share.drain", "loop.host_wait_share.arrive"]


def _obs(phase_s, bound=2000, seconds=30):
    return {"seconds": seconds, "bound_in_window": bound, "phase_s": phase_s,
            "launches": 4, "launch_cache_delta": 0, "compiles": [],
            "gc_pauses_ms": [], "trace": None}


@pytest.mark.parametrize("name, phase_s, want", [
    ("queue.pop_cpu_ms_per_kpod.drain",
     {"queue_pop": 4.0, "queue_pop.cpu": 0.25}, 125.0),
    ("queue.pop_cpu_ms_per_kpod.drain", {"queue_pop.cpu": 0.0}, 0.0),
    ("queue.pop_cpu_ms_per_kpod.drain", {"queue_pop": 4.0}, None),
    ("loop.commit_cpu_ms_per_kpod.drain",
     {"commit": 2.0, "commit.cpu": 1.5, "binder_drain": 0.7,
      "binder_drain.cpu": 0.5, "commit_pull.cpu": 9.0}, 1000.0),
    ("loop.commit_cpu_ms_per_kpod.drain", {"commit.cpu": 1.5}, 750.0),
    ("loop.commit_cpu_ms_per_kpod.drain",
     {"commit": 2.0, "binder_drain": 0.7}, None),
    ("loop.binder_cpu_ms_per_kpod.drain",
     {"bind_chunk": 6.0, "bind_chunk.cpu": 1.0}, 500.0),
    ("loop.binder_cpu_ms_per_kpod.drain",
     {"bind_chunk": 6.0, "binder_drain.cpu": 0.5}, None),
])
def test_cpu_per_kpod_reader_value_and_none_without_its_series(
        name, phase_s, want):
    got = cell.load_reader(name)(_obs(phase_s))
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", [n for n in NEW if n not in SHARES])
def test_cpu_per_kpod_reader_has_nothing_to_read_without_binds(name):
    phases = {"queue_pop.cpu": 1.0, "commit.cpu": 1.0,
              "binder_drain.cpu": 1.0, "bind_chunk.cpu": 1.0}
    assert cell.load_reader(name)(_obs(phases, bound=0)) is None


@pytest.mark.parametrize("name", SHARES)
@pytest.mark.parametrize("phase_s, want", [
    # 3 s waited in queue_pop, 0.5 in commit, of a 30 s window
    ({"queue_pop": 4.0, "queue_pop.cpu": 1.0, "commit": 2.0,
      "commit.cpu": 1.5}, 3.5 / 30),
    # waits with a phase of their own, views and overlap phases: left out
    ({"queue_pop": 4.0, "queue_pop.cpu": 1.0, "idle_wait": 20.0,
      "idle_wait.cpu": 0.1, "device_launch": 9.0, "device_launch.cpu": 0.2,
      "lock_wait": 1.0, "lock_wait.cpu": 0.0, "d2h_pull": 1.0,
      "d2h_pull.cpu": 0.0, "mirror_sync": 2.0, "mirror_sync.cpu": 0.5,
      "commit_pull": 8.0, "commit_pull.cpu": 0.1, "bind_chunk": 6.0,
      "bind_chunk.cpu": 1.0}, 3.0 / 30),
    # the clamp: CPU seconds a rounding over the wall's count for nothing
    ({"pack": 1.0, "pack.cpu": 1.0004, "event_intake": 2.0,
      "event_intake.cpu": 0.5}, 1.5 / 30),
    # all of it on the interpreter: a true 0, which is a reading
    ({"commit": 2.0, "commit.cpu": 2.0}, 0.0),
    # a phase whose CPU series is missing is not taken for all waiting
    ({"commit": 2.0, "pack": 1.0, "pack.cpu": 0.25}, 0.75 / 30),
    # no .cpu series at all (the parent): nothing to read, never 0
    ({"queue_pop": 4.0, "commit": 2.0, "idle_wait": 20.0}, None),
    ({}, None),
])
def test_host_wait_share_value_clamp_and_none_without_cpu_series(
        name, phase_s, want):
    got = cell.load_reader(name)(_obs(phase_s))
    assert got == (pytest.approx(want) if want is not None else None)


def test_host_wait_share_has_nothing_to_read_in_a_window_of_no_length():
    obs = _obs({"commit": 2.0, "commit.cpu": 1.0}, seconds=0)
    assert host_wait.host_wait_share(obs) is None


def test_host_work_phases_and_designed_waits_partition_the_exclusive_phases():
    """Every exclusive phase of the program's recorder (no view, no overlap
    phase) is either host work or a wait by design, and in one list only:
    a phase the program gains fails here until it is put in one, so it
    cannot drop out of loop.host_wait_share.* unseen."""
    from kubernetes_tpu.utils.tracing import (
        CYCLE_PHASES, EXCLUDED_PHASES, LOOP_PHASES)

    work, waits = host_wait.HOST_WORK_PHASES, host_wait.DESIGNED_WAITS
    assert len(set(work)) == len(work) and len(set(waits)) == len(waits)
    assert not set(work) & set(waits)
    exclusive = (set(CYCLE_PHASES) | set(LOOP_PHASES)) - set(EXCLUDED_PHASES)
    assert set(work) | set(waits) == exclusive
    # the fourteen the cells exercise, as ISSUE 36 lists them, lead
    assert work[:14] == (
        "queue_pop", "chain_patch", "snapshot_sync", "host_plugins",
        "learned_score", "pack", "device_dispatch", "commit",
        "failure_handling", "binder_drain", "maintenance", "event_intake",
        "gc_sweep", "drain_tail")


@pytest.mark.parametrize("phase", ["eviction_flush", "host_fallback",
                                   "gang_commit"])
def test_host_wait_share_counts_the_host_phases_no_cell_runs_today(phase):
    obs = _obs({phase: 3.0, phase + ".cpu": 1.5, "gang_device": 9.0,
                "gang_device.cpu": 0.1})
    assert host_wait.host_wait_share(obs) == pytest.approx(1.5 / 30)


def test_the_five_entries_stand_together_in_the_issues_order():
    """Appended by PR 36, in one block; a later PR's entries follow them
    (PR 37's three did, and this test then pinned "the last five")."""
    per = cell.load_manifest(REPO)["per_layer"]
    at = [m["name"] for m in per].index(next(iter(NEW)))
    five = per[at:at + 5]
    assert [m["name"] for m in five] == list(NEW)
    for m in five:
        layer, unit, moves, cells = NEW[m["name"]]
        assert (m["layer"], m["unit"], m["moves"], m["workloads"]) \
            == (layer, unit, moves, cells)
        assert (m["better"], m["source"]) == ("lower", "program_span")
    # the twins they stand beside list the same cells in the same order
    by_name = {m["name"]: m for m in per}
    assert by_name["queue.pop_ms_per_kpod.drain"]["workloads"] == DRAIN
    assert by_name["loop.commit_ms_per_kpod.drain"]["workloads"] == DRAIN
