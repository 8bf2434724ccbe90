"""The five per-layer readers that read the flight recorder's new phases
(PR 25), on a toy `obs`, and benchmark/cell.py::PhaseSpans against the real
Scheduler at a cell's rehearsal size on the CPU: the spans it rebuilds from
the two patched delivery methods carry the new phases, and none of them is
laid over another."""

import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_invariants as inv  # noqa: E402
from benchmark import cell, objects  # noqa: E402

TURN = ("maintenance", "lock_wait", "event_intake", "gc_sweep", "drain_tail")


def _obs(phase_s, bound=2000, seconds=30):
    return {"seconds": seconds, "bound_in_window": bound, "phase_s": phase_s,
            "launches": 4, "launch_cache_delta": 0, "compiles": [],
            "gc_pauses_ms": [], "trace": None}


@pytest.mark.parametrize("name, phase_s, want", [
    ("queue.done_ms_per_kpod.drain", {"queue_done": 1.5}, 750.0),
    ("queue.done_ms_per_kpod.drain", {"queue_done": 0.0}, 0.0),
    ("queue.done_ms_per_kpod.drain", {"binder_drain": 1.5}, None),
    ("mirror.snapshot_cache_ms_per_kpod.drain",
     {"snapshot_cache": 0.1, "snapshot_sync": 0.4}, 50.0),
    ("mirror.snapshot_cache_ms_per_kpod.drain", {"snapshot_sync": 0.4}, None),
    ("loop.idle_share.arrive", {"idle_wait": 18.0}, 0.6),
    ("loop.idle_share.arrive", {"commit": 1.0}, None),
    ("loop.turn_overhead_ms_per_kpod.arrive",
     dict.fromkeys(TURN, 0.02), 50.0),
    ("loop.turn_overhead_ms_per_kpod.arrive",
     {"maintenance": 0.04, "idle_wait": 20.0}, 20.0),
    ("loop.turn_overhead_ms_per_kpod.arrive", {"idle_wait": 20.0}, None),
    ("loop.gc_ms_per_kpod.arrive", {"gc_pause": 0.9}, 450.0),
    ("loop.gc_ms_per_kpod.arrive", {"gc_sweep": 0.9}, None),
])
def test_reader_value_and_none_when_its_phase_is_missing(name, phase_s, want):
    got = cell.load_reader(name)(_obs(phase_s))
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", [
    "queue.done_ms_per_kpod.drain", "mirror.snapshot_cache_ms_per_kpod.drain",
    "loop.turn_overhead_ms_per_kpod.arrive", "loop.gc_ms_per_kpod.arrive"])
def test_per_kpod_reader_has_nothing_to_read_without_binds(name):
    phases = {"queue_done": 1.0, "snapshot_cache": 1.0, "gc_pause": 1.0,
              "maintenance": 1.0}
    assert cell.load_reader(name)(_obs(phases, bound=0)) is None


def test_new_readers_are_in_the_manifest_under_their_cells():
    """Each under its layer, moving its metric, with at least the cells it
    came with at the head of its list; what was in `per_layer` keeps its
    order. A new entry and a new cell's name are appended."""
    inv.per_layer_entries_keep_their_order_and_their_cells(
        cell.load_manifest(REPO))


def _entry(manifest, name):
    return next(m for m in manifest["per_layer"] if m["name"] == name)


def _swap(manifest, i, j):
    per = manifest["per_layer"]
    per[i], per[j] = per[j], per[i]


PER_LAYER_EDITS = {
    # edit -> whether the invariant still holds after it
    "a new entry is appended": (True, lambda m: m["per_layer"].append(dict(
        _entry(m, "queue.done_ms_per_kpod.drain"), name="new.drain"))),
    "a new entry goes between two that were there": (
        True, lambda m: m["per_layer"].insert(3, dict(
            _entry(m, "queue.done_ms_per_kpod.drain"), name="new.drain"))),
    "a new cell is appended to a reader's list": (
        True, lambda m: _entry(m, "mirror.snapshot_cache_ms_per_kpod.drain")[
            "workloads"].append("a-later.cell")),
    "a reader is moved to another layer": (
        False, lambda m: _entry(m, "loop.idle_share.arrive").update(
            layer="queues")),
    "a reader moves another metric": (
        False, lambda m: _entry(m, "queue.done_ms_per_kpod.drain").update(
            moves="setup_s")),
    "a reader is dropped from one of its cells": (
        False, lambda m: _entry(m, "queue.done_ms_per_kpod.drain")[
            "workloads"].remove("topology-5k.required")),
    "a new cell is put before a reader's own": (
        False, lambda m: _entry(m, "loop.gc_ms_per_kpod.arrive")[
            "workloads"].insert(0, "a-later.cell")),
    "two entries that were there change places": (
        False, lambda m: _swap(m, 2, 9)),
    "the last entry is moved to the head": (
        False, lambda m: m["per_layer"].insert(0, m["per_layer"].pop())),
    "an entry that was there is taken out": (
        False, lambda m: m["per_layer"].pop(4)),
}


@pytest.mark.parametrize("edit", sorted(PER_LAYER_EDITS))
def test_per_layer_takes_additions_and_refuses_a_move(edit):
    holds, change = PER_LAYER_EDITS[edit]
    manifest = inv.edited(cell.load_manifest(REPO), change)
    if holds:
        inv.per_layer_entries_keep_their_order_and_their_cells(manifest)
    else:
        with pytest.raises(AssertionError):
            inv.per_layer_entries_keep_their_order_and_their_cells(manifest)


def test_phase_spans_against_the_real_scheduler():
    from kubernetes_tpu.utils.tracing import (
        CycleTrace, FlightRecorder, OVERLAP_PHASES, VIEW_PHASES)

    manifest = cell.load_manifest(REPO)
    _cell, entry = cell.find_cell(manifest, "topology-5k.required")
    cfg = cell.load_config(entry, rehearse=True, repo=REPO)
    add, observe = CycleTrace.add, FlightRecorder.observe_phase
    spans = cell.PhaseSpans()
    spans.install()
    try:
        assert CycleTrace.add is not add
        hub, sched, token = cell.build_cluster(cfg, 2_500_000_033)
        try:
            sched.start()
            spans.thread_id = sched._daemon.ident
            maker = objects.PodMaker(
                objects.load_template("pod-spread-required"))
            for wave in range(3):       # unchained: every wave syncs
                for i in range(40):
                    hub.create_pod(maker.make(f"s-{token}-{wave}-{i}"))
                deadline = time.time() + 120
                while sched.stats["scheduled"] < 40 * (wave + 1):
                    assert time.time() < deadline, sched.stats
                    time.sleep(0.02)
            time.sleep(0.1)
        finally:
            sched.close()
    finally:
        spans.remove()
    assert CycleTrace.add is add and FlightRecorder.observe_phase is observe
    names = {p for p, _a, _b in spans.spans}
    assert {"idle_wait", "maintenance", "event_intake", "gc_sweep",
            "drain_tail", "queue_pop", "snapshot_cache", "snapshot_sync",
            "mirror_sync", "pack", "device_launch", "commit",
            "binder_drain"} <= names
    # the commit thread's span is not the loop thread's: PhaseSpans keeps
    # only the daemon's, so commit_pull can no longer be laid over commit
    assert "commit_pull" not in names
    exclusive = sorted((a, b, p) for p, a, b in spans.spans
                       if p not in VIEW_PHASES and p not in OVERLAP_PHASES)
    slack = 200e3       # ns: two clocks (monotonic, time_ns) rebuilt apart
    for (_a, b, p), (a2, _b2, p2) in zip(exclusive, exclusive[1:]):
        if {p, p2} == {"eviction_flush", "binder_drain"}:
            continue    # the flush waits for the binder inside its phase
        assert a2 >= b - slack, (p, p2, b - a2)
    # snapshot_sync is timed in two pieces and each piece's view is
    # delivered just ahead of it with the same seconds, so the view sorts
    # first, as trace_reduce sorts, every time: the idle gaps read
    # snapshot_cache and mirror_sync, and snapshot_sync keeps only its sum
    sync = sorted((a, b, p) for p, a, b in spans.spans
                  if p in ("snapshot_cache", "mirror_sync", "snapshot_sync"))
    assert len(sync) >= 12
    assert [p for _a, _b, p in sync] == [
        "snapshot_cache", "snapshot_sync", "mirror_sync",
        "snapshot_sync"] * (len(sync) // 4)
