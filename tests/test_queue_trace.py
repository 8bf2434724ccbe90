"""What the queue and the collector guard count about themselves, and how
the flight recorder shows it: PriorityQueue._trim_events (calls, calls that
dropped entries from the log's head, the seconds in those, the event log's
high-water length -> the queue_done view and
the two scheduler_queue_event_* metrics) and utils/gcguard's gc.callbacks
hook (scheduler_gc_pause_seconds{generation}, the gc_pause view)."""

import gc
import json
import threading
import urllib.request

import pytest

from kubernetes_tpu.hub import Hub
from kubernetes_tpu.metrics import FINE_DURATION_BUCKETS, Histogram
from kubernetes_tpu.serving import ServingEndpoints, token_auth
from kubernetes_tpu.utils.gcguard import GCGuard
from kubernetes_tpu.utils.tracing import FlightRecorder

from tests.test_queue import POD_DELETE, mkpod, mkq
from tests.test_tracing import _sched, mknode
from tests.test_tracing import mkpod as mk_sched_pod


pytestmark = pytest.mark.observability


class Ticking:
    """Every read moves the clock: a scan that reads it twice took time."""

    def __init__(self):
        self.t = 1000.0

    def now(self):
        self.t += 0.001
        return self.t


# pod i is popped after STARTS[i] events and LOGGED events arrive in all:
# three pods in flight at distinct start seqs, the log 15 long
STARTS = (0, 5, 12)
LOGGED = 15


def _staggered(q):
    pods, seen = [], 0
    for i, start in enumerate(STARTS):
        for _ in range(start - seen):
            q.move_all_to_active_or_backoff(POD_DELETE)
        seen = start
        q.add(mkpod(f"p{i}"))
        pods.append(q.pop())
    for _ in range(LOGGED - seen):
        q.move_all_to_active_or_backoff(POD_DELETE)
    return pods


@pytest.mark.parametrize("order", [
    (0, 1, 2),      # oldest first: every done() but the last drops its span
    (0, 2, 1),
    (1, 0, 2),      # a younger pod first: nothing to drop until the oldest
    (1, 2, 0),      # leaves, then the log jumps to the oldest survivor
    (2, 0, 1),
    (2, 1, 0),      # youngest first: the log waits whole for the oldest
])
def test_done_trims_the_log_to_the_oldest_survivor(order):
    """Whatever order done() arrives in, the log starts at the oldest
    start seq still in flight; a done() that moves nothing reads no clock;
    the last pod out leaves it empty through the unclocked clear."""
    clock = Ticking()
    q, _ = mkq(clock=clock)
    pods = _staggered(q)
    assert q.event_log_len() == LOGGED
    left = set(range(len(STARTS)))
    entries, scans = LOGGED, 0
    for n, i in enumerate(order, 1):
        before = clock.t
        q.done(pods[i].uid)
        left.discard(i)
        want = LOGGED - min(STARTS[j] for j in left) if left else 0
        walked = bool(left) and want < entries
        scans += walked
        entries = want
        st = q.trim_stats()
        assert st["entries"] == want
        assert [e[0] for e in q._events] == list(range(LOGGED - want, LOGGED))
        assert st["trim_calls"] == n and st["trim_scans"] == scans
        # the clock is read twice by a call that walked the head, else not
        assert clock.t - before == pytest.approx(0.002 if walked else 0.0)
        assert st["trim_scan_s"] == pytest.approx(0.001 * scans)
        assert st["high_water"] == LOGGED
    assert q.in_flight_count() == 0 and not q._starts \
        and not q._start_holders


@pytest.mark.parametrize("first", [0, 1])
def test_pods_popped_together_hold_one_start(first):
    """A pop_batch shares one start seq: the log moves only when the last
    of the batch is done."""
    clock = Ticking()
    q, _ = mkq(clock=clock)
    for name in ("a", "b"):
        q.add(mkpod(name))
    batch = q.pop_batch(2)
    for _ in range(4):
        q.move_all_to_active_or_backoff(POD_DELETE)
    q.add(mkpod("c"))
    q.pop()
    for _ in range(2):
        q.move_all_to_active_or_backoff(POD_DELETE)
    assert len(q._starts) == 2
    before = clock.t
    q.done(batch[first].uid)
    assert q.event_log_len() == 6 and q.trim_scans == 0
    assert clock.t == before
    q.done(batch[1 - first].uid)
    assert q.event_log_len() == 2 and q.trim_scans == 1
    assert [e[0] for e in q._events] == [4, 5]


@pytest.mark.parametrize("events_after, high_water", [
    (0, 15),        # nothing more arrives: the length before the trim
    (4, 15),        # 10 kept + 4: still under the longest it was
    (9, 19),        # grows past it with no done() in between: still seen
])
def test_high_water_is_the_longest_the_log_ever_was(events_after,
                                                    high_water):
    q, _clock = mkq(clock=Ticking())
    pods = _staggered(q)
    q.done(pods[0].uid)
    assert q.event_log_len() == LOGGED - STARTS[1]
    for _ in range(events_after):
        q.move_all_to_active_or_backoff(POD_DELETE)
    assert q.trim_stats()["high_water"] == high_water
    q.done(pods[2].uid)
    q.done(pods[1].uid)
    st = q.trim_stats()
    assert st["entries"] == 0 and st["high_water"] == high_water


def test_a_requeued_pod_restarts_at_its_new_pop():
    """add_unschedulable_if_not_present releases the pod's start like
    done(); popped again, the pod holds the seq of its new pop and the
    log no longer waits for its old one."""
    q, _clock = mkq(clock=Ticking())
    pods = _staggered(q)
    pods[0].unschedulable_plugins = {"NoSuchEvent"}
    q.add_unschedulable_if_not_present(pods[0])     # no registration: any
    assert q.pending_counts()["active"] == 1        # logged event requeues
    assert q.event_log_len() == LOGGED - STARTS[1]
    again = q.pop()
    assert again is pods[0] and q._in_flight[again.uid] == LOGGED
    q.done(pods[1].uid)
    assert q.event_log_len() == LOGGED - STARTS[2]
    q.done(pods[2].uid)
    assert q.event_log_len() == 0 and q.in_flight_count() == 1
    assert q.trim_scans == 3 and q.trim_calls == 3


def test_scheduler_reports_queue_done_with_every_drain():
    """queue_done rides every binder_drain that collected a bind, 0.0
    where nothing scanned (a reading; a missing phase means 'not
    instrumented'), and carries the queue's own seconds when it did."""
    hub = Hub()
    sched = _sched(hub)
    try:
        hub.create_node(mknode(0))
        for i in range(5):
            hub.create_pod(mk_sched_pod(f"p{i}"))
        sched.run_until_idle()
        m = sched.metrics
        drains = m.phase_duration.count(phase="binder_drain")
        assert drains >= 1
        assert m.phase_duration.count(phase="queue_done") == drains
        snap = m.phase_duration.snapshot()
        assert snap["{'phase': 'queue_done'}"]["sum"] == 0.0
        # a queue that scanned: its seconds are what the view reports
        done = sched.queue.done

        def slow_done(uid):
            sched.queue.trim_scan_s += 0.25
            sched.queue.trim_scans += 1
            done(uid)

        sched.queue.done = slow_done
        for i in range(4):
            hub.create_pod(mk_sched_pod(f"q{i}"))
        sched.run_until_idle()
        snap = m.phase_duration.snapshot()
        assert snap["{'phase': 'queue_done'}"]["sum"] == pytest.approx(1.0)
        assert m.queue_event_trims.value() == 4
        text = m.registry.render_text()
        assert "scheduler_queue_event_log_entries" in text
        assert "scheduler_queue_event_trims_total 4" in text
    finally:
        sched.close()


def test_debug_trace_shows_spans_loop_spans_and_queue_counts():
    hub = Hub()
    sched = _sched(hub)
    try:
        hub.create_node(mknode(0))
        hub.create_pod(mk_sched_pod("p0"))
        sched.run_until_idle()
        srv = ServingEndpoints(sched, port=0, debug_auth=token_auth("t"))
        srv.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/debug/trace?n=4")
            req.add_header("Authorization", "Bearer t")
            tr = json.loads(urllib.request.urlopen(req, timeout=5).read())
        finally:
            srv.stop()
        cyc = tr["cycles"][-1]
        assert cyc["v"] == 5
        names = [s[0] for s in cyc["spans"]]
        assert names[0] == "queue_pop" and "commit" in names
        for name, start, end, thread, cpu_ms in cyc["spans"]:
            assert end >= start and isinstance(thread, str)
            # as read: over its length by a shared reading's age at most
            assert 0.0 <= cpu_ms <= (end - start) * 1e3 + 0.1
        assert set(cyc["cpu_ms"]) == set(names)
        assert "commit.cpu" in tr["phases"]
        assert {"snapshot_cache", "snapshot_sync", "mirror_sync"} \
            <= set(names)
        assert {s[0] for s in tr["loop_spans"]} >= {
            "lock_wait", "event_intake", "binder_drain", "queue_done",
            "drain_tail", "gc_sweep", "gc_pause"}
        assert set(tr["queue"]) == {"entries", "high_water", "trim_calls",
                                    "trim_scans", "trim_scan_s"}
    finally:
        sched.close()


def test_pack_row_cache_counts_reach_the_registry_and_debug_trace():
    """Mirror.row_cache_* -> scheduler_pack_row_cache_total{result} (by
    delta, at maintenance) and /debug/trace's "pack_row_cache"; the counts
    are the scheduler's, so a re-bucketed mirror carries them on."""
    from kubernetes_tpu.backend.mirror import CapacityError

    hub = Hub()
    sched = _sched(hub)
    try:
        hub.create_node(mknode(0))
        for i in range(6):
            hub.create_pod(mk_sched_pod(f"p{i}"))
        sched.run_until_idle()
        sched.run_maintenance()
        m, st = sched.metrics, sched.mirror.row_cache_stats()
        assert st["hits"] + st["misses"] + st["bypass"] == 6
        assert st["hits"] >= 4 and st["bypass"] == 0 and st["clears"] == 0
        for result, key in (("hit", "hits"), ("miss", "misses"),
                            ("bypass", "bypass")):
            assert m.pack_row_cache.value(result=result) == st[key]
        assert (f'scheduler_pack_row_cache_total{{result="hit"}} '
                f'{st["hits"]}') in m.registry.render_text()
        sched._grow(CapacityError("pod_labels", sched.caps.pod_labels + 1))
        assert sched.mirror.row_cache_stats() == {**st, "entries": 0}
        for i in range(6, 9):
            hub.create_pod(mk_sched_pod(f"p{i}"))
        sched.run_until_idle()
        sched.run_maintenance()
        st = sched.mirror.row_cache_stats()
        assert st["hits"] + st["misses"] + st["bypass"] == 9
        assert m.pack_row_cache.value(result="hit") == st["hits"]
        assert m.pack_row_cache.value(result="miss") == st["misses"]
        srv = ServingEndpoints(sched, port=0, debug_auth=token_auth("t"))
        srv.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/debug/trace?n=1")
            req.add_header("Authorization", "Bearer t")
            tr = json.loads(urllib.request.urlopen(req, timeout=5).read())
        finally:
            srv.stop()
        assert tr["pack_row_cache"] == st
    finally:
        sched.close()


def test_slot_row_cache_counts_reach_the_registry_and_debug_trace():
    """Mirror.slot_row_* -> scheduler_mirror_slot_row_cache_total{result}
    (by delta, at maintenance) and /debug/trace's "slot_row_cache"; every
    slot with terms is a hit, a miss or a bypass, and a re-bucketed mirror
    carries the totals on."""
    from kubernetes_tpu.api.objects import (
        Affinity,
        LABEL_HOSTNAME,
        LabelSelector,
        PodAffinity,
        PodAffinityTerm,
    )
    from kubernetes_tpu.backend.mirror import CapacityError

    def affinity_pod(name, namespace_selector=None):
        pod = mk_sched_pod(name)
        pod.metadata.labels = {"color": "blue"}
        pod.spec.affinity = Affinity(pod_affinity=PodAffinity(required=[
            PodAffinityTerm(
                topology_key=LABEL_HOSTNAME,
                label_selector=LabelSelector(match_labels={"color": "blue"}),
                namespace_selector=namespace_selector)]))
        return pod

    hub = Hub()
    sched = _sched(hub)
    try:
        hub.create_node(mknode(0))
        hub.create_pod(affinity_pod("anywhere", LabelSelector()))
        for rnd in range(3):            # every round syncs the one node
            for i in range(4):
                hub.create_pod(affinity_pod(f"p{rnd}-{i}"))
            sched.run_until_idle()
        # the slots of the last round's pods wait for the next sync
        hub.create_pod(affinity_pod("next"))
        sched.run_until_idle()
        sched.run_maintenance()
        m, mirror = sched.metrics, sched.mirror
        st = mirror.slot_row_cache_stats()
        assert set(st) == {"hits", "misses", "bypass", "clears", "entries"}
        assert (st["misses"], st["bypass"], st["clears"]) == (1, 1, 0)
        assert st["hits"] >= 11 and st["entries"] == 1
        assert (st["hits"] + st["misses"] + st["bypass"]
                == mirror.sync_stats()["slots_packed_terms"])
        for result, key in (("hit", "hits"), ("miss", "misses"),
                            ("bypass", "bypass")):
            assert m.mirror_slot_row_cache.value(result=result) == st[key]
        text = m.registry.render_text()
        assert (f'scheduler_mirror_slot_row_cache_total{{result="hit"}} '
                f'{st["hits"]}') in text
        assert ('scheduler_mirror_slot_row_cache_total{result="bypass"} 1'
                in text)
        sched._grow(CapacityError("pod_labels", sched.caps.pod_labels + 1))
        assert sched.mirror.slot_row_cache_stats() == {**st, "entries": 0}
        hub.create_pod(affinity_pod("late"))
        sched.run_until_idle()
        sched.run_maintenance()
        after = sched.mirror.slot_row_cache_stats()
        # the fresh mirror packs the cluster again: one more miss, then hits
        assert after["misses"] == 2 and after["bypass"] == 2
        assert (after["hits"] + after["misses"] + after["bypass"]
                == sched.mirror.sync_stats()["slots_packed_terms"])
        assert m.mirror_slot_row_cache.value(result="hit") == after["hits"]
        assert m.mirror_slot_row_cache.value(result="miss") == 2
        assert m.mirror_slot_terms.value() == (
            after["hits"] + after["misses"] + after["bypass"])
        srv = ServingEndpoints(sched, port=0, debug_auth=token_auth("t"))
        srv.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/debug/trace?n=1")
            req.add_header("Authorization", "Bearer t")
            tr = json.loads(urllib.request.urlopen(req, timeout=5).read())
        finally:
            srv.stop()
        assert tr["slot_row_cache"] == after
    finally:
        sched.close()


def test_mirror_sync_counts_reach_the_registry_and_debug_trace():
    """Mirror.sync_stats() -> scheduler_mirror_slot_total{result} (by
    delta, at maintenance) and /debug/trace's "mirror_sync"; a pod that
    binds once packs one slot, and a re-bucketed mirror carries the
    totals on."""
    from kubernetes_tpu.api.objects import (
        LABEL_HOSTNAME,
        LabelSelector,
        TopologySpreadConstraint,
    )
    from kubernetes_tpu.backend.mirror import CapacityError

    def spread_pod(name):
        # a topology launch never chains: every one syncs the mirror
        pod = mk_sched_pod(name)
        pod.metadata.labels = {"color": "blue"}
        pod.spec.topology_spread_constraints = [TopologySpreadConstraint(
            max_skew=50, topology_key=LABEL_HOSTNAME,
            when_unsatisfiable="DoNotSchedule",
            label_selector=LabelSelector(match_labels={"color": "blue"}))]
        return pod

    hub = Hub()
    sched = _sched(hub)
    try:
        hub.create_node(mknode(0))
        for rnd in range(4):            # every round touches the one node
            for i in range(4):
                hub.create_pod(spread_pod(f"p{rnd}-{i}"))
            sched.run_until_idle()
        # an informer resend: a new object of equal content, seen by the
        # sync of the next launch
        bound = next(p for p in hub.list_pods() if p.spec.node_name)
        sched.cache.update_pod(bound, bound.clone())
        hub.create_pod(spread_pod("next"))
        sched.run_until_idle()
        sched.run_maintenance()
        m, st = sched.metrics, sched.mirror.sync_stats()
        assert set(st) == {"rows_synced", "slots_packed",
                           "slots_packed_terms", "slots_kept",
                           "slots_released"}
        assert st["slots_packed_terms"] == 0, "no pod here carries a term"
        assert st["slots_packed"] == 16, "one slot a pod bound, not two"
        assert st["slots_kept"] == 1 and st["slots_released"] == 0
        for result in ("packed", "kept", "released"):
            assert m.mirror_slots.value(result=result) == st[
                f"slots_{result}"]
        assert (f'scheduler_mirror_slot_total{{result="packed"}} '
                f'{st["slots_packed"]}') in m.registry.render_text()
        sched._grow(CapacityError("pod_labels", sched.caps.pod_labels + 1))
        assert sched.mirror.sync_stats() == st
        hub.create_pod(spread_pod("late"))
        sched.run_until_idle()
        sched.run_maintenance()
        after = sched.mirror.sync_stats()
        # the fresh mirror packs the cluster again, on top of the totals
        assert after["slots_packed"] >= st["slots_packed"] + 17
        assert m.mirror_slots.value(result="packed") == after["slots_packed"]
        assert m.mirror_slots.value(result="kept") == after["slots_kept"]
        srv = ServingEndpoints(sched, port=0, debug_auth=token_auth("t"))
        srv.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/debug/trace?n=1")
            req.add_header("Authorization", "Bearer t")
            tr = json.loads(urllib.request.urlopen(req, timeout=5).read())
        finally:
            srv.stop()
        assert tr["mirror_sync"] == after
    finally:
        sched.close()


# ------------------------------------------------ the collector's pauses


def _watching_recorder():
    hist = Histogram("scheduler_gc_pause_seconds", "", FINE_DURATION_BUCKETS,
                     ("generation",))
    phase = Histogram("phase", "", FINE_DURATION_BUCKETS, ("phase",))
    return FlightRecorder(phase_hist=phase, gc_pause_hist=hist), hist, phase


@pytest.mark.parametrize("where", ["loop_thread", "other_thread"])
def test_forced_collection_is_one_gen2_pause(where):
    guard = GCGuard()
    rec, hist, phase = _watching_recorder()
    guard.watch(rec)
    try:
        was = gc.isenabled()
        gc.disable()                     # no stray collection in between
        try:
            if where == "loop_thread":
                gc.collect(2)
                ident = threading.get_ident()
            else:
                t = threading.Thread(target=gc.collect, args=(2,))
                t.start()
                t.join()
                ident = t.ident
        finally:
            if was:
                gc.enable()
        assert hist.count(generation="2") == 1
        assert hist.count(generation="0") == hist.count(generation="1") == 0
        assert phase.count(phase="gc_pause") == 1
        (name, start, end, thread, _turn, c0, c1), = rec.loop_spans
        assert name == "gc_pause" and thread == ident and end >= start
        assert c0 is None and c1 is None     # the guard's own seconds
    finally:
        guard.unwatch(rec)
    gc.collect(2)                        # unwatched: nothing more arrives
    assert hist.count(generation="2") == 1


def test_guard_hooks_once_and_forgets_dead_recorders():
    guard = GCGuard()
    a, hist_a, _ = _watching_recorder()
    b, hist_b, _ = _watching_recorder()
    guard.watch(a)
    guard.watch(b)
    try:
        assert gc.callbacks.count(guard._on_gc) == 1
        gc.collect(0)
        assert hist_a.count(generation="0") >= 1
        assert hist_b.count(generation="0") >= 1
        del b
        gc.collect(0)                    # a dead recorder is skipped
        guard.watch(a)                   # and dropped at the next watch
        assert all(r() is not None for r in guard._watchers)
    finally:
        guard.unwatch(a)
        gc.callbacks.remove(guard._on_gc)


def test_scheduler_exports_gc_pause_seconds():
    hub = Hub()
    sched = _sched(hub)
    try:
        hub.create_node(mknode(0))
        hub.create_pod(mk_sched_pod("p0"))
        sched.run_until_idle()           # the drain's exit sweeps gen 1
        assert sched.metrics.gc_pause.count(generation="1") >= 1
        assert sched.metrics.phase_duration.count(phase="gc_pause") >= 1
        assert "scheduler_gc_pause_seconds_bucket" in \
            sched.metrics.registry.render_text()
    finally:
        sched.close()
