"""What the queue and the collector guard count about themselves, and how
the flight recorder shows it: PriorityQueue._trim_events (calls, scans,
scan seconds, the event log's high-water length -> the queue_done view and
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


def _in_flight(q, n):
    for i in range(n):
        q.add(mkpod(f"p{i}"))
    return [q.pop() for _ in range(n)]


@pytest.mark.parametrize("events, in_flight, scans, high_water", [
    (8200, 3, 1, 8200),     # past 8,192 with pods still in flight: a scan
    (8192, 3, 0, 8192),     # at the mark: none yet
    (9000, 1, 0, 9000),     # the last pod leaves: the log clears unscanned
    (0, 2, 0, 0),
])
def test_trim_counts_at_the_queue_boundary(events, in_flight, scans,
                                           high_water):
    q, _clock = mkq(clock=Ticking())
    pods = _in_flight(q, in_flight)
    for _ in range(events):
        q.move_all_to_active_or_backoff(POD_DELETE)
    q.done(pods[0].uid)
    st = q.trim_stats()
    assert st["trim_calls"] == 1
    assert st["trim_scans"] == scans
    assert st["high_water"] == high_water
    assert (st["trim_scan_s"] > 0) == bool(scans)
    if in_flight == 1:
        assert st["entries"] == 0


def test_trim_scan_seconds_accumulate_only_while_scanning():
    q, _clock = mkq(clock=Ticking())
    pods = _in_flight(q, 3)
    for _ in range(8300):
        q.move_all_to_active_or_backoff(POD_DELETE)
    q.done(pods[0].uid)
    q.done(pods[1].uid)                  # still past the mark: scans again
    two = q.trim_scan_s
    assert q.trim_scans == 2 and two == pytest.approx(0.002)
    q.done(pods[2].uid)                  # empty in-flight set: clear, no scan
    assert q.trim_scans == 2 and q.trim_scan_s == two
    assert q.trim_calls == 3 and q.event_log_len() == 0
    assert q.events_high_water == 8300


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
        assert cyc["v"] == 4
        names = [s[0] for s in cyc["spans"]]
        assert names[0] == "queue_pop" and "commit" in names
        for name, start, end, thread in cyc["spans"]:
            assert end >= start and isinstance(thread, str)
        assert {"snapshot_cache", "snapshot_sync", "mirror_sync"} \
            <= set(names)
        assert {s[0] for s in tr["loop_spans"]} >= {
            "lock_wait", "event_intake", "binder_drain", "queue_done",
            "drain_tail", "gc_sweep", "gc_pause"}
        assert set(tr["queue"]) == {"entries", "high_water", "trim_calls",
                                    "trim_scans", "trim_scan_s"}
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
        (name, start, end, thread, _turn), = rec.loop_spans
        assert name == "gc_pause" and thread == ident and end >= start
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
