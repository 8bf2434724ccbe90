"""Flight recorder + pod lifecycle timelines + /debug trace surface.

The always-on CycleTrace recorder (utils/tracing.py): every scheduling
cycle's phases into a bounded ring + the phase/plugin histograms, pod
lifecycle stamps behind /debug/pod, and the authz-gated serving
endpoints that expose both. The slow-cycle line (CycleTrace.log_if_slow)
keeps its coverage in test_metrics.py.
"""

import json
import math
import os
import urllib.error
import urllib.request

import pytest

from kubernetes_tpu.api.objects import (
    Container,
    LABEL_HOSTNAME,
    Node,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    ResourceRequirements,
)
from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.metrics import FINE_DURATION_BUCKETS, Histogram
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.serving import ServingEndpoints, token_auth
from kubernetes_tpu.utils.tracing import (
    CYCLE_PHASES,
    CycleTrace,
    DRA_VIEW_PHASES,
    FlightRecorder,
    HOST_PHASES,
    PodTimelines,
)


def mknode(i):
    return Node(metadata=ObjectMeta(name=f"node-{i}",
                                    labels={LABEL_HOSTNAME: f"node-{i}"}),
                status=NodeStatus(allocatable={"cpu": "8", "memory": "16Gi",
                                               "pods": "110"}))


def mkpod(name, cpu="100m"):
    return Pod(metadata=ObjectMeta(name=name),
               spec=PodSpec(containers=[Container(
                   name="c", resources=ResourceRequirements(
                       requests={"cpu": cpu}))]))


def _sched(hub, recorder_capacity=256, export_path=None):
    cfg = default_config()
    cfg.batch_size = 16
    cfg.flight_recorder_capacity = recorder_capacity
    cfg.trace_export_path = export_path
    return Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))


# ------------------------------------------------- CycleTrace units


def test_cycle_trace_accumulates_and_totals():
    tr = CycleTrace(cycle=1, start=100.0, pods=8)
    tr.add("host_plugins", 0.01)
    tr.add("host_plugins", 0.02)   # touched twice: accumulates
    tr.add("device_launch", 0.1)
    tr.add("dra_mask_compile", 0.001)  # VIEWS: excluded from total()
    tr.add("dra_device_eval", 0.004)
    assert abs(tr.phases["host_plugins"] - 0.03) < 1e-12
    assert abs(tr.total() - 0.13) < 1e-12
    d = tr.to_dict()
    assert d["phases_ms"]["dra_device_eval"] == 4.0


def test_phase_vocabulary():
    # host-tail arithmetic depends on these set relations
    assert set(HOST_PHASES) < set(CYCLE_PHASES)
    assert set(DRA_VIEW_PHASES) < set(CYCLE_PHASES)
    assert not set(DRA_VIEW_PHASES) & set(HOST_PHASES)
    assert "device_launch" not in HOST_PHASES


# --------------------------------------------- FlightRecorder units


def _hists():
    phase = Histogram("phase", buckets=FINE_DURATION_BUCKETS,
                      label_names=("phase",))
    plugin = Histogram("plugin", buckets=FINE_DURATION_BUCKETS,
                       label_names=("plugin", "extension_point"))
    return phase, plugin


def test_recorder_ring_is_bounded_and_feeds_histograms():
    phase, plugin = _hists()
    rec = FlightRecorder(phase_hist=phase, plugin_hist=plugin, capacity=4)
    for i in range(10):
        tr = rec.begin(start=float(i), pods=2)
        tr.add("queue_pop", 0.001)
        tr.add("commit", 0.002)
        rec.record(tr)
    assert len(rec.ring) == 4, "ring bounded at capacity"
    assert [t["cycle"] for t in rec.last(2)] == [9, 10]
    assert rec.last(0) == [] and rec.last(-5) == [], \
        "n<=0 asks for nothing, not the whole ring"
    assert phase.count(phase="queue_pop") == 10
    assert phase.count(phase="commit") == 10
    pct = rec.phase_percentiles()
    assert set(pct) == {"queue_pop", "commit"}
    assert pct["commit"]["count"] == 10


def test_recorder_disabled_paths():
    rec = FlightRecorder(capacity=0)
    assert not rec.enabled
    tr = rec.begin(start=0.0, pods=4)
    tr.add("commit", 1.0)            # null trace: add is a no-op
    assert tr.phases == {}
    rec.record(tr)
    rec.observe_phase("commit", 1.0)
    rec.plugin_observe("NodeAffinity", "Filter", 1.0)
    assert len(rec.ring) == 0
    assert rec.phase_percentiles() == {} or rec.phase_hist is None


def test_plugin_observe_feeds_dra_view():
    phase, plugin = _hists()
    rec = FlightRecorder(phase_hist=phase, plugin_hist=plugin)
    tr = rec.begin(start=0.0, pods=1)
    rec.plugin_observe("NodeAffinity", "Filter", 0.001)
    rec.plugin_observe("DynamicResources", "Filter", 0.002)
    rec.plugin_observe("DynamicResources", "Reserve", 0.003)
    rec.record(tr)
    # per-plugin timings land on the current cycle...
    assert tr.plugins["NodeAffinity/Filter"] == 0.001
    # ...and DynamicResources time additionally fills the split dra_*
    # phase views: host Filter time -> dra_device_eval, commit-time
    # Reserve bookkeeping -> dra_commit
    assert abs(tr.phases["dra_device_eval"] - 0.002) < 1e-12
    assert abs(tr.phases["dra_commit"] - 0.003) < 1e-12
    assert plugin.count(plugin="DynamicResources",
                        extension_point="Filter") == 1
    keys = set(rec.plugin_percentiles())
    assert {"NodeAffinity/Filter", "DynamicResources/Reserve"} <= keys


def test_recorder_resume_reattaches_dispatched_cycle():
    phase, plugin = _hists()
    rec = FlightRecorder(phase_hist=phase, plugin_hist=plugin)
    tr_k = rec.begin(start=0.0, pods=1)
    tr_k1 = rec.begin(start=1.0, pods=1)   # pipelined: k+1 dispatched
    assert rec.current is tr_k1
    rec.resume(tr_k)                        # finishing k: plugins land on k
    rec.plugin_observe("DynamicResources", "Reserve", 0.001)
    assert "dra_commit" in tr_k.phases
    assert "dra_commit" not in tr_k1.phases
    rec.record(tr_k)
    assert rec.current is None or rec.current is tr_k1


def test_host_tail_share():
    phase, _ = _hists()
    rec = FlightRecorder(phase_hist=phase)
    tr = rec.begin(start=0.0, pods=1)
    tr.add("host_plugins", 0.03)           # host
    tr.add("device_launch", 0.06)          # device
    tr.add("commit", 0.01)                 # host
    tr.add("dra_device_eval", 0.02)        # view: excluded
    rec.record(tr)
    assert abs(rec.host_tail_share() - 0.4) < 1e-9


def test_commit_pull_overlap_excluded_from_total_and_tail():
    """Pipelined waves: the commit thread's device pull is booked as the
    "commit_pull" overlap phase — rendered per cycle, but excluded from
    total() and host_tail_share(); device_launch carries only the loop
    thread's actual blocked wait. Before the split the pull landed in
    device_launch on the pipelined arm, counting overlapped commit-thread
    time as if the loop had been stalled on it."""
    from kubernetes_tpu.utils.tracing import (
        EXCLUDED_PHASES,
        OVERLAP_PHASES,
        VIEW_PHASES,
    )

    assert "commit_pull" in CYCLE_PHASES
    assert "commit_pull" in OVERLAP_PHASES
    assert set(EXCLUDED_PHASES) == set(VIEW_PHASES) | set(OVERLAP_PHASES)
    phase, _ = _hists()
    rec = FlightRecorder(phase_hist=phase)
    tr = rec.begin(start=0.0, pods=1)
    tr.add("host_plugins", 0.03)           # host
    tr.add("device_launch", 0.06)          # loop-thread blocked wait
    tr.add("commit", 0.01)                 # host
    tr.add("commit_pull", 0.05)            # commit-thread pull: overlap
    rec.record(tr)
    # the pull never inflates the cycle total...
    assert abs(tr.total() - 0.10) < 1e-12
    assert tr.to_dict()["total_ms"] == 100.0
    # ...or the host-tail attribution...
    assert abs(rec.host_tail_share() - 0.4) < 1e-9
    # ...but still renders per cycle for /debug/trace readers
    assert tr.to_dict()["phases_ms"]["commit_pull"] == 50.0


def test_recorder_jsonl_export(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    rec = FlightRecorder(capacity=8, export_path=path)
    for i in range(3):
        tr = rec.begin(start=float(i), pods=1)
        tr.add("commit", 0.001 * (i + 1))
        rec.record(tr)
    rec.close()
    lines = [json.loads(ln) for ln in open(path)]
    assert [ln["cycle"] for ln in lines] == [1, 2, 3]
    assert lines[2]["phases_ms"]["commit"] == 3.0


def test_export_rotation_bounds_disk(tmp_path):
    path = str(tmp_path / "t.jsonl")
    rec = FlightRecorder(capacity=8, export_path=path,
                         export_max_bytes=2000)
    for i in range(100):
        tr = rec.begin(start=float(i), pods=1)
        tr.add("commit", 0.001)
        rec.record(tr)
    rec.close()
    assert os.path.exists(path + ".1"), "keep-last-1 rotation happened"
    assert os.path.getsize(path) <= 2200
    assert os.path.getsize(path + ".1") <= 2200
    # every surviving line is intact JSON (rotation never tears a line)
    for p in (path, path + ".1"):
        for line in open(p):
            json.loads(line)


def test_export_rotation_failure_disables_export(tmp_path, monkeypatch):
    """A failed rotation (permissions/directory gone) must DISABLE the
    export, not fall back to unbounded appends — the size bound is the
    feature's contract."""
    path = str(tmp_path / "t.jsonl")
    rec = FlightRecorder(capacity=8, export_path=path,
                         export_max_bytes=500)

    def deny(*_a):
        raise OSError("denied")

    monkeypatch.setattr("kubernetes_tpu.utils.tracing.os.replace", deny)
    for i in range(50):
        tr = rec.begin(start=float(i), pods=1)
        tr.add("commit", 0.001)
        rec.record(tr)
    assert not rec.exporting, "failed rotation disables the export"
    assert os.path.getsize(path) <= 700, "writes stopped at the bound"
    rec.close()


# --------------------------------------------------- PodTimelines


def test_timelines_lru_and_lookup():
    tl = PodTimelines(capacity=2, now=lambda: 1.0)
    pods = [mkpod(f"p{i}") for i in range(3)]
    for p in pods:
        tl.event(p, "enqueued")
    assert len(tl) == 2, "LRU bounded"
    assert tl.get(name="p0") is None, "oldest evicted"
    got = tl.get(name="p2")
    assert got["events"][0]["event"] == "enqueued"
    assert tl.get(uid=pods[1].metadata.uid)["name"] == "p1"
    tl.forget(pods[1].metadata.uid)
    assert tl.get(name="p1") is None


def test_timelines_event_cap_keeps_head_and_tail():
    tl = PodTimelines(now=lambda: 0.0)
    p = mkpod("stormy")
    tl.event(p, "enqueued")
    for i in range(200):
        tl.event(p, "popped", f"attempt {i}")
    events = tl.get(name="stormy")["events"]
    assert len(events) <= PodTimelines.MAX_EVENTS_PER_POD
    assert events[0]["event"] == "enqueued", "timeline anchor survives"
    assert events[-1]["detail"] == "attempt 199", "newest tail survives"


def test_timelines_diagnosis():
    tl = PodTimelines(now=lambda: 5.0)
    p = mkpod("sick")
    tl.diagnose(p, {"NodeResourcesFit": 12}, {"VolumeZone": 1},
                "no feasible node")
    d = tl.get(name="sick")["diagnosis"]
    assert d["device_rejects"] == {"NodeResourcesFit": 12}
    assert d["host_rejects"] == {"VolumeZone": 1}
    assert d["at"] == 5.0


# -------------------------------------- scheduler integration


def test_scheduler_records_cycle_phases_and_timelines():
    hub = Hub()
    sched = _sched(hub)
    try:
        hub.create_node(mknode(0))
        for i in range(5):
            hub.create_pod(mkpod(f"p{i}"))
        sched.run_until_idle()
        assert len(sched.flight.ring) >= 1
        cyc = sched.flight.last(1)[0]
        for phase in ("queue_pop", "snapshot_sync", "pack",
                      "device_dispatch", "device_launch", "commit"):
            assert phase in cyc["phases_ms"], phase
        assert cyc["scheduled"] >= 1
        # phase histogram fed (the /metrics surface)
        m = sched.metrics
        assert m.phase_duration.count(phase="commit") >= 1
        # per-plugin timing under the new plugin label
        assert m.plugin_duration.total_count() >= 1
        # the reference's e2e pod_scheduling_duration_seconds by attempts
        assert m.pod_e2e_duration.count(attempts="1") == 5
        # timelines: wire-created -> enqueued -> popped -> bound (the
        # hub commit's trace stamp now anchors the timeline)
        t = sched.timelines.get(name="p0")
        evs = [e["event"] for e in t["events"]]
        assert evs[0] == "wire:created"
        assert evs[1] == "enqueued"
        assert "popped" in evs and "bound" in evs
        # the cross-wire join: created + bound stamps present (no
        # kubelet in this harness, so no ack — joined stays None)
        assert "created" in t["wire"] and "bound" in t["wire"]
        assert t["joined"] is None
        text = m.registry.render_text()
        assert "scheduling_phase_duration_seconds_bucket" in text
        assert "plugin_execution_duration_seconds_bucket" in text
        assert "pod_scheduling_duration_seconds_bucket" in text
    finally:
        sched.close()


def test_scheduler_unschedulable_diagnosis():
    hub = Hub()
    sched = _sched(hub)
    try:
        hub.create_node(mknode(0))
        hub.create_pod(mkpod("big", cpu="64"))   # never fits the 8-cpu node
        sched.run_until_idle()
        t = sched.timelines.get(name="big")
        assert t is not None
        evs = [e["event"] for e in t["events"]]
        assert "unschedulable" in evs and "bound" not in evs
        d = t["diagnosis"]
        assert d is not None
        # the device filter that rejected, from the pulled reject_counts
        assert "NodeResourcesFit" in d["device_rejects"]
        assert d["device_rejects"]["NodeResourcesFit"] >= 1
    finally:
        sched.close()


def test_scheduler_recorder_disabled_still_schedules():
    hub = Hub()
    sched = _sched(hub, recorder_capacity=0)
    try:
        assert not sched.flight.enabled
        hub.create_node(mknode(0))
        hub.create_pod(mkpod("p"))
        sched.run_until_idle()
        assert hub.get_pod(
            [p for p in hub.list_pods()][0].metadata.uid
        ).spec.node_name, "pod bound with the recorder off"
        assert len(sched.flight.ring) == 0
        assert sched.metrics.phase_duration.total_count() == 0
    finally:
        sched.close()


def test_scheduler_trace_export(tmp_path):
    path = str(tmp_path / "cycles.jsonl")
    hub = Hub()
    sched = _sched(hub, export_path=path)
    try:
        hub.create_node(mknode(0))
        hub.create_pod(mkpod("p"))
        sched.run_until_idle()
    finally:
        sched.close()
    lines = [json.loads(ln) for ln in open(path)]
    assert lines and "phases_ms" in lines[0]


def test_export_v2_rows_name_the_bound_node_and_carry_a_finite_score(
        tmp_path):
    """Each bound pod has one placement row: it names the node the pod
    is bound to and carries a finite aggregate score, and nothing else
    of the launch."""
    from kubernetes_tpu.utils.tracing import EXPORT_VERSION

    export = str(tmp_path / "traces.jsonl")
    hub = Hub()
    sched = _sched(hub, export_path=export)
    try:
        hub.create_node(mknode(0))
        for i in range(4):
            hub.create_pod(mkpod(f"p{i}"))
        sched.run_until_idle()
    finally:
        sched.close()
    lines = [json.loads(x) for x in open(export) if x.strip()]
    assert lines and all(ln["v"] == EXPORT_VERSION for ln in lines)
    placed = [r for ln in lines for r in ln.get("placements", [])
              if r["node"]]
    bound = {p.metadata.uid: p.spec.node_name for p in hub.list_pods()}
    assert sorted(r["uid"] for r in placed) == sorted(bound)
    for r in placed:
        assert r["node"] == bound[r["uid"]] == "node-0"
        assert math.isfinite(r["score"]) and r["score"] > 0
        assert set(r) <= {"pod", "uid", "node", "score", "wire"}


# --------------------------------------- /debug/trace + /debug/pod


def _get(url, token=None):
    req = urllib.request.Request(url)
    if token:
        req.add_header("Authorization", f"Bearer {token}")
    return urllib.request.urlopen(req, timeout=5)


def test_debug_trace_and_pod_endpoints_authz():
    hub = Hub()
    sched = _sched(hub)
    try:
        hub.create_node(mknode(0))
        hub.create_pod(mkpod("p0"))
        hub.create_pod(mkpod("big", cpu="64"))
        sched.run_until_idle()

        # no authz callback: 403 for the whole /debug surface
        srv = ServingEndpoints(sched, port=0)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            for ep in ("/debug/trace", "/debug/pod?name=p0"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _get(base + ep)
                assert ei.value.code == 403, ep
        finally:
            srv.stop()

        # token authz: bad/missing bearer 401, good token 200 + data
        srv = ServingEndpoints(sched, port=0,
                               debug_auth=token_auth("s3cret"))
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            for ep in ("/debug/trace", "/debug/pod?name=p0"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _get(base + ep)
                assert ei.value.code == 401, ep
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _get(base + ep, token="wrong")
                assert ei.value.code == 401, ep

            tr = json.loads(_get(f"{base}/debug/trace?n=4",
                                 token="s3cret").read())
            assert tr["enabled"] is True
            assert tr["cycles"], "ring exposed"
            assert len(tr["cycles"]) <= 4
            assert "commit" in tr["phases"]
            assert 0.0 <= tr["host_tail_share"] <= 1.0
            # no daemon ran: the loop's idle waits are both zero
            assert tr["idle_waits"] == {"event": 0.0, "timeout": 0.0}

            pd = json.loads(_get(f"{base}/debug/pod?name=p0",
                                 token="s3cret").read())
            assert pd["name"] == "p0"
            assert [e["event"] for e in pd["events"]][:2] \
                == ["wire:created", "enqueued"]
            # the unschedulable pod's diagnosis rides the same endpoint
            sick = json.loads(_get(f"{base}/debug/pod?name=big",
                                   token="s3cret").read())
            assert sick["diagnosis"] is not None

            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{base}/debug/pod?name=nope", token="s3cret")
            assert ei.value.code == 404
        finally:
            srv.stop()
    finally:
        sched.close()


# suite-tier discipline (tests/test_markers.py): area marker
pytestmark = pytest.mark.observability


# ------------------------- spans: instants, delivered where they end


class TickClock:
    """A fake clock that moves one tick per read, for every thread, and
    remembers each thread's last reading: what a wrap of CycleTrace.add
    sees as "now" when nothing read the clock since the span ended."""

    def __init__(self, tick=1e-4):
        import threading

        self.t = 1000.0
        self.tick = tick
        self._lock = threading.Lock()
        self._last = threading.local()

    def __call__(self):
        with self._lock:
            self.t += self.tick
            self._last.t = self.t
            return self.t

    def last(self):
        return self._last.t


def _install_wrap(monkeypatch, clock):
    """benchmark/cell.py::PhaseSpans's wrap of the two delivery methods,
    on the fake clock, every thread kept: [(phase, start, end, thread
    ident, cycle or None)] reconstructed as (now - secs, now)."""
    import threading

    got = []
    add, observe = CycleTrace.add, FlightRecorder.observe_phase

    def traced_add(tr, phase, secs):
        t = clock.last()
        got.append((phase, t - secs, t, threading.get_ident(), tr.cycle))
        add(tr, phase, secs)

    def traced_observe(fl, phase, secs):
        t = clock.last()
        got.append((phase, t - secs, t, threading.get_ident(), None))
        observe(fl, phase, secs)

    monkeypatch.setattr(CycleTrace, "add", traced_add)
    monkeypatch.setattr(FlightRecorder, "observe_phase", traced_observe)
    return got


def _pipelined_drain(monkeypatch, pods=96, batch=8):
    """A pipelined drain on the CPU under the tick clock and the wrap;
    returns (scheduler, reconstructed spans, loop thread ident)."""
    import threading

    clock = TickClock()
    got = _install_wrap(monkeypatch, clock)
    hub = Hub()
    for i in range(8):
        hub.create_node(mknode(i))
    cfg = default_config()
    cfg.batch_size = batch
    cfg.pipelined_waves = True
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=256),
                      now=clock)
    for i in range(pods):
        hub.create_pod(mkpod(f"p{i}"))
    sched.run_until_idle()
    return sched, got, threading.get_ident()


def _exclusive(name):
    from kubernetes_tpu.utils.tracing import OVERLAP_PHASES, VIEW_PHASES

    return name not in VIEW_PHASES and name not in OVERLAP_PHASES


def test_exclusive_phases_are_delivered_where_they_end(monkeypatch):
    """(a) every exclusive phase of the loop thread reaches the two
    patched methods at the instant its span ends, so the benchmark's
    reconstruction (now - secs, now) IS the recorded span."""
    sched, got, me = _pipelined_drain(monkeypatch)
    try:
        fl = sched.flight
        recorded = [(n, a, b) for tr in fl.ring
                    for n, a, b, t, *_cpu in tr.spans
                    if t == me and _exclusive(n)]
        recorded += [(n, a, b) for n, a, b, t, _turn, *_cpu in fl.loop_spans
                     if t == me and _exclusive(n)]
        rebuilt = {(n, round(a, 9), round(b, 9))
                   for n, a, b, t, _c in got if t == me and _exclusive(n)}
        names = {n for n, _a, _b in recorded}
        for want in ("queue_pop", "pack", "device_dispatch", "device_launch",
                     "commit", "binder_drain", "event_intake", "drain_tail",
                     "lock_wait", "gc_sweep"):
            assert want in names, want
        for n, a, b in recorded:
            assert (n, round(a, 9), round(b, 9)) in rebuilt, (n, a, b)
        # exactly once per span: no phase is delivered a second time
        assert len([1 for n, _a, _b, t, _c in got
                    if t == me and _exclusive(n)]) == len(recorded)
    finally:
        sched.close()


def test_commit_is_not_laid_under_its_own_pull_or_wait(monkeypatch):
    """(b) the reconstructed commit span of a cycle shares under 5% of
    its length with the same cycle's commit_pull and device_launch: both
    are reported before the commit loop starts, the pull from the commit
    thread."""
    sched, got, me = _pipelined_drain(monkeypatch)
    try:
        by_cycle = {}
        for n, a, b, t, cyc in got:
            if cyc is not None and cyc > 0:
                by_cycle.setdefault(cyc, {}).setdefault(n, []).append(
                    (a, b, t))
        checked = 0
        for cyc, ph in by_cycle.items():
            if "commit" not in ph or "commit_pull" not in ph:
                continue
            (ca, cb, _t), = ph["commit"]
            assert all(t != me for _a, _b, t in ph["commit_pull"])
            for other in ph["commit_pull"] + ph["device_launch"]:
                shared = min(cb, other[1]) - max(ca, other[0])
                assert shared < 0.05 * (cb - ca), (cyc, ph)
            checked += 1
        assert checked >= 4
    finally:
        sched.close()


def test_loop_turns_are_tiled_by_exclusive_spans():
    """(c) over twenty turns of Scheduler.run the exclusive spans of the
    loop thread never overlap on the wall clock and hold at least 95% of
    the CPU time the thread spent between the first span's start and the
    last span's end, by the spans' own readings of the thread's CPU
    clock: what they leave out is the glue between spans, and on the
    thread's CPU clock that is the glue's own work, the same on a quiet
    machine and a busy one (ROADMAP R-A15). A reading shared by two
    spans (tracing.CPU_REUSE_S) puts glue shorter than that into the
    later span; anything longer is left out and shows here, and a lost
    span shows: without pack the same spans fall under the 95%. On the
    wall clock the glue also holds the interpreter's hand-overs, whose
    length follows the machine (0.97-0.98 alone, 0.94-0.98 beside busy
    neighbours), so there the floor is a loose 80%: it is there for a
    blocking wait between two spans, which burns no CPU."""
    import time

    hub = Hub()
    for i in range(8):
        hub.create_node(mknode(i))
    sched = _sched(hub)
    try:
        hub.create_pod(mkpod("warm"))
        sched.run_until_idle()              # the compile, outside the turns
        sched.start()
        fl = sched.flight
        time.sleep(0.05)
        first = fl.turn
        k = 0
        while fl.turn < first + 22 and k < 400:
            hub.create_pod(mkpod(f"p{k}"))
            k += 1
            time.sleep(0.01)
        me = sched._daemon.ident
        last = fl.turn
        sched.stop()
        assert last >= first + 20
        loop = [(a, b, n, c0, c1)
                for n, a, b, t, turn, c0, c1 in fl.loop_spans
                if t == me and _exclusive(n) and first < turn < last]
        t0 = min(a for a, *_rest in loop)
        t1 = max(b for _a, b, *_rest in loop)
        spans = loop + [(a, b, n, c0, c1) for tr in fl.ring
                        for n, a, b, t, c0, c1 in tr.spans
                        if t == me and _exclusive(n) and t0 <= a and b <= t1]
        spans.sort()
        assert {"idle_wait", "maintenance", "lock_wait", "event_intake",
                "gc_sweep", "drain_tail"} <= {s[2] for s in spans}
        for (_a, b, n, *_c), (a2, _b2, n2, *_c2) in zip(spans, spans[1:]):
            assert a2 >= b, (n, n2)
        assert all(c0 is not None and c1 is not None and c1 >= c0
                   for _a, _b, _n, c0, c1 in spans)
        spent = spans[-1][4] - spans[0][3]
        covered = sum(c1 - c0 for _a, _b, _n, c0, c1 in spans)
        assert spent > 0
        # for the message: the fattest CPU gaps, in us, by the spans around
        fattest = sorted(((round((nxt[3] - prev[4]) * 1e6), prev[2], nxt[2])
                          for prev, nxt in zip(spans, spans[1:])),
                         reverse=True)[:4]
        assert covered >= 0.95 * spent, (covered / spent, fattest)
        lost = sum(c1 - c0 for _a, _b, n, c0, c1 in spans if n != "pack")
        assert lost < 0.95 * spent, lost / spent
        on_the_wall = sum(b - a for a, b, *_rest in spans)
        assert on_the_wall >= 0.8 * (t1 - t0), on_the_wall / (t1 - t0)
    finally:
        sched.close()


def test_loop_phases_and_new_views_leave_the_headlines_alone():
    """(d) LOOP_PHASES and the new views change neither CycleTrace.total()
    nor host_tail_share()."""
    from kubernetes_tpu.utils.tracing import (
        CYCLE_PHASES,
        LOOP_PHASES,
        LOOP_VIEW_PHASES,
        UNCOUNTED_PHASES,
        VIEW_PHASES,
    )

    assert not set(LOOP_PHASES) & set(CYCLE_PHASES)
    assert not set(LOOP_PHASES) & set(HOST_PHASES)
    assert {"queue_done", "snapshot_cache", "mirror_sync", "gc_pause",
            "preempt_select"} <= set(VIEW_PHASES)
    assert set(LOOP_PHASES) | set(LOOP_VIEW_PHASES) <= UNCOUNTED_PHASES
    phase, _ = _hists()
    rec = FlightRecorder(phase_hist=phase)
    tr = rec.begin(start=0.0, pods=1)
    tr.add("host_plugins", 0.03)
    tr.add("device_launch", 0.06)
    tr.add("commit", 0.01)
    before = tr.total()
    tr.add("snapshot_cache", 0.02)
    tr.add("mirror_sync", 0.02)
    rec.record(tr)
    share = rec.host_tail_share()
    for p in LOOP_PHASES + LOOP_VIEW_PHASES:
        rec.observe_phase(p, 0.5)
    assert tr.total() == before
    assert abs(share - 0.4) < 1e-9
    assert rec.host_tail_share() == share


class FakeCpu:
    """A thread CPU clock the test sets: every read returns ``t`` and then
    moves it by ``tick``."""

    def __init__(self, tick=0.0):
        self.t = 50.0
        self.tick = tick
        self.reads = 0

    def __call__(self):
        self.reads += 1
        t = self.t
        self.t += self.tick
        return t


def test_span_export_v5_and_disabled_recorder(tmp_path):
    """A cycle's spans ride its export line as [name, start, end, thread,
    ms between the two CPU readings] beside phases_ms and cpu_ms (what the
    spans delivered); a disabled recorder still times a span for its
    caller, reads no CPU clock and records nothing."""
    from kubernetes_tpu.utils.tracing import EXPORT_VERSION

    clock, cpu = TickClock(tick=0.5), FakeCpu(tick=0.125)
    path = str(tmp_path / "t.jsonl")
    rec = FlightRecorder(capacity=4, export_path=path, now=clock,
                         cpu_now=cpu)
    tr = rec.begin(start=clock(), pods=1)
    with rec.span("pack", tr) as sp:
        pass
    half = rec.span("queue_pop")
    half.end(tr=tr)                      # handed to a cycle opened later
    with rec.span("idle_wait"):
        pass
    rec.record(tr)
    rec.close()
    line = json.loads(open(path).read().splitlines()[0])
    assert line["v"] == EXPORT_VERSION == 5
    assert [s[0] for s in line["spans"]] == ["pack", "queue_pop"]
    assert line["spans"][0][1:3] == [sp.t0, sp.t1] and sp.secs == 0.5
    assert [s[4] for s in line["spans"]] == [125.0, 125.0]
    assert (sp.c0, sp.c1, sp.cpu) == (50.0, 50.125, 0.125)
    assert line["phases_ms"] == {"pack": 500.0, "queue_pop": 500.0}
    assert line["cpu_ms"] == {"pack": 125.0, "queue_pop": 125.0}
    (name, a, b, _thread, turn, cpu_ms), = rec.last_loop_spans()
    assert (name, b - a, turn, cpu_ms) == ("idle_wait", 0.5, 0, 125.0)
    reads = cpu.reads
    off = FlightRecorder(capacity=0, now=clock, cpu_now=cpu)
    with off.span("commit", off.begin(0.0, 1)) as sp:
        pass
    assert sp.secs == 0.5 and not off.ring and not off.loop_spans
    assert sp.cpu is None and cpu.reads == reads


# ------------------- spans: the thread's CPU clock beside the wall clock


def _cpu_recorder(wall_tick=0.5, cpu_tick=0.125):
    phase, _ = _hists()
    return phase, FlightRecorder(phase_hist=phase, now=TickClock(wall_tick),
                                 cpu_now=FakeCpu(cpu_tick))


def _sum(hist, phase):
    return hist.snapshot().get(str({"phase": phase}), {"sum": None})["sum"]


@pytest.mark.parametrize("cpu_tick, want", [
    (0.125, 0.125),       # on the interpreter a quarter of the interval
    (0.0, 0.0),           # off it all the while
    (2.0, 0.5),           # a CPU clock that ran ahead: held to the wall
    (-1.0, 0.0),          # or backwards: held to zero
])
def test_a_spans_cpu_seconds_stay_within_its_wall_seconds(cpu_tick, want):
    """What a span delivers to its phase's .cpu series is held to [0, its
    wall seconds] by the one rule (_settle_cpu); the span itself and the
    span lists keep the readings as read."""
    phase, rec = _cpu_recorder(cpu_tick=cpu_tick)
    with rec.span("maintenance") as sp:
        pass
    assert sp.secs == 0.5
    assert sp.cpu == sp.c1 - sp.c0 == cpu_tick
    (*_x, shown), = rec.last_loop_spans()
    assert shown == cpu_tick * 1e3
    assert _sum(phase, "maintenance") == 0.5
    assert _sum(phase, "maintenance.cpu") == want


def test_a_cpu_clock_that_ticks_keeps_a_phases_sum_and_stays_under_the_wall():
    """The benchmark's host advances a thread's CPU clock a tick at a time
    (milliseconds), so a span shorter than the tick reads nothing or a
    whole tick. Cut down to the span, each tick would count as one span's
    length and 500 spans of 0.8 ms of work in 1 ms would read 0.04 s; what
    the cut leaves over is carried to the phase's next spans instead, and
    the phase reads its 0.4 s, no span more than its own length."""

    class Ticking:
        def __init__(self):
            self.true, self.reads = 0.0, 0

        def __call__(self):
            self.reads += 1
            if self.reads % 2 == 0:          # the reading at a span's end
                self.true += 0.0008
            return int(self.true / 0.01 + 1e-9) * 0.01

    phase, _ = _hists()
    rec = FlightRecorder(phase_hist=phase, now=TickClock(tick=0.001),
                         cpu_now=Ticking(), capacity=1024)
    tr = rec.begin(start=0.0, pods=1)
    for _ in range(250):
        with rec.span("maintenance"):
            pass
        with rec.span("commit", tr):
            pass
    rec.record(tr)
    got = {}
    for name in ("maintenance", "commit"):     # a tick lands in either
        got[name] = _sum(phase, name + ".cpu")
        assert 0.17 <= got[name] <= 0.23, got
        assert _sum(phase, name) == pytest.approx(0.25)
    assert 0.38 <= sum(got.values()) <= 0.4 + 1e-9, got
    # each span by itself: a whole tick or nothing, shown as it was read
    shown = [s[5] for s in rec.last_loop_spans(1024)]
    assert set(shown) == {0.0, 10.0} and 17 <= shown.count(10.0) <= 23
    assert tr.cpu["commit"] <= tr.phases["commit"]
    # what a phase is owed stays a tick's remainder
    from kubernetes_tpu.utils.tracing import CPU_CARRY_MAX_S
    assert max(rec._cpu_owed.values()) < CPU_CARRY_MAX_S, rec._cpu_owed


def test_spans_that_follow_each_other_share_the_reading_at_their_boundary():
    """One read of the thread's CPU clock a span where spans tile: a span's
    start takes the last span's closing reading while that is at most
    CPU_REUSE_S old on the wall clock (the glue between the two counts to
    the later one), and reads the clock after a longer gap, on another
    thread, or where nothing was read yet; after a longer gap in which
    the thread burnt under CPU_REUSE_S it still takes the closing
    reading. A span closed unrecorded reads nothing at its end, so what
    it burnt goes to the next span too."""
    import threading

    from kubernetes_tpu.utils.tracing import CPU_REUSE_S

    clock, cpu = TickClock(tick=1e-6), FakeCpu(tick=0.001)
    phase, _ = _hists()
    rec = FlightRecorder(phase_hist=phase, now=clock, cpu_now=cpu)
    a = rec.span("maintenance")
    a.end()
    b = rec.span("lock_wait")
    b.end()
    assert cpu.reads == 3 and b.c0 == a.c1 and b.c1 > b.c0
    empty = rec.span("queue_pop")
    empty.end(report=False)              # an empty pop: no phase
    assert cpu.reads == 3 and empty.c0 == b.c1 and empty.cpu is None
    c = rec.span("event_intake")
    c.end()
    assert cpu.reads == 4 and c.c0 == b.c1
    clock.t += 2 * CPU_REUSE_S           # the thread was elsewhere meanwhile
    d = rec.span("idle_wait")
    d.end()
    assert cpu.reads == 6 and d.c0 > c.c1
    # a gap that a busy machine stretched, not work: the clock is read,
    # and what little the thread burnt still counts to the later span
    clock.t += 2 * CPU_REUSE_S
    cpu.t = d.c1 + CPU_REUSE_S / 4
    e = rec.span("maintenance")
    assert cpu.reads == 7 and e.c0 == d.c1
    e.end()
    assert cpu.reads == 8 and e.c1 > e.c0
    got = []
    other = threading.Thread(
        target=lambda: got.append(rec.span("bind_chunk")) or got[0].end())
    other.start()
    other.join(timeout=10)
    assert cpu.reads == 10 and got[0].c0 > e.c1     # its own clock, read


def test_what_a_phase_is_owed_is_capped():
    """A phase whose spans all read a little more than their length (the
    shared reading's age) is never owed more than CPU_CARRY_MAX_S: a debt
    that grew for hours would hide as much real waiting later."""
    from kubernetes_tpu.utils.tracing import CPU_CARRY_MAX_S

    phase, rec = _cpu_recorder(wall_tick=0.5, cpu_tick=0.6)
    for _ in range(40):
        with rec.span("pack"):
            pass
    assert rec._cpu_owed["pack"] == CPU_CARRY_MAX_S
    assert _sum(phase, "pack.cpu") == _sum(phase, "pack") == 20.0


def test_a_sleeping_span_reads_no_cpu_and_a_spinning_one_its_work():
    """The real clocks: a span that sleeps was off the interpreter, one
    that spins until its thread has burnt 30 ms holds those 30 ms,
    however long the machine took to grant them."""
    import time

    rec = FlightRecorder()
    with rec.span("idle_wait") as asleep:
        time.sleep(0.05)
    assert asleep.secs >= 0.05 and asleep.cpu < 0.01
    with rec.span("commit") as busy:
        until = time.thread_time() + 0.03
        while time.thread_time() < until:
            pass
    # at most the glue ahead of it on top (its start may take the
    # sleeper's closing reading)
    assert 0.03 * 0.9 <= busy.cpu <= busy.secs + 1e-3


def test_a_cycles_cpu_series_are_the_sums_of_its_spans():
    phase, rec = _cpu_recorder()
    tr = rec.begin(start=0.0, pods=2)
    for name in ("pack", "commit", "pack"):
        with rec.span(name, tr):
            pass
    with rec.span("snapshot_sync", tr, view="mirror_sync"):
        pass
    assert phase.total_count() == 0          # nothing before the record
    rec.record(tr)
    by_name = {}
    for n, a, b, _t, c0, c1 in tr.spans:
        assert c1 - c0 <= b - a
        by_name[n] = by_name.get(n, 0.0) + (c1 - c0)
    assert by_name == {"pack": 0.25, "commit": 0.125, "mirror_sync": 0.125,
                       "snapshot_sync": 0.125} == tr.cpu
    for name, cpu in by_name.items():
        assert _sum(phase, name + ".cpu") == cpu
        assert phase.count(phase=name + ".cpu") == 1     # once a cycle
    assert tr.to_dict()["cpu_ms"]["pack"] == 250.0
    assert set(rec.phase_percentiles()) == set(by_name) | {
        n + ".cpu" for n in by_name}


def test_cpu_series_reach_no_delivery_method_and_no_headline(monkeypatch):
    """`.cpu` goes beside the phases, never through CycleTrace.add or
    observe_phase (benchmark/cell.py::PhaseSpans wraps those two and takes
    what it sees for a phase), and neither total() nor host_tail_share()
    counts it."""
    clock = TickClock()
    got = _install_wrap(monkeypatch, clock)
    phase, _ = _hists()
    rec = FlightRecorder(phase_hist=phase, now=clock, cpu_now=FakeCpu(3e-4))
    tr = rec.begin(start=clock(), pods=1)
    for name in ("host_plugins", "device_launch", "commit", "commit_pull"):
        with rec.span(name, tr):
            pass
    for name in ("idle_wait", "binder_drain", "bind_chunk"):
        with rec.span(name):
            pass
    wall_only = sum(v for k, v in tr.phases.items() if k != "commit_pull")
    assert tr.total() == pytest.approx(wall_only)
    assert not any(k.endswith(".cpu") for k in tr.phases)
    rec.record(tr)
    assert [n for n, *_rest in got] == [
        "host_plugins", "device_launch", "commit", "commit_pull",
        "idle_wait", "binder_drain", "bind_chunk"]
    # host_plugins, commit and binder_drain of those three and
    # device_launch: the CPU series would make it another number
    assert rec.host_tail_share() == pytest.approx(0.75)
    # TickClock's 1e-4 a span holds each span's 3e-4 of CPU to its length
    assert _sum(phase, "commit.cpu") == pytest.approx(1e-4)
    assert _sum(phase, "bind_chunk.cpu") == pytest.approx(1e-4)


def test_a_view_another_component_measured_carries_no_cpu():
    phase, rec = _cpu_recorder()
    rec.observe_view("queue_done", 0.25)
    rec.gc_pause(0.01, 2)
    tr = rec.begin(start=0.0, pods=1)
    rec.plugin_observe("DynamicResources", "Reserve", 0.003)
    tr.add("device_compile", 0.2)
    rec.record(tr)
    assert all(c0 is None and c1 is None
               for *_x, c0, c1 in rec.loop_spans)
    assert [s[5] for s in rec.last_loop_spans()] == [None, None]
    assert tr.cpu == {}
    assert not any(p.endswith(".cpu") for p in rec.phase_percentiles())


def test_a_span_ended_on_another_thread_records_no_cpu():
    import threading

    phase, rec = _cpu_recorder()
    sp = rec.span("lock_wait")
    other = threading.Thread(target=sp.end)
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    assert sp.secs == 0.5 and sp.cpu is None and sp.c1 is None
    assert _sum(phase, "lock_wait") == 0.5
    assert _sum(phase, "lock_wait.cpu") is None
    (_n, _a, _b, _t, _turn, cpu_ms), = rec.last_loop_spans()
    assert cpu_ms is None


def test_bind_chunk_is_an_overlap_phase_reported_from_the_binder_threads():
    import threading

    from kubernetes_tpu.utils.tracing import (
        OVERLAP_PHASES,
        UNCOUNTED_PHASES,
    )

    assert "bind_chunk" in OVERLAP_PHASES and "bind_chunk" in UNCOUNTED_PHASES
    hub = Hub()
    for i in range(8):
        hub.create_node(mknode(i))
    sched = _sched(hub)
    try:
        assert sched._binder is not None
        for i in range(40):
            hub.create_pod(mkpod(f"p{i}"))
        sched.run_until_idle()
        fl = sched.flight
        me = threading.get_ident()
        chunks = [s for s in fl.loop_spans if s[0] == "bind_chunk"]
        assert chunks and all(s[3] != me for s in chunks)
        assert all(s[5] is not None and s[6] is not None for s in chunks)
        names = {fl._thread_names[s[3]] for s in chunks}
        assert all(n.startswith("binder") for n in names), names
        m = sched.metrics.phase_duration
        assert m.count(phase="bind_chunk") == len(chunks)
        assert m.count(phase="bind_chunk.cpu") == len(chunks)
        assert not any(n == "bind_chunk" for tr in fl.ring
                       for n, *_rest in tr.spans)
        # every pod's bind ran inside one of them
        assert sched.stats["scheduled"] == 40
    finally:
        sched.close()


# ------------------------------------------------- the preemption sweep's view


def preemption_cluster(hub, nodes=3):
    """`nodes` nodes of 4 cpu, each full of four 900m pods of priority 0
    (400m left): a 3000m pod of priority 10 has to evict three of them."""
    for i in range(nodes):
        hub.create_node(Node(
            metadata=ObjectMeta(name=f"node-{i}",
                                labels={LABEL_HOSTNAME: f"node-{i}"}),
            status=NodeStatus(allocatable={"cpu": "4", "memory": "32Gi",
                                           "pods": "110"})))
        for j in range(4):
            filler = mkpod(f"fill-{i}-{j}", cpu="900m")
            filler.spec.priority = 0
            filler.spec.node_name = f"node-{i}"
            hub.create_pod(filler)


def preemptor(name, priority=10, policy=None):
    pod = mkpod(name, cpu="3000m")
    pod.spec.priority = priority
    if policy is not None:
        pod.spec.preemption_policy = policy
    return pod


def test_preempt_select_view_sits_inside_failure_handling():
    """The view is reported once per failure handling that ran PostFilter,
    before its parent ends and inside its span, with the seconds the
    evaluator's own clock gave batch_preempt; in the benchmark's idle gaps
    it takes no time of its own (its parent started earlier)."""
    import threading

    from benchmark import cell, trace_reduce
    from kubernetes_tpu.utils.tracing import LOOP_VIEW_PHASES, UNCOUNTED_PHASES

    assert "preempt_select" in LOOP_VIEW_PHASES
    assert "preempt_select" in UNCOUNTED_PHASES
    hub = Hub()
    sched = _sched(hub)
    spans = cell.PhaseSpans()
    spans.install()
    try:
        spans.thread_id = threading.get_ident()
        preemption_cluster(hub)
        for i in range(2):
            hub.create_pod(preemptor(f"high-{i}"))
        sched.run_until_idle()
        sched.run_maintenance()
        sched.run_until_idle()
    finally:
        spans.remove()
        sched.close()
    names = [p for p, _a, _b in spans.spans]
    bound = [p for p in hub.list_pods()
             if p.metadata.name.startswith("high-") and p.spec.node_name]
    assert len(bound) == 2
    views = [(a, b) for p, a, b in spans.spans if p == "preempt_select"]
    parents = [(a, b) for p, a, b in spans.spans if p == "failure_handling"]
    assert len(views) == len(parents) >= 1
    for (va, vb), (pa, pb) in zip(views, parents):
        assert pa <= va <= vb <= pb
    for i, name in enumerate(names):
        if name == "preempt_select":
            assert names[i + 1:].index("failure_handling") >= 0
    total = sum((b - a) for a, b in views) / 1e9
    assert total == pytest.approx(sched.preemption.select_s, rel=1e-3,
                                  abs=1e-6)
    assert sched.preemption.select_s > 0.0
    t0 = min(a for _p, a, _b in spans.spans)
    t1 = max(b for _p, _a, b in spans.spans)
    idle = dict(trace_reduce.reduce_events(
        {"devices": {"d": {"ops": [], "modules": []}}}, t0, t1,
        spans.spans)["idle_gaps"])
    assert "failure_handling" in idle and "preempt_select" not in idle
