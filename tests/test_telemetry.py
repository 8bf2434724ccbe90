"""The fleet telemetry plane (ISSUE 10): wire trace propagation,
fleet-wide metrics aggregation, and the device-launch profiler.

Covers: TraceContext on both codecs and across relay hops (hop data
degrades, events never drop), the JSON-era-middlebox (chaos proxy)
path, WAL persistence of trace stamps, the PodTimelines end-to-end
join (hub commit -> relay -> scheduler -> bind -> kubelet ack), the
strict exposition parser + FleetView merge, the DeviceProfiler's
compile attribution, and the hub-client stream-counter tail flush.
"""

import json
import time
import urllib.error
import urllib.request

import pytest

from kubernetes_tpu.fabric import codec as binwire
from kubernetes_tpu.hub import EventHandlers, Hub
from kubernetes_tpu.storage import JournalEvent
from kubernetes_tpu.telemetry.fleet import (
    FleetView,
    hub_metrics_text,
    kubemark_metrics_text,
    merge_expositions,
    parse_exposition,
    relay_metrics_text,
)
from kubernetes_tpu.telemetry.profiler import DeviceProfiler, shape_key
from kubernetes_tpu.telemetry.trace import (
    TraceContext,
    format_ack_trace,
    joined_latency,
    latency_summary,
    new_context,
    parse_ack_trace,
)
from kubernetes_tpu.testing import MakeNode, MakePod
from kubernetes_tpu.utils.wire import from_wire, to_wire

pytestmark = pytest.mark.observability


# ----------------------------------------------- trace context basics


def test_trace_context_wire_round_trip_both_codecs():
    tr = TraceContext(origin="pods-3", ts=123.456789, hops=2)
    # JSON wire
    assert from_wire(to_wire(tr)) == tr
    # bin1 wire (registered kind -> positional struct)
    assert binwire.decode(binwire.encode(tr)) == tr


def test_trace_hop_is_derivation_not_mutation():
    tr = new_context("hub")
    h1 = tr.hop()
    assert (h1.origin, h1.ts, h1.hops) == (tr.origin, tr.ts, 1)
    assert tr.hops == 0


def test_ack_trace_baggage_round_trip_and_malformed():
    tr = TraceContext(origin="hub", ts=11.5, hops=2)
    assert parse_ack_trace(format_ack_trace(tr)) == \
        TraceContext("hub", 11.5, 2)
    assert parse_ack_trace("garbage") is None
    assert parse_ack_trace("") is None


def test_hub_commit_stamps_trace_and_wal_persists_it(tmp_path):
    wal = str(tmp_path / "hub.wal")
    hub = Hub(wal_path=wal)
    got = []
    hub.watch_pods(EventHandlers(on_event=got.append))
    hub.create_pod(MakePod().name("t0").obj())
    assert got and got[0].trace is not None
    assert got[0].trace.origin == "hub"
    assert got[0].trace.hops == 0
    assert got[0].trace.ts > 0
    hub.close()
    # a restarted hub's ring still serves STAMPED events
    hub2 = Hub(wal_path=wal)
    evs = hub2.journal.events_after("pods", 0)
    assert evs and evs[0].trace is not None
    assert evs[0].trace.origin == "hub"
    hub2.close()


def test_sharded_hub_trace_origin_names_the_shard():
    from kubernetes_tpu.fabric.sharded import ShardedHub

    hub = ShardedHub(pod_shards=2)
    got = []
    hub.watch_pods(EventHandlers(on_event=got.append))
    hub.create_pod(MakePod().name("s0").namespace("nsa").obj())
    assert got[0].trace.origin.startswith("pods-")
    hub.close()


def test_joined_latency_requires_all_three_stamps():
    tl = {"wire": {"created": {"t": 1.0, "origin": "hub", "hops": 0},
                   "bound": {"t": 1.5, "origin": "hub", "hops": 0}}}
    assert joined_latency(tl) is None       # no ack yet
    tl["wire"]["acked"] = {"t": 2.0, "origin": "hub", "hops": 0}
    j = joined_latency(tl)
    assert j["create_to_ack_s"] == 1.0
    assert j["create_to_bind_s"] == 0.5
    tl["wire"]["kubelet_recv"] = {"t": 1.7, "origin": "hub", "hops": 2}
    j = joined_latency(tl)
    assert j["bind_to_kubelet_s"] == pytest.approx(0.2)
    assert j["relay_hops"] == 2
    assert joined_latency(None) is None


def test_latency_summary_percentiles():
    s = latency_summary([0.1 * i for i in range(1, 101)])
    assert s["count"] == 100
    assert s["p99_s"] == pytest.approx(10.0)
    assert latency_summary([]) == {"count": 0}


# --------------------------------------- wire + relay hop propagation


def _collect_stream(url, n_events, timeout=10.0):
    """Read a watch stream's JSON lines until n_events non-marker
    events arrived."""
    events = []
    resp = urllib.request.urlopen(url, timeout=timeout)
    deadline = time.monotonic() + timeout
    for raw in resp:
        line = raw.strip()
        if not line or time.monotonic() > deadline:
            break
        d = json.loads(line)
        if d.get("synced") or not d:
            continue
        events.append(d)
        if len(events) >= n_events:
            break
    resp.close()
    return events


def test_trace_survives_hubserver_json_wire():
    from kubernetes_tpu.hubserver import HubServer

    hub = Hub()
    srv = HubServer(hub).start()
    try:
        # connect FIRST: live events carry the commit stamp (a LIST
        # replay synthesizes adds — those are the documented trace=None
        # degradation, asserted below)
        resp = urllib.request.urlopen(
            srv.address + "/watch?kind=pods&replay=1", timeout=10.0)
        hub.create_pod(MakePod().name("w0").obj())
        live = replayed = None
        deadline = time.monotonic() + 10.0
        for raw in resp:
            if time.monotonic() > deadline:
                break
            line = raw.strip()
            if not line:
                continue
            d = json.loads(line)
            if d.get("synced") or not d:
                continue
            live = d
            break
        resp.close()
        assert live is not None and "trace" in live
        tr = from_wire(live["trace"])
        assert isinstance(tr, TraceContext) and tr.origin == "hub"
        # now a replayed LIST: the synthetic add has no stamp but the
        # event itself is delivered (degraded, never dropped)
        evs = _collect_stream(srv.address + "/watch?kind=pods&replay=1",
                              1)
        assert evs and evs[0].get("trace") is None
        replayed = evs[0]
        assert replayed["new"] is not None
    finally:
        srv.stop()
        hub.close()


def test_trace_rides_bin1_and_json_only_server_fallback():
    """Negotiation matrix: on the bin1 wire the stamp arrives as a
    positional struct; against a JSON-only server (fingerprint-era
    skew) the client degrades to JSON and the stamp STILL arrives."""
    from kubernetes_tpu.hubclient import RemoteHub
    from kubernetes_tpu.hubserver import HubServer

    for codecs in ((binwire.CODEC_BINARY, binwire.CODEC_JSON),
                   (binwire.CODEC_JSON,)):
        hub = Hub()
        srv = HubServer(hub, codecs=codecs).start()
        client = RemoteHub(srv.address, timeout=10.0)
        got = []
        try:
            client.list_pods()          # settle codec negotiation
            client.watch_pods(EventHandlers(on_event=got.append))
            hub.create_pod(MakePod().name("nb0").obj())
            deadline = time.monotonic() + 10.0
            while not got and time.monotonic() < deadline:
                time.sleep(0.05)
            assert got, f"no event over codecs={codecs}"
            assert isinstance(got[0].trace, TraceContext)
            assert got[0].trace.origin == "hub"
            expect = binwire.CODEC_BINARY if len(codecs) == 2 \
                else binwire.CODEC_JSON
            assert client.codec == expect
        finally:
            client.close()
            srv.stop()
            hub.close()


def test_trace_survives_chaos_proxy_json_fallback():
    """The JSON-era middlebox: the chaos proxy strips the CODEC offer
    (forcing the JSON wire) but the in-body trace stamp passes through
    — hop data degraded nowhere, zero events dropped."""
    from kubernetes_tpu.chaos import ChaosConfig, ChaosProxy
    from kubernetes_tpu.hubclient import RemoteHub
    from kubernetes_tpu.hubserver import HubServer

    hub = Hub()
    srv = HubServer(hub).start()
    proxy = ChaosProxy(srv.address, config=ChaosConfig(seed=7)).start()
    client = RemoteHub(proxy.address, timeout=10.0)
    got = []
    try:
        client.watch_pods(EventHandlers(on_event=got.append))
        for i in range(5):
            hub.create_pod(MakePod().name(f"cp-{i}").obj())
        deadline = time.monotonic() + 10.0
        while len(got) < 5 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert len(got) == 5, "all events delivered through the proxy"
        assert all(ev.trace is not None and ev.trace.origin == "hub"
                   for ev in got)
        # the proxy pinned the stream to JSON — negotiation degraded,
        # trace did not
        assert client.resilience_stats()["codec"] in ("json",
                                                      "negotiating")
    finally:
        client.close()
        proxy.stop()
        srv.stop()
        hub.close()


def test_relay_increments_hops_and_ring_resume_keeps_trace():
    from kubernetes_tpu.fabric.relay import RelayCore
    from kubernetes_tpu.hubserver import HubServer

    hub = Hub()
    srv = HubServer(hub).start()
    core = None
    try:
        core = RelayCore(srv.address, kinds=("pods",), timeout=10.0)
        sub = core.subscribe(("pods",))
        hub.create_pod(MakePod().name("r0").obj())
        deadline = time.monotonic() + 10.0
        evs = []
        while time.monotonic() < deadline:
            evs += sub.drain()
            if evs:
                break
            time.sleep(0.05)
        assert evs and evs[0]["trace"].hops == 1
        assert evs[0]["trace"].origin == "hub"
        # a resume off the ring re-serves the SAME stamped event
        sub2 = core.subscribe(("pods",), since_rv=0)
        resumed = sub2.drain()
        assert resumed and resumed[0]["trace"].hops == 1
        # a state-mirror LIST replay has no events to stamp: degraded
        sub3 = core.subscribe(("pods",), replay=True)
        listed = sub3.drain()
        assert listed and listed[0]["trace"] is None
    finally:
        if core is not None:
            core.close()
        srv.stop()
        hub.close()


def test_scheduler_joins_end_to_end_timeline_with_kubelet_ack():
    """The whole pillar-(a) loop in-process: hub commit stamps ->
    scheduler timeline join -> kubelet ack baggage -> joined e2e."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.kubemark import HollowNodes
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    hub = Hub()
    hollow = HollowNodes(hub, 2, prefix="tn", cpu="8")
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    try:
        pods = [MakePod().name(f"j{i}").req(cpu="100m").obj()
                for i in range(3)]
        for p in pods:
            hub.create_pod(p)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            sched.run_until_idle()
            joins = [sched.timelines.joined(p.metadata.uid)
                     for p in pods]
            if all(j is not None for j in joins):
                break
            time.sleep(0.05)
        joins = [sched.timelines.joined(p.metadata.uid) for p in pods]
        assert all(j is not None for j in joins), joins
        for j in joins:
            assert j["create_to_ack_s"] >= 0.0
            assert j["create_to_bind_s"] >= 0.0
            # in-process: no relay between kubelet and hub -> 0 hops,
            # but the kubelet-recv leg is still stamped via baggage
            assert "bind_to_kubelet_s" in j
        # /debug/pod serves the join
        tl = sched.timelines.get(name="j0")
        assert tl["joined"] is not None
        assert {"created", "bound", "acked",
                "kubelet_recv"} <= set(tl["wire"])
    finally:
        sched.close()
        hollow.stop()
        hub.close()


def test_trace_export_placement_rows_carry_wire_stamps(tmp_path):
    """The v2 export's placement rows gain the commit-time wire stamps
    (created hub-commit ts + hops) — the offline join anchor."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    path = str(tmp_path / "tr.jsonl")
    hub = Hub()
    hub.create_node(MakeNode().name("xn").capacity(cpu="8").obj())
    cfg = default_config()
    cfg.batch_size = 16
    cfg.trace_export_path = path
    cfg.trace_export_max_bytes = 0
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    try:
        hub.create_pod(MakePod().name("xp").req(cpu="100m").obj())
        sched.run_until_idle()
    finally:
        sched.close()
        hub.close()
    rows = [json.loads(ln) for ln in open(path)]
    placed = [p for r in rows for p in r.get("placements", [])
              if p["pod"].endswith("/xp")]
    assert placed and placed[0]["node"]
    assert placed[0]["wire"]["created"]["t"] > 0
    assert placed[0]["wire"]["created"]["origin"] == "hub"


def test_hubclient_flushes_stream_counters_on_short_stream_eof():
    """Satellite: a stream shorter than the 64-event flush batch must
    still land its tail in wire_codec_* when the connection dies."""
    from kubernetes_tpu.hubclient import RemoteHub
    from kubernetes_tpu.hubserver import HubServer

    hub = Hub()
    srv = HubServer(hub).start()
    client = RemoteHub(srv.address, timeout=10.0)
    got = []
    try:
        client.watch_pods(EventHandlers(on_event=got.append))
        for i in range(5):          # well under the 64-event batch
            hub.create_pod(MakePod().name(f"f{i}").obj())
        deadline = time.monotonic() + 10.0
        while len(got) < 5 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert len(got) == 5
    finally:
        client.close()              # cuts the stream mid-batch
        srv.stop()
    wire = client.resilience_stats()["wire"]
    total_msgs = sum(w["msgs"] for w in wire.values())
    total_recv = sum(w["bytes_recv"] for w in wire.values())
    # 5 events + sync marker rode the stream; the close() above must
    # have flushed them (plus /call probe traffic) deterministically
    assert total_msgs >= 6, wire
    assert total_recv > 0
    hub.close()


# ------------------------------------------------- fleet aggregation


def test_parse_exposition_strict_accepts_and_rejects():
    good = ('# HELP m_total a "quoted" help\n'
            '# TYPE m_total counter\n'
            'm_total{a="x\\ny",b="z\\"q\\\\w"} 3.5\n'
            'plain_gauge 1\n')
    exp = parse_exposition(good)
    assert exp.type["m_total"] == "counter"
    assert exp.samples[0].labels == {"a": "x\ny", "b": 'z"q\\w'}
    assert exp.samples[1].name == "plain_gauge"
    for bad in ('1bad_name 3\n',
                'm{bad-label="x"} 1\n',
                'm{a="unterminated} 1\n',
                'm notafloat\n',
                '# TYPE m wrongtype\n'):
        with pytest.raises(ValueError):
            parse_exposition(bad)


def test_merge_expositions_injects_component_labels():
    a = parse_exposition("# TYPE x_total counter\nx_total 1\n")
    b = parse_exposition("# TYPE x_total counter\n"
                         'x_total{z="1"} 2\n')
    merged = merge_expositions([({"component": "hub"}, a),
                                ({"component": "relay",
                                  "shard": "l1-0"}, b)])
    exp = parse_exposition(merged)       # merged output re-parses
    assert len(exp.samples) == 2
    assert exp.samples[0].labels["component"] == "hub"
    assert exp.samples[1].labels == {"component": "relay",
                                     "shard": "l1-0", "z": "1"}


def test_component_metrics_render_and_parse():
    from kubernetes_tpu.fabric.relay import RelayCore
    from kubernetes_tpu.hubserver import HubServer
    from kubernetes_tpu.kubemark import HollowNodes

    hub = Hub()
    srv = HubServer(hub).start()
    core = None
    hollow = None
    try:
        hub.create_pod(MakePod().name("m0").obj())
        core = RelayCore(srv.address, kinds=("pods",), timeout=10.0)
        hollow = HollowNodes(hub, 2, prefix="mk")
        for text, needle in (
                (hub_metrics_text(hub), "hub_journal_depth"),
                (relay_metrics_text(core), "relay_events_in_total"),
                (kubemark_metrics_text(hollow),
                 "kubemark_hollow_nodes")):
            exp = parse_exposition(text)    # strict parse = the lint
            assert any(s.name.startswith(needle) for s in exp.samples)
    finally:
        if hollow is not None:
            hollow.stop()
        if core is not None:
            core.close()
        srv.stop()
        hub.close()


def test_fleet_view_scrape_merge_and_summary():
    from kubernetes_tpu.fabric.relay import RelayCore, RelayServer
    from kubernetes_tpu.hubserver import HubServer

    hub = Hub()
    srv = HubServer(hub).start()
    relay = RelayServer(RelayCore(srv.address, kinds=("pods",),
                                  timeout=10.0)).start()
    try:
        hub.create_pod(MakePod().name("fv0").obj())
        fleet = FleetView([
            {"component": "hub", "shard": "hub", "url": srv.address},
            {"component": "relay", "shard": "l1-0",
             "url": relay.address},
            {"component": "ghost", "shard": "",
             "url": "http://127.0.0.1:1"},     # dead endpoint
        ], timeout=5.0)
        summary = fleet.summary()
        assert summary["total"] == 3
        assert summary["healthy"] == 2
        assert not summary["ok"]               # the ghost is reported
        ghost = [r for r in summary["endpoints"]
                 if r["component"] == "ghost"][0]
        assert ghost["error"] and not ghost["healthy"]
        merged = parse_exposition(fleet.render_text())
        comps = {s.labels.get("component") for s in merged.samples}
        assert comps == {"hub", "relay"}       # dead one skipped
        shards = {s.labels.get("shard") for s in merged.samples}
        assert {"hub", "l1-0"} <= shards
    finally:
        relay.stop()
        srv.stop()
        hub.close()


def test_scheduler_metrics_exposition_passes_strict_parser():
    """Metrics-lint half 2: the scheduler's full /metrics body (label
    escaping included) round-trips the strict parser."""
    from kubernetes_tpu.metrics import SchedulerMetrics

    m = SchedulerMetrics()
    # poison a label value with everything the spec escapes
    m.schedule_attempts.inc(result='we"ird\\label\nvalue',
                            profile="default")
    m.phase_duration.observe(0.01, phase="commit")
    exp = parse_exposition(m.registry.render_text())
    assert any(s.labels.get("result") == 'we"ird\\label\nvalue'
               for s in exp.samples)


# ------------------------------------------------- device profiler


def test_device_profiler_attributes_compiles():
    sizes = [0]

    def cache():
        return sizes[0]

    from kubernetes_tpu.ops.features import Capacities

    caps = Capacities(nodes=64, pods=128)
    prof = DeviceProfiler(cache_size_fn=cache, now=lambda: 0.0)

    def shape(c, b):
        return shape_key(c, b, False, 0, 0, True, False, False, False)

    # first launch compiles
    sizes[0] = 1
    assert prof.note_launch(shape(caps, 32)) is True
    assert prof.compile_causes == {"first": 1}
    # same shape again, cache unchanged: no compile
    assert prof.note_launch(shape(caps, 32)) is False
    # batch bucket grows -> compile attributed to batch_bucket
    sizes[0] = 2
    assert prof.note_launch(shape(caps, 64)) is True
    assert prof.compile_causes["batch_bucket"] == 1
    # capacity doubled (re-bucket churn) -> rebucket
    import dataclasses

    caps2 = dataclasses.replace(caps, nodes=128)
    sizes[0] = 3
    assert prof.note_launch(shape(caps2, 64)) is True
    assert prof.compile_causes["rebucket"] == 1
    # cache grew on an ALREADY-SEEN shape: unattributed (the alarm)
    sizes[0] = 4
    assert prof.note_launch(shape(caps2, 64)) is True
    snap = prof.snapshot()
    assert snap["unattributed_compiles"] == 1
    assert snap["launches"] == 5 and snap["compiles"] == 4
    assert len(snap["recent_compiles"]) == 4
    prof.observe_walltime(shape(caps2, 64), 0.5)
    snap = prof.snapshot()
    assert any(s["walltime_s"] == 0.5 for s in snap["shapes"])


def test_device_profiler_tells_launches_apart_by_their_active_features():
    """`active` is a static argument of the launch program: two launches
    that differ only in it are two programs and two shapes. A key built
    without it is the older tuple with ("active", ()) appended."""
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.telemetry.profiler import _CAP_FIELDS

    caps = Capacities(nodes=8192, pods=262144)
    plain = shape_key(caps, 4096, False, 0, 8, False, False, False, False)
    older = (("b", 4096), ("topo", False), ("d_cap", 0), ("g_cap", 8),
             ("serial", False), ("dra", False), ("learned", False),
             ("feats", False), ("gang", 0), ("alts", False), ("soft", False),
             *((f, getattr(caps, f)) for f in _CAP_FIELDS
               if hasattr(caps, f)))
    assert plain == older + (("active", ()),)
    aff = shape_key(caps, 4096, False, 0, 8, False, False, False, False,
                    active=("nodeaffinity",))
    assert aff != plain and dict(aff)["active"] == ("nodeaffinity",)
    sizes = [1]
    prof = DeviceProfiler(cache_size_fn=lambda: sizes[0], now=lambda: 0.0)
    prof.note_launch(plain, 4096)
    sizes[0] = 2
    assert prof.note_launch(aff, 4096) is True
    prof.note_launch(aff, 4000)
    assert len(prof.shapes) == 2
    assert (prof.shapes[aff]["launches"], prof.shapes[aff]["pods"]) \
        == (2, 8096)
    assert prof.compile_causes == {"flags": 1}
    assert prof.compile_events[-1]["shape"]["active"] == ("nodeaffinity",)


@pytest.mark.parametrize("d_cap, serial, soft, label", [
    # the zone scan of the spread and affinity cells, the hostname scan of
    # the anti-affinity cell, a soft-only topology launch, an auction
    (8, True, False, "topo=1 d_cap=8 serial=1 soft=0"),
    (8192, True, False, "topo=1 d_cap=8192 serial=1 soft=0"),
    (8192, True, True, "topo=1 d_cap=8192 serial=1 soft=1"),
    (8, False, False, "topo=1 d_cap=8 serial=0 soft=0"),
])
def test_device_profiler_keeps_pods_and_fill_per_shape(d_cap, serial, soft,
                                                       label):
    """Each shape keeps the pods its launches carried beside their number;
    the snapshot gives their share of the rows launched and names what
    tells one topology program from another."""
    from kubernetes_tpu.metrics import SchedulerMetrics
    from kubernetes_tpu.ops.features import Capacities

    caps = Capacities(nodes=8192, pods=131072)
    metrics = SchedulerMetrics()
    prof = DeviceProfiler(metrics=metrics, cache_size_fn=lambda: 0,
                          now=lambda: 0.0)
    shape = shape_key(caps, 1024, True, d_cap, 2, serial, False, False,
                      False, soft=soft)
    other = shape_key(caps, 1024, False, 0, 0, False, False, False, False)
    for pods in (13, 12, 14, 13):
        prof.note_launch(shape, pods)
    prof.note_launch(other, 1024)
    prof.note_launch(other)             # a caller that gives no count
    assert prof.shapes[shape]["launches"] == 4
    assert prof.shapes[shape]["pods"] == 52
    snap = {s["shape"]: s for s in prof.snapshot()["shapes"]}
    mine = f"b=1024 nodes=8192 pods=131072 {label} dra=0"
    plain = "b=1024 nodes=8192 pods=131072 topo=0 d_cap=0 serial=0 soft=0 dra=0"
    assert set(snap) == {mine, plain}
    assert (snap[mine]["launches"], snap[mine]["pods"]) == (4, 52)
    assert snap[mine]["fill"] == round(52 / 4096, 4)
    assert (snap[plain]["pods"], snap[plain]["fill"]) == (1024, 0.5)
    # the gauge carries the same share under the same label
    fills = {dict(k)["shape"]: v
             for k, v in metrics.device_launch_fill.collect().items()}
    assert fills == {mine: 52 / 4096, plain: 0.5}
    text = metrics.registry.render_text()
    assert f'scheduler_device_launch_fill{{shape="{mine}"}}' in text


@pytest.mark.parametrize("b, carried, skipped", [
    # the anti-affinity cell: 10 or 15 pods a 1,024-row launch, one block
    # of 16 steps each; a backlog's full launches; a half-full drain tail
    (1024, (13, 10, 15, 5), 1 - 64 / 4096),
    (1024, (1024, 1024, 1011), 1 - (1024 + 1024 + 1024) / 3072),
    (64, (33, 64), 1 - (48 + 64) / 128),
])
def test_device_profiler_keeps_scan_steps_per_serial_shape(b, carried,
                                                           skipped,
                                                           monkeypatch):
    """A serial launch's scan runs whole blocks up to its last pod; the
    shape keeps the steps, the snapshot the share of the rows launched
    that the scan skipped, the counter both sides of it."""
    from kubernetes_tpu.metrics import SchedulerMetrics
    from kubernetes_tpu.models import pipeline
    from kubernetes_tpu.ops.features import Capacities

    monkeypatch.setattr(pipeline, "_SCAN_UNROLL", 16)   # the chip's
    caps = Capacities(nodes=8192, pods=131072)
    metrics = SchedulerMetrics()
    prof = DeviceProfiler(metrics=metrics, cache_size_fn=lambda: 0,
                          now=lambda: 0.0)
    scan = shape_key(caps, b, True, 8192, 2, True, False, False, False)
    auction = shape_key(caps, b, False, 0, 0, False, False, False, False)
    steps = [pipeline.scan_steps_for(n, b) for n in carried]
    assert all(s % 16 == 0 and n <= s < n + 16
               for n, s in zip(carried, steps))
    for n, s in zip(carried, steps):
        prof.note_launch(scan, n, s)
    prof.note_launch(auction, 7)        # no scan: no steps, none skipped
    rec = prof.shapes[scan]
    assert (rec["launches"], rec["steps"]) == (len(carried), sum(steps))
    snap = {s["shape"]: s for s in prof.snapshot()["shapes"]}
    mine = snap[f"b={b} nodes=8192 pods=131072 topo=1 d_cap=8192 "
                "serial=1 soft=0 dra=0"]
    assert mine["steps"] == sum(steps)
    assert mine["steps_skipped"] == round(skipped, 4)
    other = snap[f"b={b} nodes=8192 pods=131072 topo=0 d_cap=0 "
                 "serial=0 soft=0 dra=0"]
    assert (other["steps"], other["steps_skipped"]) == (0, 0.0)
    run = metrics.device_scan_steps.value(result="run")
    gone = metrics.device_scan_steps.value(result="skipped")
    assert (run, gone) == (sum(steps), len(carried) * b - sum(steps))
    assert gone / (run + gone) == pytest.approx(skipped)
    text = metrics.registry.render_text()
    assert 'scheduler_device_scan_steps_total{result="run"}' in text
    assert 'scheduler_device_scan_steps_total{result="skipped"}' in text
    parse_exposition(text)


@pytest.mark.parametrize("pt, blocks, skipped", [
    # the anti-affinity cell: 3,010 live slots, all in the first of 16
    # blocks of 8,192; a backlog's table filling up; an empty table and a
    # full one
    (131072, (1, 1, 1, 1), 1 - 4 / 64),
    (131072, (12, 13, 16), 1 - 41 / 48),
    (64, (0, 16), 0.5),
])
def test_device_profiler_keeps_table_blocks_per_topology_shape(pt, blocks,
                                                               skipped):
    """A topology launch's phase 1b reads whole blocks of the pod table up
    to its last live slot; the shape keeps the blocks, the snapshot the
    share of the table's blocks it skipped, the counter both sides of it,
    and a shape without topology reads 0.0."""
    from kubernetes_tpu.metrics import SchedulerMetrics
    from kubernetes_tpu.models import pipeline
    from kubernetes_tpu.ops.features import Capacities

    caps = Capacities(nodes=8192, pods=pt)
    per_table = pipeline.table_blocks_for(pt, pt)
    assert per_table == 16
    assert pipeline.table_blocks_for(3010, 131072) == 1
    metrics = SchedulerMetrics()
    prof = DeviceProfiler(metrics=metrics, cache_size_fn=lambda: 0,
                          now=lambda: 0.0)
    topo = shape_key(caps, 1024, True, 8192, 2, True, False, False, False)
    auction = shape_key(caps, 1024, False, 0, 0, False, False, False, False)
    for n in blocks:
        prof.note_launch(topo, 9, 16, n)
    prof.note_launch(auction, 7)       # no topology: no table read
    assert prof.shapes[topo]["table_blocks"] == sum(blocks)
    snap = {s["shape"]: s for s in prof.snapshot()["shapes"]}
    mine = snap[f"b=1024 nodes=8192 pods={pt} topo=1 d_cap=8192 "
                "serial=1 soft=0 dra=0"]
    assert mine["table_blocks"] == sum(blocks)
    assert mine["table_skipped"] == round(skipped, 4)
    other = snap[f"b=1024 nodes=8192 pods={pt} topo=0 d_cap=0 "
                 "serial=0 soft=0 dra=0"]
    assert (other["table_blocks"], other["table_skipped"]) == (0, 0.0)
    run = metrics.device_table_blocks.value(result="run")
    gone = metrics.device_table_blocks.value(result="skipped")
    assert (run, gone) == (sum(blocks), len(blocks) * 16 - sum(blocks))
    text = metrics.registry.render_text()
    assert 'scheduler_device_table_blocks_total{result="run"}' in text
    assert 'scheduler_device_table_blocks_total{result="skipped"}' in text
    parse_exposition(text)


def test_device_profiler_on_live_scheduler_rebucket():
    """Every recompile in a churn-with-growth run attributes to a
    bucket-shape transition (the MixedChurn acceptance criterion in
    miniature: capacity growth forces a re-bucket -> new shape ->
    compile attributed, never 'unattributed')."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    hub = Hub()
    for i in range(4):
        hub.create_node(MakeNode().name(f"pn-{i}")
                        .capacity(cpu="64").obj())
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=16))
    try:
        # more pods than the pod-table bucket: forces _grow (re-bucket)
        for i in range(40):
            hub.create_pod(MakePod().name(f"g{i}")
                           .req(cpu="50m").obj())
        sched.run_until_idle()
        snap = sched.profiler.snapshot()
        assert snap["launches"] >= 2
        assert snap["compiles"] >= 1
        # every pod rode a launch, 16 rows wide; the gauge has each shape
        assert sum(s["pods"] for s in snap["shapes"]) >= 40
        assert all(0.0 < s["fill"] <= 1.0 for s in snap["shapes"])
        assert len(sched.metrics.device_launch_fill.collect()) \
            == len(snap["shapes"])
        assert snap["unattributed_compiles"] == 0, snap
        assert snap["buffer_bytes"].get("cluster", 0) > 0
        # the compile counter mirrored into the registry
        total = sum(
            sched.metrics.device_compiles._values.values())
        assert total == snap["compiles"]
        # the device_compile view phase recorded for compiling cycles
        phases = [tr.phases for tr in sched.flight.ring]
        assert any("device_compile" in p for p in phases)
    finally:
        sched.close()
        hub.close()


def test_live_scheduler_counts_the_scan_steps_its_launches_ran():
    """Five pods with a hostname anti-affinity term ride one 16-row launch
    of the serial scan: the profiler's host arithmetic reads two blocks of
    four steps (the CPU's unroll), the rest of the bucket skipped, and
    /debug/trace's device block and the exported counter say the same."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.models.pipeline import scan_steps_for
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    hub = Hub()
    for i in range(8):
        hub.create_node(MakeNode().name(f"sn-{i}").capacity(cpu="64").obj())
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=16))
    try:
        for i in range(5):
            hub.create_pod(MakePod().name(f"s{i}").label("color", "green")
                           .req(cpu="50m").pod_anti_affinity(
                               "kubernetes.io/hostname",
                               {"color": "green"}).obj())
        sched.run_until_idle()
        nodes = {p.spec.node_name for p in hub.list_pods()}
        assert len(nodes) == 5 and "" not in nodes
        shapes = sched.profiler.snapshot()["shapes"]
        scans = [s for s in shapes if s["steps"]]
        assert scans and all("serial=1" in s["shape"] for s in scans)
        assert sum(s["pods"] for s in scans) == 5
        assert scan_steps_for(5, 16) == 8
        one = [s for s in scans if s["launches"] == 1 and s["pods"] == 5]
        assert one and one[0]["steps"] == 8
        assert one[0]["steps_skipped"] == 0.5
        run = sched.metrics.device_scan_steps.value(result="run")
        gone = sched.metrics.device_scan_steps.value(result="skipped")
        assert run == sum(s["steps"] for s in scans)
        assert run + gone == sum(s["launches"] for s in scans) * 16
    finally:
        sched.close()
        hub.close()


# ------------------------------------------------- authz matrices


def _get(url, token=None):
    req = urllib.request.Request(url)
    if token:
        req.add_header("Authorization", f"Bearer {token}")
    return urllib.request.urlopen(req, timeout=10.0)


def test_relay_debug_authz_matrix():
    """Satellite: RelayServer /debug/fabric — no auth configured 403,
    wrong token 401, good token 200 (mirrors the scheduler's)."""
    from kubernetes_tpu.fabric.relay import RelayCore, RelayServer
    from kubernetes_tpu.hubserver import HubServer
    from kubernetes_tpu.serving import token_auth

    hub = Hub()
    srv = HubServer(hub).start()
    open_relay = RelayServer(RelayCore(srv.address, kinds=("pods",),
                                       timeout=10.0)).start()
    gated = RelayServer(RelayCore(srv.address, kinds=("pods",),
                                  timeout=10.0),
                        debug_auth=token_auth("rtok")).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(open_relay.address + "/debug/fabric")
        assert ei.value.code == 403
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(gated.address + "/debug/fabric")
        assert ei.value.code == 401
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(gated.address + "/debug/fabric", token="wrong")
        assert ei.value.code == 401
        d = json.loads(_get(gated.address + "/debug/fabric",
                            token="rtok").read())
        assert "subscribers" in d
        # /metrics and /healthz are the OPEN fleet surface (scrapers
        # don't bear debug tokens), on both relays
        for relay in (open_relay, gated):
            assert _get(relay.address + "/healthz").status == 200
            body = _get(relay.address + "/metrics").read().decode()
            parse_exposition(body)
    finally:
        gated.stop()
        open_relay.stop()
        srv.stop()
        hub.close()


def test_scheduler_fleet_endpoints_authz_matrix():
    """Satellite: /debug/fleet follows the /debug authz matrix; the
    merged /metrics/fleet exposition is open like /metrics."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hubserver import HubServer
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.serving import ServingEndpoints, token_auth

    hub = Hub()
    hub_srv = HubServer(hub).start()
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    sched.fleet = FleetView([{"component": "hub", "shard": "hub",
                              "url": hub_srv.address}])
    try:
        # no debug_auth: 403 for /debug/fleet
        srv = ServingEndpoints(sched, port=0)
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base + "/debug/fleet")
            assert ei.value.code == 403
            # the merged exposition is open (scrape surface)
            merged = _get(base + "/metrics/fleet").read().decode()
            exp = parse_exposition(merged)
            assert all(s.labels.get("component") == "hub"
                       for s in exp.samples)
        finally:
            srv.stop()
        srv = ServingEndpoints(sched, port=0,
                               debug_auth=token_auth("ftok"))
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base + "/debug/fleet")
            assert ei.value.code == 401
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base + "/debug/fleet", token="wrong")
            assert ei.value.code == 401
            d = json.loads(_get(base + "/debug/fleet",
                                token="ftok").read())
            assert d["total"] == 1 and d["healthy"] == 1
            # /debug/trace now carries the device profiler column
            tr = json.loads(_get(base + "/debug/trace",
                                 token="ftok").read())
            assert "device" in tr
        finally:
            srv.stop()
    finally:
        sched.close()
        hub_srv.stop()
        hub.close()


def test_hubserver_metrics_and_healthz():
    from kubernetes_tpu.hubserver import HubServer

    hub = Hub()
    srv = HubServer(hub).start()
    try:
        assert _get(srv.address + "/healthz").status == 200
        hub.create_pod(MakePod().name("hm0").obj())
        exp = parse_exposition(
            _get(srv.address + "/metrics").read().decode())
        assert any(s.name == "hub_rv" and s.value >= 1
                   for s in exp.samples)
    finally:
        srv.stop()
        hub.close()


def test_journal_event_trace_default_none_back_compat():
    ev = JournalEvent(rv=1, kind="pods", type="add", new=None)
    assert ev.trace is None


def test_live_scheduler_counts_the_table_blocks_its_launches_read():
    """Three waves of three green pods with a hostname anti-affinity term
    over a 16-slot table (blocks of one slot): a bound pod takes its slot
    at the next launch's sync, so the launches read 0, 3 and 6 blocks, and
    /debug/trace's device block and the exported counter say so."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    hub = Hub()
    for i in range(10):
        hub.create_node(MakeNode().name(f"tn-{i}").capacity(cpu="64").obj())
    cfg = default_config()
    cfg.batch_size = 4
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=16))
    try:
        for wave in range(3):
            for i in range(3):
                hub.create_pod(MakePod().name(f"t{wave}-{i}")
                               .label("color", "green").req(cpu="50m")
                               .pod_anti_affinity("kubernetes.io/hostname",
                                                  {"color": "green"}).obj())
            sched.run_until_idle()
        nodes = {p.spec.node_name for p in hub.list_pods()}
        assert len(nodes) == 9 and "" not in nodes
        assert sched.mirror.slots_hi == 6
        topo = [s for s in sched.profiler.snapshot()["shapes"]
                if "topo=1" in s["shape"]]
        launches = sum(s["launches"] for s in topo)
        read = sum(s["table_blocks"] for s in topo)
        assert (launches, read) == (3, 0 + 3 + 6)
        assert [s["table_skipped"] for s in topo] == [round(1 - 9 / 48, 4)]
        run = sched.metrics.device_table_blocks.value(result="run")
        gone = sched.metrics.device_table_blocks.value(result="skipped")
        assert (run, run + gone) == (read, launches * 16)
    finally:
        sched.close()
        hub.close()


def test_preemption_sweep_stats_add_up_and_reach_the_registry():
    """Evaluator.sweep_stats(): swept = nominated + no_candidates +
    ineligible + extender_errors; nominated = the preemptors bound; one
    full victim-state build, then updates; nothing stranded. Exported at
    maintenance, by delta, as scheduler_preemption_sweep_total{result},
    scheduler_preemption_victim_state_total{path} and
    scheduler_preemption_stranded_activations_total, and on /debug/trace
    as "preemption"."""
    from kubernetes_tpu.framework.preemption import SWEEP_STATS
    from kubernetes_tpu.serving import ServingEndpoints, token_auth
    from tests.test_tracing import _sched, preemption_cluster, preemptor

    hub = Hub()
    sched = _sched(hub)
    try:
        preemption_cluster(hub, nodes=3)
        for i in range(2):
            hub.create_pod(preemptor(f"high-{i}"))
        hub.create_pod(preemptor("never", policy="Never"))
        # priority 0 beside fillers of 0: nothing is lower, no candidates
        hub.create_pod(preemptor("peer", priority=0))
        for _ in range(3):
            sched.run_until_idle()
            sched.run_maintenance()
        sweep = sched.preemption.sweep_stats()
        assert tuple(sweep) == SWEEP_STATS
        bound = {p.metadata.name for p in hub.list_pods() if p.spec.node_name
                 and not p.metadata.name.startswith("fill-")}
        assert bound == {"high-0", "high-1"}
        assert sweep["nominated"] == 2
        assert sweep["ineligible"] >= 1 and sweep["no_candidates"] >= 1
        assert sweep["preemptors_swept"] == sum(
            sweep[k] for k in ("nominated", "no_candidates", "ineligible",
                               "extender_errors"))
        assert sweep["victim_state_rebuilds"] >= 1
        assert sweep["stranded_activations"] == 0
        # every eligible preemptor that found victims reached the kmin memo
        assert sweep["kmin_computed"] >= 1
        assert sweep["kmin_computed"] + sweep["kmin_reused"] \
            >= sweep["nominated"]
        m = sched.metrics
        for result in ("nominated", "no_candidates", "ineligible",
                       "extender_errors"):
            assert m.preemption_sweep.value(result=result) == sweep[result]
        assert m.preemption_victim_state.value(path="rebuild") \
            == sweep["victim_state_rebuilds"]
        assert m.preemption_victim_state.value(path="update") \
            == sweep["victim_state_updates"]
        for result in ("computed", "reused"):
            assert m.preemption_kmin.value(result=result) \
                == sweep[f"kmin_{result}"]
        text = m.registry.render_text()
        assert ('scheduler_preemption_sweep_total{result="nominated"} 2'
                in text)
        assert "scheduler_preemption_victim_state_total" in text
        assert "scheduler_preemption_stranded_activations_total" in text
        srv = ServingEndpoints(sched, port=0, debug_auth=token_auth("t"))
        srv.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/debug/trace?n=1")
            req.add_header("Authorization", "Bearer t")
            tr = json.loads(urllib.request.urlopen(req, timeout=5).read())
        finally:
            srv.stop()
        assert tr["preemption"] == sched.preemption.sweep_stats()
    finally:
        sched.close()
        hub.close()


def test_preemption_sweep_computes_one_kmin_a_distinct_request():
    """A burst of ten equal preemptors computes one host kmin and reuses it
    nine times; a burst of all-different requests reuses none. Both
    results reach the registry at maintenance as
    scheduler_preemption_kmin_total{result="computed|reused"}."""
    import types

    from tests.test_tracing import _sched, mkpod, preemption_cluster

    hub = Hub()
    sched = _sched(hub)
    try:
        preemption_cluster(hub, nodes=16)
        sched.run_until_idle()
        sched.cache.update_snapshot(sched.snapshot)
        sched.mirror.sync(sched.snapshot)
        ev = sched.preemption

        def burst(cpus):
            jobs = []
            for cpu in cpus:
                pod = mkpod(f"high-{len(hub.list_pods())}", cpu=cpu)
                pod.spec.priority = 10
                hub.create_pod(pod)
                jobs.append(types.SimpleNamespace(pod=pod,
                                                  uid=pod.metadata.uid))
            before = ev.sweep_stats()
            out = ev.batch_preempt(jobs, sched.snapshot)
            after = ev.sweep_stats()
            return out, {k: after[k] - before[k]
                         for k in ("kmin_computed", "kmin_reused")}

        out, kmin = burst(["3000m"] * 10)
        assert kmin == {"kmin_computed": 1, "kmin_reused": 9}
        assert all(node for node, _s in out.values())
        out, kmin = burst(["2600m", "2800m", "3000m", "3200m"])
        assert kmin == {"kmin_computed": 4, "kmin_reused": 0}
        sched.run_maintenance()
        m = sched.metrics
        assert m.preemption_kmin.value(result="computed") == 5
        assert m.preemption_kmin.value(result="reused") == 9
        text = m.registry.render_text()
        assert 'scheduler_preemption_kmin_total{result="computed"} 5' in text
        assert 'scheduler_preemption_kmin_total{result="reused"} 9' in text
    finally:
        sched.close()


def test_cache_snapshot_refreshes_reach_the_registry_and_the_trace():
    """Cache.snapshot_stats() counts update_snapshot's refreshes by path:
    the first a full one (the nodes are new), a refresh after binds over
    the same nodes a delta one, a refresh with nothing new a noop (chained
    launches skip the refresh, so the test refreshes by hand). Exported
    at maintenance, by delta, as
    scheduler_cache_snapshot_refresh_total{path="noop|delta|full"}, and
    on /debug/trace as "snapshot"."""
    from kubernetes_tpu.backend.cache import SNAPSHOT_STATS
    from kubernetes_tpu.serving import ServingEndpoints, token_auth
    from tests.test_tracing import _sched, mknode, mkpod

    hub = Hub()
    sched = _sched(hub)
    try:
        for i in range(4):
            hub.create_node(mknode(i))
        for wave in range(3):
            for j in range(4):
                hub.create_pod(mkpod(f"p-{wave}-{j}"))
            sched.run_until_idle()
            sched.cache.update_snapshot(sched.snapshot)
            sched.cache.update_snapshot(sched.snapshot)
        sched.run_maintenance()
        stats = sched.cache.snapshot_stats()
        assert tuple(stats) == SNAPSHOT_STATS
        assert stats["full"] == 1
        assert stats["delta"] >= 3 and stats["noop"] >= 3
        assert stats["cloned"] >= 4 + 3
        m = sched.metrics
        for path in ("noop", "delta", "full"):
            assert m.cache_snapshot_refresh.value(path=path) == stats[path]
        text = m.registry.render_text()
        assert ('scheduler_cache_snapshot_refresh_total{path="delta"} '
                f'{stats["delta"]}') in text
        srv = ServingEndpoints(sched, port=0, debug_auth=token_auth("t"))
        srv.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/debug/trace?n=1")
            req.add_header("Authorization", "Bearer t")
            tr = json.loads(urllib.request.urlopen(req, timeout=5).read())
        finally:
            srv.stop()
        assert tr["snapshot"] == sched.cache.snapshot_stats()
    finally:
        sched.close()
        hub.close()
