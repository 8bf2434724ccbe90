"""L0 storage: event journal, compaction, watch-resume, WAL, /debug authz.

The etcd-analog layer (kubernetes_tpu/storage): ring wraparound advances
the compaction watermark correctly, the ``since_rv == compacted_rv``
boundary resumes, RvTooOld fires below it; hub watches resume in-process
and over the HTTP wire (where 410 drives the client's relist fallback);
a WAL-backed hub replays its state across restarts; broken CEL selectors
surface as hub Events + dra_cel_errors_total instead of silently parking
pods; /debug endpoints stay behind the pluggable auth callback."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from kubernetes_tpu.api.objects import (
    DeviceClass,
    DeviceSelector,
    ObjectMeta,
)
from kubernetes_tpu.hub import EventHandlers, Hub, RvTooOld
from kubernetes_tpu.hubclient import RemoteHub
from kubernetes_tpu.hubserver import HubServer
from kubernetes_tpu.serving import ServingEndpoints, token_auth
from kubernetes_tpu.storage import Journal, JournalEvent
from kubernetes_tpu.testing import MakeNode, MakePod


def _wait(cond, timeout=10.0, interval=0.02):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# ---------------------------------------------------------------- journal


def test_ring_wraparound_advances_watermark():
    j = Journal(capacity=4)
    for rv in range(1, 11):
        j.append(JournalEvent(rv=rv, kind="pods", type="add"))
    # ring holds rvs 7..10; the newest DROPPED event (rv 6) is the
    # watermark
    assert j.compacted_rv("pods") == 6
    assert [e.rv for e in j.events_after("pods", 7)] == [8, 9, 10]
    st = j.stats()["pods"]
    assert st["depth"] == 4 and st["last_rv"] == 10


def test_since_rv_equals_watermark_boundary_resumes():
    j = Journal(capacity=4)
    for rv in range(1, 11):
        j.append(JournalEvent(rv=rv, kind="pods", type="add"))
    # inclusive boundary: a client that saw exactly rv 6 (the last
    # compacted event) still has a complete history ahead of it
    assert [e.rv for e in j.events_after("pods", 6)] == [7, 8, 9, 10]
    with pytest.raises(RvTooOld) as ei:
        j.events_after("pods", 5)
    assert ei.value.compacted_rv == 6
    # a never-journaled kind has watermark 0: any resume point is legal
    assert j.events_after("nodes", 0) == []


def test_journal_rv_gaps_across_kinds_are_complete_per_kind():
    j = Journal(capacity=8)
    for rv in range(1, 9):
        kind = "pods" if rv % 2 else "nodes"
        j.append(JournalEvent(rv=rv, kind=kind, type="add"))
    assert [e.rv for e in j.events_after("pods", 1)] == [3, 5, 7]
    assert [e.rv for e in j.events_after("nodes", 0)] == [2, 4, 6, 8]


# ---------------------------------------------------------------- hub


def test_hub_watch_resume_in_process():
    hub = Hub()
    p1 = MakePod().name("p1").obj()
    p2 = MakePod().name("p2").obj()
    hub.create_pod(p1)
    rv = hub.current_rv
    hub.create_pod(p2)
    hub.delete_pod(p1.metadata.uid)
    got = []
    cur = hub.watch_pods(
        EventHandlers(on_add=lambda o: got.append(("add", o.metadata.name)),
                      on_delete=lambda o: got.append(
                          ("del", o.metadata.name))),
        since_rv=rv)
    # only the journal suffix replays — no synthetic adds of the world
    assert got == [("add", "p2"), ("del", "p1")]
    assert cur == hub.current_rv
    # the delete consumed a revision of its own (etcd stamps deletions)
    assert cur == rv + 2


def test_hub_watch_resume_raises_rv_too_old_before_registering():
    hub = Hub(journal_capacity=4)
    for i in range(10):
        hub.create_pod(MakePod().name(f"p{i}").obj())
    h = EventHandlers(on_add=lambda o: None)
    with pytest.raises(RvTooOld):
        hub.watch_pods(h, since_rv=1)
    # the failed watch must not have left a registered handler behind
    assert h not in hub._pods.handlers
    # boundary: resuming exactly AT the watermark works
    wm = hub.journal.compacted_rv("pods")
    got = []
    hub.watch_pods(EventHandlers(on_add=lambda o: got.append(1)),
                   since_rv=wm)
    assert len(got) == 4


def test_record_event_dedups_and_bumps_count():
    hub = Hub()
    hub.record_event("DeviceClass", "gpu", "CELSelectorError", "boom 1")
    hub.record_event("DeviceClass", "gpu", "CELSelectorError", "boom 2")
    hub.record_event("DeviceClass", "other", "CELSelectorError", "x")
    evs = hub.list_events(ref_kind="DeviceClass", ref_key="gpu")
    assert len(evs) == 1
    assert evs[0].count == 2 and evs[0].message == "boom 2"
    assert len(hub.list_events(ref_kind="DeviceClass")) == 2


# ---------------------------------------------------------------- WAL


def test_wal_replay_rebuilds_hub_state(tmp_path):
    wal = str(tmp_path / "hub.wal")
    h1 = Hub(wal_path=wal)
    n = MakeNode().name("n1").capacity(cpu="8").obj()
    h1.create_node(n)
    pods = [MakePod().name(f"p{i}").obj() for i in range(3)]
    for p in pods:
        h1.create_pod(p)
    h1.bind(pods[0], "n1")
    h1.delete_pod(pods[2].metadata.uid)
    rv_end = h1.current_rv
    watch_rv = h1.current_rv
    h1.close()

    h2 = Hub(wal_path=wal)
    # revision space continues, stores + secondary indexes rebuilt
    assert h2.current_rv == rv_end
    assert h2.get_node("n1").metadata.uid == n.metadata.uid
    assert h2.get_pod(pods[0].metadata.uid).spec.node_name == "n1"
    assert h2.get_pod(pods[2].metadata.uid) is None
    assert len(h2.list_pods()) == 2
    # the journal rings replayed too: a client at a pre-restart rv
    # resumes across the hub restart
    h2.create_pod(MakePod().name("post").obj())
    assert h2.current_rv == rv_end + 1
    got = []
    h2.watch_pods(EventHandlers(on_add=lambda o: got.append(
        o.metadata.name)), since_rv=watch_rv)
    assert got == ["post"]
    # and new mutations keep appending to the same WAL
    h2.close()
    h3 = Hub(wal_path=wal)
    assert h3.get_pod(pods[0].metadata.uid).spec.node_name == "n1"
    assert any(p.metadata.name == "post" for p in h3.list_pods())
    h3.close()


def test_wal_tolerates_and_repairs_torn_final_line(tmp_path):
    wal = str(tmp_path / "hub.wal")
    h1 = Hub(wal_path=wal)
    h1.create_pod(MakePod().name("whole").obj())
    h1.close()
    with open(wal, "a") as f:
        f.write('{"rv": 99, "kind": "pods", "ty')   # torn mid-append
    h2 = Hub(wal_path=wal)
    assert len(h2.list_pods()) == 1
    assert h2.current_rv == 1
    # the torn tail was TRUNCATED on boot: appending now must start a
    # clean line, not merge into the partial record (which would become
    # interior corruption and brick every later boot)
    h2.create_pod(MakePod().name("after-tear").obj())
    h2.close()
    h3 = Hub(wal_path=wal)
    assert sorted(p.metadata.name for p in h3.list_pods()) == \
        ["after-tear", "whole"]
    h3.close()
    # a record cut exactly between the json and its newline is torn too
    with open(wal, "rb+") as f:
        f.seek(-1, 2)
        assert f.read(1) == b"\n"
        f.seek(-1, 2)
        f.truncate()                         # strip the final newline
    h4 = Hub(wal_path=wal)
    assert [p.metadata.name for p in h4.list_pods()] == ["whole"], \
        "newline-less tail never committed"
    h4.close()


def test_watch_resume_from_future_rv_is_rv_too_old():
    """A since_rv beyond the hub's newest revision means the client
    watched a DIFFERENT revision space (a hub reborn without its WAL):
    'no events' would pin phantom state in the client forever, so the
    hub answers RvTooOld and the wire answers 410 → relist, whose diff
    deletes the phantoms."""
    hub = Hub()
    hub.create_pod(MakePod().name("p").obj())
    with pytest.raises(RvTooOld):
        hub.watch_pods(EventHandlers(on_add=lambda o: None), since_rv=99)
    # end-to-end: reflector synced against hub A resumes against a
    # fresh empty hub B on the same port -> relist-as-deletes
    hub_a = Hub()
    server = HubServer(hub_a).start()
    host, port = server._httpd.server_address[:2]
    for i in range(5):
        hub_a.create_node(MakeNode().name(f"n{i}").obj())
    client = RemoteHub(server.address, retry_base=0.02, retry_cap=0.2)
    adds, dels = [], []
    try:
        client.watch_nodes(EventHandlers(
            on_add=lambda o: adds.append(o.metadata.name),
            on_delete=lambda o: dels.append(o.metadata.name)))
        assert len(adds) == 5
        server.stop()
        server = HubServer(Hub(), host=host, port=port).start()
        assert _wait(lambda: len(dels) == 5, 15), \
            f"phantom objects not deleted: dels={dels}"
        stats = client.resilience_stats()
        assert stats["watch_relists"] >= 1
        assert stats["watch_resumes"] == 0
    finally:
        client.close()
        server.stop()


def test_wal_boot_compaction_bounds_the_file(tmp_path):
    """A WAL whose history dwarfs the live objects is snapshot-rewritten
    on boot: the file shrinks to (compact record + live objects), state
    survives further restarts, revisions continue above the floor, and a
    resume from below the floor relists via RvTooOld on the NEXT boot."""
    wal = str(tmp_path / "hub.wal")
    h1 = Hub(wal_path=wal)
    keep = MakePod().name("keeper").obj()
    h1.create_pod(keep)
    for i in range(200):                  # churn: 400 events, 1 survivor
        p = MakePod().name(f"churn{i}").obj()
        h1.create_pod(p)
        h1.delete_pod(p.metadata.uid)
    rv_end = h1.current_rv
    pre_resume_rv = rv_end - 10
    h1.close()
    size_before = len(open(wal).read().splitlines())
    assert size_before > 400

    h2 = Hub(wal_path=wal)                # boot compaction triggers here
    assert len(open(wal).read().splitlines()) < 10
    assert h2.current_rv == rv_end
    assert [p.metadata.name for p in h2.list_pods()] == ["keeper"]
    # this boot's rings still hold the real history: resume works
    got = []
    h2.watch_pods(EventHandlers(on_add=lambda o: got.append(1),
                                on_delete=lambda o: got.append(-1)),
                  since_rv=pre_resume_rv)
    assert got, "in-memory rings still serve pre-compaction resumes"
    h2.close()

    h3 = Hub(wal_path=wal)                # replays the compacted snapshot
    assert h3.current_rv == rv_end
    assert [p.metadata.name for p in h3.list_pods()] == ["keeper"]
    with pytest.raises(RvTooOld):
        h3.watch_pods(EventHandlers(on_add=lambda o: None),
                      since_rv=pre_resume_rv)
    # at/above the floor is fine
    h3.watch_pods(EventHandlers(on_add=lambda o: None), since_rv=rv_end)
    h3.close()


def test_wal_interior_corruption_raises(tmp_path):
    wal = str(tmp_path / "hub.wal")
    h1 = Hub(wal_path=wal)
    h1.create_pod(MakePod().name("a").obj())
    h1.create_pod(MakePod().name("b").obj())
    h1.close()
    lines = open(wal).read().splitlines()
    lines[0] = lines[0][: len(lines[0]) // 2]      # corrupt the interior
    with open(wal, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(ValueError):
        Hub(wal_path=wal)


# ------------------------------------------------------------- the wire


@pytest.fixture()
def served():
    hub = Hub()
    server = HubServer(hub).start()
    client = RemoteHub(server.address, retry_base=0.02, retry_cap=0.2)
    yield hub, server, client
    client.close()
    server.stop()


def test_watch_endpoint_since_rv_and_410(served):
    hub, server, _client = served
    for i in range(3):
        hub.create_pod(MakePod().name(f"p{i}").obj())
    # a raw since_rv stream: only the suffix, then a sync marker with rv
    resp = urllib.request.urlopen(
        f"{server.address}/watch?kind=pods&since_rv=1", timeout=5)
    lines = []
    for raw in resp:
        ev = json.loads(raw)
        lines.append(ev)
        if ev.get("synced"):
            break
    resp.close()
    assert [e["rv"] for e in lines[:-1]] == [2, 3]
    assert lines[-1] == {"synced": True, "rv": 3}
    # compacted gap -> 410 with the RvTooOld error body
    small = Hub(journal_capacity=2)
    srv2 = HubServer(small).start()
    try:
        for i in range(6):
            small.create_pod(MakePod().name(f"q{i}").obj())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{srv2.address}/watch?kind=pods&since_rv=1", timeout=5)
        assert ei.value.code == 410
        assert json.loads(ei.value.read())["error"] == "RvTooOld"
    finally:
        srv2.stop()


def test_reflector_resumes_after_server_restart_without_relist():
    """The PR-1 scenario that used to force a relist-as-deletes diff:
    the hub server dies mid-watch and comes back (same hub, same port).
    With the journal, the reflector reconnects with since_rv and replays
    only the gap — watch_resumes counts it, watch_relists stays 0."""
    hub = Hub()
    server = HubServer(hub).start()
    host, port = server._httpd.server_address[:2]
    for i in range(5):
        hub.create_node(MakeNode().name(f"n{i}").obj())
    client = RemoteHub(server.address, retry_base=0.02, retry_cap=0.2)
    adds, dels = [], []
    try:
        client.watch_nodes(EventHandlers(
            on_add=lambda o: adds.append(o.metadata.name),
            on_delete=lambda o: dels.append(o.metadata.name)))
        assert len(adds) == 5
        server.stop()                      # the cut
        # the gap: one add + one delete while no stream exists
        hub.create_node(MakeNode().name("gap-add").obj())
        hub.delete_node(hub.get_node("n0").metadata.uid)
        server = HubServer(hub, host=host, port=port).start()
        assert _wait(lambda: "gap-add" in adds and "n0" in dels)
        stats = client.resilience_stats()
        assert stats["watch_resumes"] >= 1
        assert stats["watch_relists"] == 0
        assert len(adds) == 6              # no duplicate adds either
    finally:
        client.close()
        server.stop()


def test_reflector_falls_back_to_relist_on_rv_too_old():
    """When the outage outlives the ring, the 410 answer drives the old
    relist path — including the relist-as-deletes diff for objects that
    vanished during the gap."""
    hub = Hub(journal_capacity=4)
    server = HubServer(hub).start()
    host, port = server._httpd.server_address[:2]
    nodes = [MakeNode().name(f"n{i}").obj() for i in range(6)]
    for n in nodes:
        hub.create_node(n)
    client = RemoteHub(server.address, retry_base=0.02, retry_cap=0.2)
    adds, dels = [], []
    try:
        client.watch_nodes(EventHandlers(
            on_add=lambda o: adds.append(o.metadata.name),
            on_delete=lambda o: dels.append(o.metadata.name)))
        assert len(adds) == 6
        server.stop()
        # churn far beyond the 4-slot ring: compaction passes the
        # client's resume point
        hub.delete_node(nodes[0].metadata.uid)
        for i in range(10):
            hub.create_node(MakeNode().name(f"extra{i}").obj())
        server = HubServer(hub, host=host, port=port).start()
        assert _wait(lambda: "n0" in dels
                     and sum(1 for a in adds
                             if a.startswith("extra")) == 10)
        stats = client.resilience_stats()
        assert stats["watch_relists"] >= 1
    finally:
        client.close()
        server.stop()


def test_cut_mid_list_replay_never_arms_resume():
    """A stream cut in the middle of the initial LIST replay must NOT
    arm watch-resume: LIST replay is insertion-ordered, so the highest
    rv seen mid-replay can lie beyond objects never delivered — resuming
    from it would skip them silently forever. The reconnect must run a
    full relist instead (watch_resumes == 0)."""
    import socket as socketlib
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from kubernetes_tpu.utils.wire import to_wire

    hub = Hub()
    nodes = [MakeNode().name(f"n{i}").obj() for i in range(5)]
    for n in nodes:
        hub.create_node(n)
    # n0 updated LAST: insertion order replays it FIRST with the
    # highest rv — the poisoned resume point
    upd = hub.get_node("n0").clone()
    upd.metadata.labels["x"] = "1"
    hub.update_node(upd)
    top_rv = hub.current_rv

    class TruncatingHandler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *args):
            pass

        def do_GET(self):  # noqa: N802
            # serve TWO replay events (n0 at top_rv included), then die
            # before the rest of the LIST or any sync marker
            self.send_response(200)
            self.send_header("Content-Type", "application/jsonlines")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            for obj in [hub.get_node("n0"), hub.get_node("n1")]:
                line = (json.dumps(
                    {"type": "add", "rv": obj.metadata.resource_version,
                     "old": None, "new": to_wire(obj)}).encode() + b"\n")
                self.wfile.write(f"{len(line):x}\r\n".encode() + line
                                 + b"\r\n")
                self.wfile.flush()
            try:
                self.connection.shutdown(socketlib.SHUT_RDWR)
            except OSError:
                pass
            self.close_connection = True

    fake = ThreadingHTTPServer(("127.0.0.1", 0), TruncatingHandler)
    fake.daemon_threads = True
    port = fake.server_address[1]
    t = threading.Thread(target=fake.serve_forever, daemon=True)
    t.start()
    client = RemoteHub(f"http://127.0.0.1:{port}", retry_base=0.02,
                       retry_cap=0.2)
    adds = []
    server = None
    try:
        # initial connect hits the truncating server; swap in the real
        # one on the same port before the reflector's reconnect dials
        watcher = threading.Thread(
            target=lambda: client.watch_nodes(EventHandlers(
                on_add=lambda o: adds.append(o.metadata.name))),
            daemon=True)
        watcher.start()
        assert _wait(lambda: len(adds) >= 2, 10), "truncated replay seen"
        fake.shutdown()
        fake.server_close()
        server = HubServer(hub, port=port).start()
        assert _wait(lambda: len(set(adds)) == 5, 15), \
            f"objects skipped after mid-LIST cut: {sorted(set(adds))}"
        stats = client.resilience_stats()
        assert stats["watch_resumes"] == 0, \
            f"resume armed from a partial LIST: {stats}"
        assert stats["watch_relists"] >= 1
    finally:
        client.close()
        fake.shutdown()
        fake.server_close()
        if server is not None:
            server.stop()


# ----------------------------------------------- CEL errors surfaced


def test_broken_cel_selector_records_event_and_stats():
    from kubernetes_tpu.api.objects import Device
    from kubernetes_tpu.plugins.dra import DynamicResources

    hub = Hub()
    plugin = DynamicResources(hub)
    dc = DeviceClass(metadata=ObjectMeta(name="tpu"),
                     selectors=[DeviceSelector(
                         cel_expression="device.nope.missing(")])
    hub.create_device_class(dc)
    dev = Device(name="d0")
    entry = ("drv", "pool", dev)
    assert not plugin._device_matches(entry, "tpu", dc, [], "ns/claim")
    # once per (object, expression), not per device
    assert not plugin._device_matches(
        ("drv", "pool", Device(name="d1")), "tpu", dc, [], "ns/claim")
    assert plugin.cel_error_stats() == {"DeviceClass/tpu": 1}
    evs = hub.list_events(ref_kind="DeviceClass", ref_key="tpu")
    assert len(evs) == 1 and evs[0].reason == "CELSelectorError"
    # claim-side selectors attribute to the claim
    sel = [DeviceSelector(cel_expression="device.driver ==")]
    assert not plugin._device_matches(entry, "", None, sel, "ns/claim")
    assert plugin.cel_error_stats()["ResourceClaim/ns/claim"] == 1
    assert hub.list_events(ref_kind="ResourceClaim", ref_key="ns/claim")


# ------------------------------------------------------- /debug authz


def _tiny_sched(hub):
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    cfg = default_config()
    cfg.batch_size = 4
    return Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=16))


def _get(url, token=None):
    req = urllib.request.Request(url)
    if token:
        req.add_header("Authorization", f"Bearer {token}")
    return urllib.request.urlopen(req, timeout=5)


def test_debug_endpoints_require_auth_callback():
    hub = Hub()
    sched = _tiny_sched(hub)
    try:
        # no callback configured: the surface answers 403, never data
        srv = ServingEndpoints(sched, port=0)
        srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://127.0.0.1:{srv.port}/debug/cache")
            assert ei.value.code == 403
        finally:
            srv.stop()
        # with token_auth: wrong/missing token 401, right token 200
        srv = ServingEndpoints(sched, port=0,
                               debug_auth=token_auth("s3cret"))
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{base}/debug/cache")
            assert ei.value.code == 401
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{base}/debug/cache", token="wrong")
            assert ei.value.code == 401
            body = json.loads(_get(f"{base}/debug/cache",
                                   token="s3cret").read())
            assert "nodes" in body
            q = json.loads(_get(f"{base}/debug/queue",
                                token="s3cret").read())
            assert "pending" in q
            js = json.loads(_get(f"{base}/debug/journal",
                                 token="s3cret").read())
            assert "kinds" in js
            # non-debug endpoints stay open
            assert _get(f"{base}/healthz").read() == b"ok"
        finally:
            srv.stop()
    finally:
        sched.close()


def test_journal_metrics_exported_on_scheduler():
    hub = Hub()
    sched = _tiny_sched(hub)
    try:
        hub.create_node(MakeNode().name("n0").capacity(cpu="8").obj())
        hub.create_pod(MakePod().name("p").req(cpu="1").obj())
        sched.run_until_idle()
        sched.run_maintenance()
        text = sched.metrics.registry.render_text()
        assert "hub_watch_resumes_total" in text
        assert "hub_watch_relists_total" in text
        assert 'hub_journal_depth{kind="pods"}' in text
        assert "dra_cel_errors_total" in text
    finally:
        sched.close()


# suite-tier discipline (tests/test_markers.py): area marker
pytestmark = pytest.mark.core


# ----------------------- bin1 WAL codec (ISSUE 11) -----------------------


def test_bin1_wal_roundtrip_and_size(tmp_path):
    """The bin1 WAL replays identically to the JSON-lines WAL and is
    several times smaller on disk (positional structs: field names
    never hit the file)."""
    paths = {}
    for codec in ("json", "bin1"):
        wal = str(tmp_path / f"h-{codec}.wal")
        hub = Hub(wal_path=wal, wal_codec=codec)
        for i in range(20):
            hub.create_pod(MakePod().name(f"b{i}")
                           .namespace(f"ns-{i % 3}").obj())
        hub.bind(hub.list_pods()[0], "n-x")
        rv = hub.current_rv
        hub.close()
        paths[codec] = (wal, rv)
        hub2 = Hub(wal_path=wal, wal_codec=codec)
        assert hub2.current_rv == rv
        assert len(hub2.list_pods()) == 20
        assert sum(1 for p in hub2.list_pods()
                   if p.spec.node_name) == 1
        # rings replayed too: resumes across the restart serve
        assert hub2.journal.events_after("pods", 0)
        hub2.close()
    import os as _os

    jb = _os.path.getsize(paths["json"][0])
    bb = _os.path.getsize(paths["bin1"][0])
    assert jb / bb >= 3.0, f"bin1 WAL must be ≥3x smaller ({jb}/{bb})"


def test_bin1_wal_torn_tail_tolerated(tmp_path):
    wal = str(tmp_path / "torn.wal")
    hub = Hub(wal_path=wal, wal_codec="bin1")
    for i in range(5):
        hub.create_pod(MakePod().name(f"t{i}").obj())
    hub.close()
    # a frame cut mid-write: bogus length prefix + partial payload
    with open(wal, "ab") as f:
        f.write(b"\x00\x00\x02\x00only-part-of-a-frame")
    hub2 = Hub(wal_path=wal, wal_codec="bin1")
    assert len(hub2.list_pods()) == 5
    # repair truncated the tail: the next restart replays cleanly too
    hub2.create_pod(MakePod().name("after-torn").obj())
    hub2.close()
    hub3 = Hub(wal_path=wal, wal_codec="bin1")
    assert len(hub3.list_pods()) == 6
    hub3.close()


def test_json_wal_upgrades_in_place_to_bin1(tmp_path):
    """Mixed-format replay: an old JSON-lines WAL opened under
    wal_codec='bin1' replays fine and is rewritten as bin1 on the
    spot (the in-place upgrade), preserving revisions and state."""
    wal = str(tmp_path / "up.wal")
    hub = Hub(wal_path=wal)            # JSON era
    for i in range(8):
        hub.create_pod(MakePod().name(f"u{i}").obj())
    rv = hub.current_rv
    hub.close()
    with open(wal, "rb") as f:
        assert f.read(1) == b"{"
    hub2 = Hub(wal_path=wal, wal_codec="bin1")
    assert hub2.current_rv == rv
    assert len(hub2.list_pods()) == 8
    assert hub2.journal.wal_format == "bin1", \
        "first replay must rewrite the file in the configured codec"
    with open(wal, "rb") as f:
        assert f.read(1) != b"{"
    hub2.create_pod(MakePod().name("post-upgrade").obj())
    hub2.close()
    hub3 = Hub(wal_path=wal, wal_codec="bin1")
    assert len(hub3.list_pods()) == 9
    assert hub3.current_rv == rv + 1
    hub3.close()


def test_segment_transfer_control_records_replay(tmp_path):
    """Ring-rebalance segment transfers persist as WAL control
    records: a restart replays attaches/detaches silently (no events,
    original revisions)."""
    wal_a = str(tmp_path / "a.wal")
    wal_b = str(tmp_path / "b.wal")
    a = Hub(wal_path=wal_a, wal_codec="bin1")
    b = Hub(wal_path=wal_b, wal_codec="bin1")
    for i in range(6):
        a.create_pod(MakePod().name(f"x{i}").namespace(f"ns-{i}").obj())
    moved = a.export_segment([0], 1)        # every slot -> slot 0
    assert len(moved) == 6
    assert b.import_segment(moved) == 6
    assert a.drop_segment([0], 1) == 6
    a.close()
    b.close()
    a2 = Hub(wal_path=wal_a, wal_codec="bin1")
    b2 = Hub(wal_path=wal_b, wal_codec="bin1")
    assert a2.list_pods() == []
    got = sorted(p.metadata.name for p in b2.list_pods())
    assert got == [f"x{i}" for i in range(6)]
    # original revisions survived the transfer
    assert {p.metadata.resource_version
            for p in b2.list_pods()} == set(range(1, 7))
    a2.close()
    b2.close()
