"""Out-of-process control-plane fabric (ISSUE 11): shard processes,
the stateless bin1 router, per-shard resume cursors, ring rebalancing,
and relay auto-topology.

Most tests run the REAL wire with in-thread shard servers (the routing
and cursor logic is identical; threads keep tier-1 fast); the
subprocess tests spawn actual OS processes — a seconds-scale
two-process smoke stays tier-1, the storm-scale batteries are
slow-marked (fabric.fanout --procs / chaos --storm proc run them at
full size).
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from kubernetes_tpu.fabric.cluster import (
    RING_SLOTS,
    ClusterClient,
    ProcShardHub,
    StateCore,
    ring_slot,
)
from kubernetes_tpu.fabric.router import RouterServer, fetch_topology
from kubernetes_tpu.hub import EventHandlers, Fenced, NotFound
from kubernetes_tpu.hubclient import RemoteHub
from kubernetes_tpu.hubserver import HubServer
from kubernetes_tpu.testing import MakeNode, MakePod

pytestmark = pytest.mark.fabric_proc


class _ThreadCluster:
    """The full fabric topology with in-thread shard servers: real
    HTTP, real routing, real cursors — no subprocess spawn cost."""

    def __init__(self, pod_shards: int = 2, tmp_path=None,
                 wal_codec: str = "bin1"):
        self.pod_names = [f"pods-{i}" for i in range(pod_shards)]
        self.state_core = StateCore(pod_shards=self.pod_names)
        self.state_srv = HubServer(self.state_core).start()
        self.state_url = self.state_srv.address
        self.hubs: dict[str, ProcShardHub] = {}
        self.servers: dict[str, HubServer] = {}
        self._state_clients: list[RemoteHub] = []
        specs = [("nodes", ["nodes"]), ("events", ["events"]),
                 ("meta", ["*"])]
        specs += [(n, ["pods"]) for n in self.pod_names]
        for name, kinds in specs:
            sc = RemoteHub(self.state_url, timeout=10.0)
            self._state_clients.append(sc)
            wal = str(tmp_path / f"{name}.wal") if tmp_path else None
            hub = ProcShardHub(name, sc, wal_path=wal,
                               wal_codec=wal_codec)
            srv = HubServer(hub).start()
            self.hubs[name] = hub
            self.servers[name] = srv
            self.state_core.fabric_register_shard(
                name, srv.address, kinds, os.getpid())
        self.router = RouterServer(self.state_url).start()
        self.router_url = self.router.address

    def restart_shard(self, name: str, tmp_path=None,
                      wal_codec: str = "bin1"):
        """The in-thread analog of a process restart: tear the shard's
        server down (watchers cut), rebuild the hub from its WAL, and
        re-register on a NEW port."""
        self.servers[name].stop()
        self.hubs[name].close()
        sc = RemoteHub(self.state_url, timeout=10.0)
        self._state_clients.append(sc)
        wal = str(tmp_path / f"{name}.wal") if tmp_path else None
        hub = ProcShardHub(name, sc, wal_path=wal, wal_codec=wal_codec)
        srv = HubServer(hub).start()
        self.hubs[name] = hub
        self.servers[name] = srv
        kinds = ["pods"] if name in self.pod_names else \
            {"nodes": ["nodes"], "events": ["events"],
             "meta": ["*"]}[name]
        self.state_core.fabric_register_shard(name, srv.address, kinds,
                                              os.getpid())
        return srv

    def stop(self) -> None:
        self.router.stop()
        for srv in self.servers.values():
            srv.stop()
        for hub in self.hubs.values():
            hub.close()
        for sc in self._state_clients:
            sc.close()
        self.state_srv.stop()


@pytest.fixture()
def cluster(tmp_path):
    c = _ThreadCluster(pod_shards=2, tmp_path=tmp_path)
    yield c
    c.stop()


# ------------------------- shared-state shard -------------------------


def test_state_shard_rv_allocation_and_fencing():
    core = StateCore(pod_shards=["pods-0"])
    srv = HubServer(core).start()
    a = RemoteHub(srv.address, timeout=10.0)
    b = RemoteHub(srv.address, timeout=10.0)
    try:
        seen = [a.rv.next(), b.rv.next(), a.rv.next()]
        assert seen == sorted(seen) and len(set(seen)) == 3
        assert b.rv.last() == seen[-1]
        a.rv.advance_to(100)
        assert b.rv.next() == 101
        # fencing epochs over the wire
        from kubernetes_tpu.leaderelection import Lease

        assert a.leases.epoch_of("kube-scheduler") == 0
        a.leases.update(Lease(name="kube-scheduler",
                              holder_identity="x", renew_time=1.0,
                              acquire_time=1.0), None)
        assert b.leases.epoch_of("kube-scheduler") == 1
        # ring CAS
        ring = a.fabric_ring()
        assert ring["epoch"] == 1 and len(ring["slots"]) == RING_SLOTS
        assert not a.fabric_set_ring(
            {"epoch": 5, "slots": ring["slots"]}, 99)
    finally:
        a.close()
        b.close()
        srv.stop()


# ----------------------- router: /call + /watch -----------------------


def test_router_routes_and_tags_events(cluster):
    client = RemoteHub(cluster.router_url, timeout=10.0)
    try:
        client.create_node(MakeNode().name("rn").obj())
        pods = [MakePod().name(f"rp{i}").namespace(f"ns-{i}").obj()
                for i in range(8)]
        for p in pods:
            client.create_pod(p)
        assert len(client.list_pods()) == 8
        assert cluster.hubs["nodes"].commits == 1
        spread = [h.commits for n, h in cluster.hubs.items()
                  if n.startswith("pods-")]
        assert sum(spread) == 8 and all(spread), \
            "namespace ring must spread pods over both shard procs"
        evs = []
        client.watch_kinds({"pods": EventHandlers(
            on_event=lambda ev: evs.append(ev))})
        assert len(evs) == 8
        assert {e.shard for e in evs} == {"pods-0", "pods-1"}
        # live events keep their source tag
        client.create_pod(MakePod().name("live").namespace("zz").obj())
        deadline = time.time() + 5
        while len(evs) < 9 and time.time() < deadline:
            time.sleep(0.02)
        assert evs[-1].shard in ("pods-0", "pods-1")
        # uid ops probe the right shard; fencing is hub-wide
        client.bind(pods[0], "rn")
        assert client.get_pod(pods[0].metadata.uid).spec.node_name \
            == "rn"
        from kubernetes_tpu.leaderelection import Lease

        client.leases.update(Lease(name="kube-scheduler",
                                   holder_identity="x", renew_time=1.0,
                                   acquire_time=1.0), None)
        client.leases.update(Lease(name="kube-scheduler",
                                   holder_identity="y", renew_time=2.0,
                                   acquire_time=2.0), "x")
        with pytest.raises(Fenced):
            client.bind(pods[1], "rn", 1)   # stale epoch (positional:
        #                                     the wire carries no kwargs)
    finally:
        client.close()


def test_router_cursor_resume_is_exact(cluster):
    client = RemoteHub(cluster.router_url, timeout=10.0)
    try:
        for i in range(6):
            client.create_pod(MakePod().name(f"c{i}")
                              .namespace(f"ns-{i}").obj())
        evs = []
        client.watch_kinds({"pods": EventHandlers(
            on_event=lambda ev: evs.append(ev))})
        cursors: dict[str, int] = {}
        for e in evs:
            cursors[e.shard] = max(cursors.get(e.shard, 0), e.rv)
        for i in range(6, 9):
            client.create_pod(MakePod().name(f"c{i}")
                              .namespace(f"ns-{i}").obj())
        # a fresh client resuming at the captured composite cursor
        # gets EXACTLY the commits it missed, across both shards
        late = RemoteHub(cluster.router_url, timeout=10.0)
        try:
            evs2 = []
            late.watch_kinds({"pods": EventHandlers(
                on_event=lambda ev: evs2.append(ev))},
                cursors=cursors)
            deadline = time.time() + 5
            while len(evs2) < 3 and time.time() < deadline:
                time.sleep(0.02)
            assert sorted(e.new.metadata.name for e in evs2) \
                == ["c6", "c7", "c8"]
        finally:
            late.close()
        # a resume point beyond the revision space answers 410 -> the
        # reflector relists (counted) instead of pinning phantom state
        relist = RemoteHub(cluster.router_url, timeout=10.0)
        try:
            evs3 = []
            relist.watch_kinds({"pods": EventHandlers(
                on_event=lambda ev: evs3.append(ev))},
                since_rv=10_000)
            assert len(evs3) == 9, "410 must degrade to a full LIST"
            assert relist.resilience_stats()["watch_relists"] == 0, \
                "the first-dial 410 fallback is not a mid-life relist"
        finally:
            relist.close()
    finally:
        client.close()


def test_shard_restart_with_wal_replay_heals_router(cluster, tmp_path):
    client = RemoteHub(cluster.router_url, timeout=10.0,
                       retry_deadline=15.0)
    try:
        pods = [MakePod().name(f"w{i}").namespace(f"ns-{i}").obj()
                for i in range(6)]
        for p in pods:
            client.create_pod(p)
        evs = []
        client.watch_kinds({"pods": EventHandlers(
            on_event=lambda ev: evs.append(ev))})
        n0 = len(evs)
        rv_before = client.rv.last()
        cluster.restart_shard("pods-0", tmp_path=tmp_path)
        # the revision space continues (allocator survives the shard)
        assert client.rv.last() >= rv_before
        # writes heal once the router re-resolves the new port
        deadline = time.time() + 20
        landed = False
        while time.time() < deadline and not landed:
            try:
                client.create_pod(MakePod().name("post-restart")
                                  .namespace("ns-0").obj())
                landed = True
            except Exception:  # noqa: BLE001 — mid-restart window
                time.sleep(0.2)
        assert landed
        assert len(client.list_pods()) == 7, \
            "WAL replay must resurrect the shard's pods"
        # the cut watcher resumed (cursors) and sees the new commit
        deadline = time.time() + 15
        while time.time() < deadline and not any(
                e.new is not None
                and e.new.metadata.name == "post-restart"
                for e in evs[n0:]):
            time.sleep(0.1)
        assert any(e.new is not None
                   and e.new.metadata.name == "post-restart"
                   for e in evs[n0:])
        assert client.resilience_stats()["watch_relists"] == 0
    finally:
        client.close()


# --------------------------- ring rebalance ---------------------------


def test_rebalance_is_event_silent_and_reroutes(cluster):
    client = RemoteHub(cluster.router_url, timeout=10.0)
    try:
        for i in range(6):
            client.create_pod(MakePod().name(f"m{i}")
                              .namespace(f"ns-{i}").obj())
        evs = []
        client.watch_kinds({"pods": EventHandlers(
            on_event=lambda ev: evs.append(ev))})
        n0 = len(evs)
        slot = ring_slot("ns-0", RING_SLOTS)
        src = client.fabric_ring()["slots"][slot]
        dst = "pods-1" if src == "pods-0" else "pods-0"
        r = client.rebalance_segment([slot], dst)
        assert r["moved"].get(src, 0) >= 1
        assert r["pending_drops"] == []
        time.sleep(0.3)
        assert len(evs) == n0, "a segment move must emit NO events"
        # post-move commits land on (and are tagged with) the target
        client.create_pod(MakePod().name("moved").namespace("ns-0")
                          .obj())
        deadline = time.time() + 5
        while time.time() < deadline and not any(
                e.new is not None and e.new.metadata.name == "moved"
                for e in evs):
            time.sleep(0.05)
        tagged = [e for e in evs if e.new is not None
                  and e.new.metadata.name == "moved"]
        assert tagged and tagged[0].shard == dst
        # no duplicates, no holes in a fresh merged LIST
        assert len(client.list_pods()) == 7
    finally:
        client.close()


def test_rebalance_property_resume_points_survive(cluster):
    """The satellite property test: for ANY ring move, every live
    watch's composite cursor remains servable (0 relists) and
    list_changes never skips a commit that landed around the handoff.
    Seeded random segment moves with commits interleaved."""
    import random

    rng = random.Random(1711)
    client = RemoteHub(cluster.router_url, timeout=10.0)
    try:
        namespaces = [f"prop-{i}" for i in range(10)]
        n_created = 0

        def commit(n: int) -> None:
            nonlocal n_created
            for _ in range(n):
                client.create_pod(
                    MakePod().name(f"pp-{n_created}")
                    .namespace(rng.choice(namespaces)).obj())
                n_created += 1

        commit(6)
        evs = []
        client.watch_kinds({"pods": EventHandlers(
            on_event=lambda ev: evs.append(ev))})
        for round_no in range(4):
            # capture a composite cursor from the live watch
            cursors: dict[str, int] = {}
            for e in evs:
                if e.shard:
                    cursors[e.shard] = max(cursors.get(e.shard, 0),
                                           e.rv)
            snap_rv = client.rv.last()
            seen_before = len(evs)
            commit(2)
            # any segment, any direction, mid-commit
            slot = ring_slot(rng.choice(namespaces), RING_SLOTS)
            ring = client.fabric_ring()
            src = ring["slots"][slot]
            dst = rng.choice([n for n in cluster.pod_names
                              if n != src])
            client.rebalance_segment([slot], dst)
            commit(2)
            # (a) the captured cursor resumes exactly: a fresh client
            # must receive precisely the 4 commits after the capture
            probe = RemoteHub(cluster.router_url, timeout=10.0)
            try:
                got = []
                probe.watch_kinds({"pods": EventHandlers(
                    on_event=lambda ev: got.append(ev))},
                    cursors=dict(cursors))
                deadline = time.time() + 10
                while len(got) < 4 and time.time() < deadline:
                    time.sleep(0.02)
                names = sorted(g.new.metadata.name for g in got)
                want = sorted(f"pp-{i}" for i in
                              range(n_created - 4, n_created))
                assert names == want, \
                    f"round {round_no}: resume skipped/duplicated: " \
                    f"{names} != {want}"
                assert probe.resilience_stats()["watch_relists"] == 0
            finally:
                probe.close()
            # (b) the live watch saw every commit (no move events, no
            # holes) ...
            deadline = time.time() + 10
            while len(evs) < seen_before + 4 \
                    and time.time() < deadline:
                time.sleep(0.02)
            assert len(evs) == seen_before + 4
            # (c) ... and list_changes from the snapshot rv never
            # skips a commit that landed around the handoff
            changes = client.list_changes(snap_rv, ("pods",))
            assert not changes["too_old"]
            got_rvs = {c["rv"] for c in changes["changes"]}
            new_rvs = {e.rv for e in evs[seen_before:]}
            assert new_rvs <= got_rvs, \
                f"round {round_no}: list_changes skipped " \
                f"{new_rvs - got_rvs}"
        assert client.resilience_stats()["watch_relists"] == 0
    finally:
        client.close()


# ------------------------ relay auto-topology ------------------------


def test_relay_advertise_discover_and_reparent(cluster):
    from kubernetes_tpu.fabric.relay import (
        RelayCore,
        RelayServer,
        discover_relay_url,
        pick_relay,
    )

    client = RemoteHub(cluster.router_url, timeout=10.0)
    l1a = RelayServer(
        RelayCore(cluster.router_url, kinds=("pods",), timeout=10.0),
        advertise={"state_url": cluster.router_url, "name": "l1-a",
                   "parent": cluster.router_url,
                   "interval_s": 0.2}).start()
    l1b = RelayServer(
        RelayCore(cluster.router_url, kinds=("pods",), timeout=10.0),
        advertise={"state_url": cluster.router_url, "name": "l1-b",
                   "parent": cluster.router_url,
                   "interval_s": 0.2}).start()
    l2 = None
    try:
        for i in range(4):
            client.create_pod(MakePod().name(f"t{i}")
                              .namespace(f"ns-{i}").obj())
        deadline = time.time() + 10
        topo = {}
        while time.time() < deadline:
            topo = fetch_topology(cluster.router_url)
            if len(topo.get("relays", [])) >= 2:
                break
            time.sleep(0.1)
        assert sorted(r["name"] for r in topo["relays"]) \
            == ["l1-a", "l1-b"]
        assert topo["routers"], "the router must register itself"
        assert pick_relay(topo, seed=3) is not None
        url = discover_relay_url(cluster.router_url, seed=3)
        assert url in (l1a.address, l1b.address)
        # an L2 relay discovers its parent instead of being flagged
        from kubernetes_tpu.fabric.relay import RelayCore as RC

        l2 = RC(url, kinds=("pods",), timeout=10.0)
        sub = l2.subscribe(("pods",))
        assert len(sub.drain()) == 4
        # re-parent onto the sibling: per-shard cursors carry over,
        # the move costs a resume, downstream sees every later event
        other = l1b.address if url == l1a.address else l1a.address
        l2.reparent(other)
        client.create_pod(MakePod().name("after-reparent")
                          .namespace("ns-7").obj())
        deadline = time.time() + 10
        seen = False
        while time.time() < deadline and not seen:
            sub.event.wait(0.1)
            seen = any(d["new"] is not None
                       and d["new"].metadata.name == "after-reparent"
                       for d in sub.drain())
        assert seen
        assert l2.client.resilience_stats()["watch_relists"] == 0
    finally:
        if l2 is not None:
            l2.close()
        l1a.stop()
        l1b.stop()
        client.close()


def test_relay_cursor_resume_through_router(cluster):
    from kubernetes_tpu.fabric.relay import RelayCore

    client = RemoteHub(cluster.router_url, timeout=10.0)
    core = RelayCore(cluster.router_url, kinds=("pods",), timeout=10.0)
    try:
        for i in range(5):
            client.create_pod(MakePod().name(f"rr{i}")
                              .namespace(f"ns-{i}").obj())
        deadline = time.time() + 10
        while core.last_rv < client.rv.last() \
                and time.time() < deadline:
            time.sleep(0.05)
        sub = core.subscribe(("pods",))
        backlog = sub.drain()
        assert len(backlog) == 5
        assert all(d.get("sh") for d in backlog)
        curs = {k: v for k, v in sub.cursors.items() if k}
        core.unsubscribe(sub)
        client.create_pod(MakePod().name("gap").namespace("ns-0")
                          .obj())
        deadline = time.time() + 10
        while time.time() < deadline:
            with core._lock:
                caught = any(rv >= client.rv.last() for rv in
                             core._ring_rv.values())
            if caught:
                break
            time.sleep(0.05)
        sub2 = core.subscribe(("pods",), since_rv=sub.cursor,
                              cursors=curs)
        got = [d["new"].metadata.name for d in sub2.drain()
               if d["new"] is not None]
        assert got == ["gap"], "composite-cursor resume must replay " \
                               "exactly the gap"
        assert core.resume_serves == 1
    finally:
        core.close()
        client.close()


def test_relay_watchdog_auto_reparents_on_upstream_kill(cluster):
    """ISSUE-13 satellite: an L1 relay dies (SIGKILL analog — its
    server stops answering mid-stream) and its downstream L2 relay
    auto-reparents onto the advertised sibling via the liveness
    watchdog — a cursor-carrying RESUME, so the downstream subscriber
    sees every later event exactly once with 0 relists."""
    from kubernetes_tpu.fabric.relay import RelayCore, RelayServer

    client = RemoteHub(cluster.router_url, timeout=10.0)
    l1a = RelayServer(
        RelayCore(cluster.router_url, kinds=("pods",), timeout=5.0),
        advertise={"state_url": cluster.router_url, "name": "l1-a",
                   "parent": cluster.router_url,
                   "interval_s": 0.2}).start()
    l1b = RelayServer(
        RelayCore(cluster.router_url, kinds=("pods",), timeout=5.0),
        advertise={"state_url": cluster.router_url, "name": "l1-b",
                   "parent": cluster.router_url,
                   "interval_s": 0.2}).start()
    l2 = None
    try:
        for i in range(4):
            client.create_pod(MakePod().name(f"wd{i}")
                              .namespace(f"ns-{i}").obj())
        # both L1s must be on the served map before the kill, so the
        # watchdog has a sibling to discover
        from kubernetes_tpu.fabric.router import fetch_topology

        deadline = time.time() + 10
        while time.time() < deadline:
            if len(fetch_topology(cluster.router_url)
                   .get("relays", [])) >= 2:
                break
            time.sleep(0.1)
        l2 = RelayCore(l1a.address, kinds=("pods",), timeout=5.0,
                       watchdog={"topology_url": cluster.router_url,
                                 "deadline_s": 0.8,
                                 "interval_s": 0.2})
        sub = l2.subscribe(("pods",))
        got = {d["new"].metadata.name for d in sub.drain()
               if d["new"] is not None}
        assert len(got) == 4
        # SIGKILL analog: the upstream stops answering, no drain
        l1a.stop()
        # the watchdog must notice, discover l1-b, and resume there
        deadline = time.time() + 20
        while time.time() < deadline and l2.watchdog_reparents == 0:
            time.sleep(0.1)
        assert l2.watchdog_reparents >= 1, \
            "watchdog never reparented off the dead upstream"
        assert l2.upstream_url == l1b.address
        # later events flow through the new parent, exactly once each
        for i in range(3):
            client.create_pod(MakePod().name(f"post-wd{i}")
                              .namespace(f"ns-{i}").obj())
        want = {f"post-wd{i}" for i in range(3)}
        seen: list[str] = []
        deadline = time.time() + 15
        while time.time() < deadline and not want <= set(seen):
            sub.event.wait(0.1)
            seen.extend(d["new"].metadata.name for d in sub.drain()
                        if d["new"] is not None)
        assert want <= set(seen), f"lost events after reparent: {seen}"
        assert len(seen) == len(set(seen)), f"duplicates: {seen}"
        # the reparent was a RESUME off the sibling's rings, not a
        # relist — downstream continuity is the whole point
        assert l2.client.resilience_stats()["watch_relists"] == 0
    finally:
        if l2 is not None:
            l2.close()
        try:
            l1a.stop()
        except Exception:  # noqa: BLE001 — already stopped
            pass
        l1b.stop()
        client.close()


def test_two_router_concurrent_rebalance_fencing(cluster):
    """ISSUE-13 satellite: a second router keeps writing through its
    own (deliberately stale — TTL pinned high) ring while the first
    router migrates the written segment back and forth. Shard-side
    ring-epoch fencing must redirect every misrouted write (StaleRing
    → re-resolve → retry): zero pods lost, zero duplicated, and every
    pod ends on the shard the final ring assigns."""
    import threading

    from kubernetes_tpu.fabric.router import RouterServer

    # router B re-reads the ring ONLY when fenced: the stale window is
    # guaranteed, not racy
    writer_cluster = ClusterClient(cluster.state_url, ring_ttl_s=60.0)
    router_b = RouterServer(cluster.state_url, name="router-b",
                            cluster=writer_cluster).start()
    admin = RemoteHub(cluster.router_url, timeout=10.0)
    writer = RemoteHub(router_b.address, timeout=10.0,
                       retry_deadline=10.0)
    stop = threading.Event()
    created: list[str] = []
    errors: list[str] = []

    def write_loop() -> None:
        i = 0
        while not stop.is_set():
            name = f"w2r-{i}"
            try:
                writer.create_pod(MakePod().name(name)
                                  .namespace("two-router").obj())
                created.append(name)
            except Exception as e:  # noqa: BLE001 — a write may park
                errors.append(f"{name}: {e!r}")   # during the window
            i += 1
            time.sleep(0.01)

    t = threading.Thread(target=write_loop, daemon=True)
    try:
        slot = ring_slot("two-router", RING_SLOTS)
        t.start()
        deadline = time.time() + 10
        while not created and time.time() < deadline:
            time.sleep(0.02)
        # migrate the written segment back and forth under the writes
        for _ in range(4):
            ring = admin.fabric_ring()
            src = ring["slots"][slot]
            dst = next(n for n in cluster.pod_names if n != src)
            admin.rebalance_segment([slot], dst)
            time.sleep(0.15)
        stop.set()
        t.join(timeout=10)
        assert created, "writer never landed a pod"
        assert not errors, f"writes failed outright: {errors[:3]}"
        # no pod lost or duplicated across the whole churn
        pods = [p for p in admin.list_pods()
                if p.metadata.namespace == "two-router"]
        names = sorted(p.metadata.name for p in pods)
        assert names == sorted(created), \
            f"lost={set(created) - set(names)} " \
            f"extra={set(names) - set(created)}"
        # the stale writer was actually fenced and redirected at least
        # once (ring TTL 60s: only StaleRing can have re-resolved it)
        assert writer_cluster.stale_ring_retries >= 1
        # final ownership agrees with the final ring: the segment's
        # pods live ONLY on the assigned shard
        final_owner = admin.fabric_ring()["slots"][slot]
        for name, hub in cluster.hubs.items():
            if not name.startswith("pods-"):
                continue
            here = [p.metadata.name for p in hub.list_pods()
                    if p.metadata.namespace == "two-router"]
            if name == final_owner:
                assert sorted(here) == sorted(created)
            else:
                assert here == [], \
                    f"stray segment copy on {name}: {here[:3]}"
        # and the two routers cannot both win one epoch: a racing CAS
        # loses cleanly (Conflict → rolled back), never half-applies
        ring = admin.fabric_ring()
        src = ring["slots"][slot]
        dst = next(n for n in cluster.pod_names if n != src)
        results: list = [None, None]

        def race(idx, client_) -> None:
            try:
                results[idx] = client_.rebalance_segment([slot], dst)
            except Exception as e:  # noqa: BLE001 — the loser's verdict
                results[idx] = e

        ra = threading.Thread(target=race, args=(0, admin))
        rb = threading.Thread(target=race, args=(1, writer))
        ra.start()
        rb.start()
        ra.join(15)
        rb.join(15)
        wins = [r for r in results if isinstance(r, dict)]
        assert len(wins) >= 1, results
        assert len(admin.list_pods()) >= len(created), results
    finally:
        stop.set()
        admin.close()
        writer.close()
        router_b.stop()


# ----------------------- real OS processes -----------------------


def test_two_process_smoke(tmp_path):
    """Tier-1 process smoke: state + ONE all-kinds shard as real OS
    processes (the minimal fabric), an in-thread router, CRUD + watch
    + kill -9 + restart-with-WAL-replay — seconds, not minutes."""
    from kubernetes_tpu.fabric.supervisor import spawn_local_cluster

    c = spawn_local_cluster(pod_shards=1, kind_shards=False,
                            wal_dir=str(tmp_path), router=False)
    router = RouterServer(c.state_url).start()
    client = RemoteHub(router.address, timeout=10.0)
    try:
        assert len(c.sup.procs) == 2, sorted(c.sup.procs)
        client.create_node(MakeNode().name("n").obj())
        for i in range(4):
            client.create_pod(MakePod().name(f"s{i}")
                              .namespace(f"ns-{i}").obj())
        evs = []
        client.watch_kinds({"pods": EventHandlers(
            on_event=lambda ev: evs.append(ev))})
        assert len(evs) == 4 and evs[0].shard == "pods-0"
        rv = client.rv.last()
        # kill -9: no drain, no WAL close — the replay must cover it
        c.sup.kill_shard("pods-0")
        c.sup.restart_shard("pods-0")
        deadline = time.time() + 20
        landed = False
        while time.time() < deadline and not landed:
            try:
                client.create_pod(MakePod().name("back")
                                  .namespace("ns-0").obj())
                landed = True
            except Exception:  # noqa: BLE001 — router re-resolving
                time.sleep(0.2)
        assert landed
        assert len(client.list_pods()) == 5
        assert client.get_node("n") is not None
        assert client.rv.last() > rv
        deadline = time.time() + 15
        while time.time() < deadline and not any(
                e.new is not None and e.new.metadata.name == "back"
                for e in evs):
            time.sleep(0.1)
        assert any(e.new is not None and e.new.metadata.name == "back"
                   for e in evs), "the cut watcher must resume"
        assert client.resilience_stats()["watch_relists"] == 0
    finally:
        client.close()
        router.stop()
        c.stop()


@pytest.mark.slow
def test_fanout_smoke_procs_small():
    """The process-mode storm battery at reduced scale (the full 50k
    run is ``python -m kubernetes_tpu.fabric.fanout --procs``)."""
    from kubernetes_tpu.fabric.fanout import run_fanout_smoke_procs

    r = run_fanout_smoke_procs(subscribers=200, pods=40, churn=20,
                               cuts=4, resub=40, timeout_s=240)
    assert r["ok"], r
    assert r["upstream_relists"] == 0
    assert r["event_count_min"] == r["event_count_max"] \
        == r["pod_events"]
    assert r["wal_replay_ratio"] >= 3.0
    assert all(v <= 2 for v in r["shard_pod_watchers"].values())


@pytest.mark.slow
def test_proc_crash_storm_small():
    """Process-level kill -9 + WAL-replay chaos (the full battery is
    ``chaos --storm proc``)."""
    from kubernetes_tpu.chaos import run_proc_crash_storm

    r = run_proc_crash_storm(pods=80, nodes=8, timeout_s=180)
    assert r["ok"], r
    assert r["duplicate_binds"] == {}
    assert r["epoch_after_restart"] >= r["epoch_before_kill"] >= 1
    assert r["stale_epoch_fenced"]
