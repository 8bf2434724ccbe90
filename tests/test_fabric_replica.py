"""Replicated state core (ISSUE 13): leader election, majority-ack log
replication for rv/fencing/ring, WAL log replay, leader-lease reads,
NotLeader redirects, and the retry-idempotency audit.

Everything here is in-thread (real HTTP, real Raft-lite RPCs, fast
election timeouts) and runs at seconds scale in tier-1; the kill -9
storm batteries live in ``chaos --storm state`` and the fanout procs
smoke (slow-marked).
"""

from __future__ import annotations

import time
import urllib.request

import pytest

from kubernetes_tpu.fabric.replica import (
    ReplicaClient,
    StateReplica,
)
from kubernetes_tpu.hub import NotLeader, Unavailable
from kubernetes_tpu.hubclient import RemoteHub
from kubernetes_tpu.hubserver import HubServer
from kubernetes_tpu.leaderelection import Lease

pytestmark = pytest.mark.fabric_replica

FAST = {"heartbeat_s": 0.05, "election_timeout_s": (0.25, 0.5)}


class _Trio:
    """Three in-thread replicas behind real HubServers."""

    def __init__(self, tmp_path, names=("state-0", "state-1", "state-2"),
                 pod_shards=("pods-0", "pods-1"),
                 log_compact_threshold: int = 4096):
        self.tmp = tmp_path
        self.names = list(names)
        self.pod_shards = list(pod_shards)
        self.compact = log_compact_threshold
        self.replicas: dict[str, StateReplica] = {}
        self.servers: dict[str, HubServer] = {}
        for n in self.names:
            self.replicas[n] = self._make(n)
            self.servers[n] = HubServer(self.replicas[n])
        self.peer_map = {n: self.servers[n].address for n in self.names}
        for n in self.names:
            self.replicas[n].set_peers(self.peer_map)
            self.servers[n].start()
        for n in self.names:
            self.replicas[n].start()

    def _make(self, name: str) -> StateReplica:
        return StateReplica(name, pod_shards=self.pod_shards,
                            wal_path=str(self.tmp / f"{name}.wal"),
                            log_compact_threshold=self.compact,
                            **FAST)

    def client(self) -> ReplicaClient:
        return ReplicaClient(list(self.peer_map.values()))

    def leader_name(self, timeout_s: float = 10.0) -> str:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            for n, r in self.replicas.items():
                if r.fabric_replica_status()["role"] == "leader":
                    return n
            time.sleep(0.05)
        raise AssertionError("no leader elected")

    def kill(self, name: str) -> None:
        """In-thread kill -9 analog: the server stops answering and the
        replica's ticker halts — no drain, no clean WAL close."""
        self.servers[name].stop()
        self.replicas[name].close()

    def restart(self, name: str) -> StateReplica:
        """Rebuild from the same WAL onto the SAME pinned port (the
        etcd static-bootstrap model the supervisor uses)."""
        port = int(self.peer_map[name].rsplit(":", 1)[1])
        r = self._make(name)
        r.set_peers(self.peer_map)
        srv = HubServer(r, port=port).start()
        r.start()
        self.replicas[name] = r
        self.servers[name] = srv
        return r

    def stop(self) -> None:
        for n in self.names:
            try:
                self.servers[n].stop()
            except Exception:  # noqa: BLE001 — already stopped
                pass
            try:
                self.replicas[n].close()
            except Exception:  # noqa: BLE001
                pass


@pytest.fixture()
def trio(tmp_path):
    t = _Trio(tmp_path)
    yield t
    t.stop()


def test_election_and_replicated_allocation(trio):
    client = trio.client()
    try:
        leader = trio.leader_name()
        # exactly one leader
        roles = [r.fabric_replica_status()["role"]
                 for r in trio.replicas.values()]
        assert roles.count("leader") == 1, roles
        # rv allocation is monotone through the quorum
        seen = [client.rv.next() for _ in range(8)]
        assert seen == sorted(seen) and len(set(seen)) == 8
        # ...and every replica converges to the same applied counter
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            rvs = {r.fabric_replica_status()["applied_rv"]
                   for r in trio.replicas.values()}
            if rvs == {seen[-1]}:
                break
            time.sleep(0.05)
        assert rvs == {seen[-1]}, rvs
        # a write addressed directly to a follower answers NotLeader
        # with a redirect hint that names the leader
        follower = next(n for n in trio.names if n != leader)
        direct = RemoteHub(trio.peer_map[follower], timeout=5.0)
        try:
            with pytest.raises(NotLeader) as ei:
                direct.rv.next()
            assert ei.value.leader_url == trio.peer_map[leader]
            assert ei.value.term >= 1
        finally:
            direct.close()
    finally:
        client.close()


def test_follower_reads_within_staleness_bound(trio):
    client = trio.client()
    try:
        leader = trio.leader_name()
        client.rv.next()
        follower = next(n for n in trio.names if n != leader)
        direct = RemoteHub(trio.peer_map[follower], timeout=5.0)
        try:
            # non-fencing reads serve from a follower inside the
            # leader-lease staleness bound...
            ring = direct.fabric_ring()
            assert ring["epoch"] == 1 and len(ring["slots"]) == 64
            assert "replicas" in direct.fabric_topology()
            # ...but fencing reads are leader-only: a lagging follower
            # answering epoch_of would un-fence a deposed scheduler
            with pytest.raises(NotLeader):
                direct.leases.epoch_of("kube-scheduler")
        finally:
            direct.close()
    finally:
        client.close()


def test_leader_kill_failover_no_rv_reuse_epoch_monotone(trio):
    client = trio.client()
    try:
        # epoch 1: acquire; some allocation traffic
        client.leases.update(Lease(name="kube-scheduler",
                                   holder_identity="a",
                                   renew_time=1.0, acquire_time=1.0),
                             None)
        assert client.leases.epoch_of("kube-scheduler") == 1
        before = [client.rv.next() for _ in range(6)]
        leader = trio.leader_name()
        trio.kill(leader)
        # the client rides out the election and keeps allocating —
        # never reusing or reissuing a committed revision
        after = [client.rv.next() for _ in range(6)]
        allrv = before + after
        assert len(set(allrv)) == len(allrv), "rv reused across failover"
        assert min(after) > max(before), "rv went backwards"
        # fencing state survived: the epoch is monotone, and a steal
        # through the NEW quorum bumps it exactly once
        assert client.leases.epoch_of("kube-scheduler") == 1
        client.leases.update(Lease(name="kube-scheduler",
                                   holder_identity="b",
                                   renew_time=2.0, acquire_time=2.0),
                             "a")
        assert client.leases.epoch_of("kube-scheduler") == 2
    finally:
        client.close()


def test_wal_replay_rejoins_log_consistent(trio):
    client = trio.client()
    try:
        for _ in range(5):
            client.rv.next()
        client.leases.update(Lease(name="kube-scheduler",
                                   holder_identity="x",
                                   renew_time=1.0, acquire_time=1.0),
                             None)
        ring = client.fabric_ring()
        assert client.fabric_set_ring(
            {"epoch": 2, "slots": ring["slots"]}, 1)
        leader = trio.leader_name()
        victim = next(n for n in trio.names if n != leader)
        trio.kill(victim)
        post_kill = [client.rv.next() for _ in range(4)]
        # restart from the WAL: the log replays, the leader catches the
        # rejoined follower up, and its applied state machine matches
        r2 = trio.restart(victim)
        deadline = time.monotonic() + 10
        caught = False
        while time.monotonic() < deadline:
            st = r2.fabric_replica_status()
            if st["applied_rv"] >= max(post_kill):
                caught = True
                break
            time.sleep(0.05)
        assert caught, r2.fabric_replica_status()
        assert r2._sm_ring["epoch"] == 2
        assert r2._sm_leases.epoch_of("kube-scheduler") == 1
        assert r2.fabric_replica_status()["role"] == "follower"
    finally:
        client.close()


def test_retry_budget_audit_cas_and_epoch_of_idempotent(trio):
    """The ISSUE-13 retry audit: under the replica protocol a
    timeout-retried ``fabric_set_ring`` CAS never double-applies (the
    duplicate answers False and the epoch bumps exactly once), repeated
    ``leases.epoch_of`` reads are stable, and a retried ``rv.next``
    burns a gap — a fresh value, never a reissued one."""
    client = trio.client()
    try:
        ring = client.fabric_ring()
        new_ring = {"epoch": 2, "slots": ring["slots"]}
        assert client.fabric_set_ring(new_ring, 1) is True
        # the blind retry of an already-committed CAS: False, and the
        # epoch did NOT bump twice
        assert client.fabric_set_ring(new_ring, 1) is False
        assert client.fabric_ring()["epoch"] == 2
        # epoch_of is a pure read: stable across retries
        client.leases.update(Lease(name="kube-scheduler",
                                   holder_identity="x",
                                   renew_time=1.0, acquire_time=1.0),
                             None)
        assert [client.leases.epoch_of("kube-scheduler")
                for _ in range(3)] == [1, 1, 1]
        # a retried rv.next draws a FRESH revision (gap-burn, the
        # journal's contract) — never the same one twice
        a, b = client.rv.next(), client.rv.next()
        assert b > a
    finally:
        client.close()


def test_follower_healthz_and_replica_metrics(trio):
    """ISSUE-13 telemetry satellite: followers answer /healthz with
    200-with-role (healthy, not degraded), /metrics carries the
    fabric_state_* gauges, and FleetView summary rows say who leads."""
    from kubernetes_tpu.telemetry.fleet import FleetView, parse_exposition

    leader = trio.leader_name()
    follower = next(n for n in trio.names if n != leader)
    with urllib.request.urlopen(trio.peer_map[follower] + "/healthz",
                                timeout=5.0) as resp:
        assert resp.status == 200
        body = resp.read().decode()
    assert body.startswith("ok") and "role=follower" in body
    with urllib.request.urlopen(trio.peer_map[follower] + "/metrics",
                                timeout=5.0) as resp:
        exp = parse_exposition(resp.read().decode())
    names = {s.name for s in exp.samples}
    assert {"fabric_state_replica_role", "fabric_state_term",
            "fabric_state_log_index",
            "fabric_state_commit_index"} <= names
    role_samples = [s for s in exp.samples
                    if s.name == "fabric_state_replica_role"]
    assert role_samples[0].labels["role"] == "follower"
    assert role_samples[0].labels["replica"] == follower
    # FleetView: every replica healthy, exactly one leader row
    fleet = FleetView([{"component": "state", "shard": n, "url": u}
                       for n, u in trio.peer_map.items()])
    summary = fleet.summary()
    assert summary["ok"], summary
    roles = [r["role"] for r in summary["endpoints"]]
    assert roles.count("leader") == 1
    assert roles.count("follower") == 2


def test_replica_client_discovers_full_set(trio):
    """A client pointed at ONE member learns the rest from the status
    verb and can therefore survive that member's death."""
    some_url = list(trio.peer_map.values())[0]
    client = ReplicaClient([some_url])
    try:
        rows = client.replica_status()
        assert len(rows) >= 1
        # after discovery, the full set is known
        rows = client.replica_status()
        assert len(rows) == 3, rows
        assert client.rv.next() >= 1
    finally:
        client.close()


def test_log_compaction_bounds_wal_and_snapshot_install(tmp_path):
    """The log and WAL must not grow with every rv the fleet ever
    drew: past the threshold, applied entries compact behind a
    state-machine snapshot (bounded memory + bounded WAL), and a
    follower whose WAL is GONE rejoins via leader snapshot install."""
    import os

    trio = _Trio(tmp_path, log_compact_threshold=24)
    client = trio.client()
    try:
        client.leases.update(Lease(name="kube-scheduler",
                                   holder_identity="x",
                                   renew_time=1.0, acquire_time=1.0),
                             None)
        for _ in range(120):
            client.rv.next()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if all(r.fabric_replica_status()["applied_rv"] == 120
                   for r in trio.replicas.values()):
                break
            time.sleep(0.05)
        for n, r in trio.replicas.items():
            st = r.fabric_replica_status()
            assert st["applied_rv"] == 120, (n, st)
            assert len(r._log) <= 30, \
                f"{n}: log not compacted ({len(r._log)} entries)"
            assert st["compact_floor"] > 0
            wal = os.path.getsize(str(tmp_path / f"{n}.wal"))
            assert wal < 200_000, f"{n}: WAL unbounded ({wal}B)"
        # a follower that lost its ENTIRE WAL (disk replaced) catches
        # up from the leader's snapshot, state machine included
        leader = trio.leader_name()
        victim = next(n for n in trio.names if n != leader)
        trio.kill(victim)
        os.remove(str(tmp_path / f"{victim}.wal"))
        for _ in range(30):
            client.rv.next()
        r2 = trio.restart(victim)
        deadline = time.monotonic() + 15
        caught = False
        while time.monotonic() < deadline:
            if r2.fabric_replica_status()["applied_rv"] >= 150:
                caught = True
                break
            time.sleep(0.05)
        assert caught, r2.fabric_replica_status()
        assert r2._floor_idx > 0, "rejoin must be a snapshot install"
        assert r2._sm_leases.epoch_of("kube-scheduler") == 1
    finally:
        client.close()
        trio.stop()


@pytest.mark.slow
def test_state_storm_small():
    """The replicated-state kill -9 battery at reduced scale (the full
    300-pod run is ``chaos --storm state``)."""
    from kubernetes_tpu.chaos import run_state_storm

    r = run_state_storm(pods=80, nodes=8, timeout_s=180)
    assert r["ok"], r
    assert r["duplicate_binds"] == {}
    assert r["rv_reused"] == 0
    assert r["stale_epoch_fenced"]
    assert r["client_relists"] == 0
    assert r["rebalance"]["result"] in ("completed", "rolled_back")


def test_quorum_loss_parks_writes(trio, tmp_path):
    """Majority gone: the survivor parks writes (Unavailable) instead
    of answering from a minority — the failure-ladder's 'quorum loss'
    rung."""
    client = trio.client()
    try:
        client.rv.next()
        leader = trio.leader_name()
        others = [n for n in trio.names if n != leader]
        trio.kill(others[0])
        trio.kill(others[1])
        # give the survivor time to lose its lease
        time.sleep(1.0)
        short = ReplicaClient([trio.peer_map[leader]],
                              redirect_deadline_s=1.5)
        try:
            with pytest.raises(Unavailable):
                short.rv.next()
        finally:
            short.close()
    finally:
        client.close()
