"""Horizontal scheduler scale-out (ISSUE 16): the slice ring
(rebalance math, board CAS, slice-lease fencing), the SliceManager's
join/death/release lifecycle, partition filters in both queues (gangs
route whole by their group's namespace), the journal-replay bind audit,
the replicated sched-ring surviving leader failover, and an in-thread
two-replica partition drain.

Everything here runs at tier-1 speed; the 4-replica kill -9 storm is
slow-marked (it also runs in ``chaos --storm scaleout``).
"""

from __future__ import annotations

import time

import pytest

from kubernetes_tpu.api.objects import (
    LABEL_HOSTNAME,
    LABEL_POD_GROUP,
    LABEL_QUEUE,
    pod_group_key,
)
from kubernetes_tpu.backend.jobqueue import JobQueue
from kubernetes_tpu.backend.queue import PriorityQueue
from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.fabric.replica import StateReplica
from kubernetes_tpu.framework.interface import Status
from kubernetes_tpu.hub import Conflict, Fenced, Hub
from kubernetes_tpu.hubclient import RemoteHub
from kubernetes_tpu.hubserver import HubServer
from kubernetes_tpu.leaderelection import (
    RING_SLOTS,
    SCHED_SLICE_LEASE,
    SliceBoard,
    SliceManager,
    rebalance_slots,
    ring_slot,
)
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing import MakeNode, MakePod, audit_bind_journal

pytestmark = pytest.mark.scaleout


# ------------------------------------------------ ring / rebalance math


def test_ring_slot_stable_and_in_range():
    for ns in ("default", "team-a", "team-b", "", "ns-11"):
        s = ring_slot(ns)
        assert 0 <= s < RING_SLOTS
        assert s == ring_slot(ns), "hash must be stable"


def test_rebalance_even_split_and_deterministic():
    out = rebalance_slots([], ["a", "b", "c", "d"])
    assert len(out) == RING_SLOTS
    counts = {r: out.count(r) for r in "abcd"}
    assert all(c == RING_SLOTS // 4 for c in counts.values()), counts
    # deterministic: every replica proposes the same map from the same
    # inputs, so CAS racers collide on the epoch, not on divergent maps
    assert out == rebalance_slots([], ["d", "c", "b", "a"])


def test_rebalance_minimal_churn_on_join():
    base = rebalance_slots([], ["a"])
    after = rebalance_slots(base, ["a", "b"])
    # a keeps exactly its even share; only the overflow moved to b
    moved = sum(1 for i in range(RING_SLOTS) if base[i] != after[i])
    assert after.count("a") == after.count("b") == RING_SLOTS // 2
    assert moved == RING_SLOTS // 2, "join must move only the overflow"


def test_rebalance_reassigns_orphans_on_death():
    both = rebalance_slots(rebalance_slots([], ["a"]), ["a", "b"])
    after = rebalance_slots(both, ["a"])
    assert after.count("a") == RING_SLOTS
    # a's surviving slots never churned
    for i in range(RING_SLOTS):
        if both[i] == "a":
            assert after[i] == "a"


def test_rebalance_empty_live_keeps_map():
    cur = rebalance_slots([], ["a", "b"])
    assert rebalance_slots(cur, []) == cur


# ------------------------------------------------ slice board


def test_slice_board_register_ttl_and_cas():
    board = SliceBoard(ring_slots=8)
    reg = board.register("a", url="http://a", pid=1)
    assert reg["ring"] == {"epoch": 0, "slots": []}
    board.register("b")
    assert set(board.schedulers()) == {"a", "b"}
    assert set(board.live(ttl_s=60.0)) == {"a", "b"}
    assert board.live(ttl_s=0.0) in ({}, board.live(ttl_s=0.0))
    # CAS by epoch: stale expect loses, winner's map sticks
    assert board.set_ring({"epoch": 1, "slots": ["a"] * 8}, 0) is True
    assert board.set_ring({"epoch": 1, "slots": ["b"] * 8}, 0) is False
    assert board.ring() == {"epoch": 1, "slots": ["a"] * 8}
    board.unregister("b")
    assert set(board.schedulers()) == {"a"}


# ------------------------------------------------ slice manager lifecycle


def _tick(sm, hb=0.01):
    time.sleep(hb * 2)
    return sm.tick()


def test_single_manager_owns_everything():
    hub = Hub()
    sm = SliceManager(hub, "solo", heartbeat_s=0.01, ttl_s=5.0)
    assert sm.tick() is True
    assert sm.owned == frozenset(range(RING_SLOTS))
    assert sm.is_leader()
    assert sm.ring_epoch == 1
    assert sm.epoch >= 1, "fence lease must be stamped with the map"
    assert sm.owns_namespace("default") and sm.owns_namespace("x")
    hub.close()


def test_two_managers_split_fence_bumps_and_release_rehomes():
    hub = Hub()
    a = SliceManager(hub, "a", heartbeat_s=0.01, ttl_s=5.0)
    b = SliceManager(hub, "b", heartbeat_s=0.01, ttl_s=5.0)
    assert a.tick()
    fence1 = a.epoch
    assert _tick(b), "joiner rebalances in and owns its share"
    assert _tick(a), "incumbent adopts the new map"
    assert a.owned and b.owned and not (a.owned & b.owned)
    assert a.owned | b.owned == frozenset(range(RING_SLOTS))
    assert a.ring_epoch == b.ring_epoch == 2
    # each committed rebalance is exactly one holder change => one
    # fresh fencing epoch; re-applied syncs are no-ops
    assert a.epoch == b.epoch > fence1
    fence2 = a.epoch
    assert _tick(a) and a.epoch == fence2, "steady-state must not bump"
    # every namespace has exactly one owner
    for ns in ("default", "team-a", "ns-7", "zz"):
        assert a.owns_namespace(ns) != b.owns_namespace(ns)
    # graceful departure re-homes NOW (no TTL wait)
    b.release()
    assert not b.is_leader() and not b.owned
    assert _tick(a)
    assert a.owned == frozenset(range(RING_SLOTS))
    assert set(hub.fabric_schedulers()) == {"a"}
    hub.close()


class _CuttableHub:
    """Hub proxy whose fabric_* verbs can be severed (board outage)."""

    def __init__(self, hub):
        self._hub = hub
        self.broken = False

    def __getattr__(self, name):
        if self.broken and name.startswith("fabric_"):
            raise ConnectionError("board unreachable")
        return getattr(self._hub, name)


def test_manager_survives_blip_demotes_past_ttl():
    clock = {"t": 1000.0}
    hub = _CuttableHub(Hub())
    sm = SliceManager(hub, "a", heartbeat_s=1.0, ttl_s=5.0,
                      now=lambda: clock["t"])
    assert sm.tick() is True
    hub.broken = True
    clock["t"] += 2.0
    assert sm.tick() is True, "a blip inside the TTL keeps the slices"
    assert sm.transport_errors == 1
    clock["t"] += 10.0
    assert sm.tick() is False, "past the TTL peers re-homed our slices"
    assert not sm.is_leader()
    hub._hub.close()


def test_deposed_map_loses_the_fence():
    hub = Hub()
    hub.create_node(MakeNode().name("n").label(LABEL_HOSTNAME, "n")
                    .capacity(cpu="8", memory="16Gi", pods="110").obj())
    a = SliceManager(hub, "a", heartbeat_s=0.01, ttl_s=5.0)
    b = SliceManager(hub, "b", heartbeat_s=0.01, ttl_s=5.0)
    assert a.tick()
    stale = a.epoch              # fence as of the single-replica map
    assert _tick(b) and _tick(a)  # rebalance bumped the fence
    pod = MakePod().name("p").req(cpu="100m").obj()
    hub.create_pod(pod)
    with pytest.raises(Fenced):
        hub.bind(pod, "n", stale, SCHED_SLICE_LEASE)
    assert hub.get_pod(pod.metadata.uid).spec.node_name == "", \
        "a bind from a deposed slice map must not land"
    hub.bind(pod, "n", a.epoch, a.lease_name)
    assert hub.get_pod(pod.metadata.uid).spec.node_name == "n"
    with pytest.raises(Conflict):
        hub.bind(pod, "n", b.epoch, b.lease_name)  # bind-once holds
    hub.close()


# ------------------------------------------------ partition filters


def test_gang_routes_by_group_namespace_never_splits():
    hub = Hub()
    cfg = default_config()
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=32))

    class _Slices:
        is_slice_manager = True

        def owns_namespace(self, ns):
            return ns == "mine"

    sched._slices = _Slices()
    solo = MakePod().name("solo").namespace("mine").obj()
    foreign = MakePod().name("f").namespace("theirs").obj()
    member = MakePod().name("m0").namespace("mine").obj()
    member.metadata.labels[LABEL_POD_GROUP] = "g1"
    assert pod_group_key(member) == "mine/g1"
    assert sched._owns_pod(solo) is True
    assert sched._owns_pod(foreign) is False
    # the gang member routes by its GROUP's namespace — every member
    # of mine/g1 lands on the same replica, whatever else changes
    assert sched._owns_pod(member) is True
    sched.close()
    hub.close()


def test_queue_drain_unowned_sweeps_every_pool():
    def pre(pod):
        if pod.metadata.name.startswith("gate"):
            return Status.unschedulable("gated", plugin="G",
                                        resolvable=False)
        return Status()

    q = PriorityQueue(less_fn=lambda a, b: a.timestamp < b.timestamp,
                      pre_enqueue=pre)

    def mk(name, ns):
        return MakePod().name(name).namespace(ns).uid(name).obj()

    unsched = mk("u", "foreign")
    q.add(unsched)
    qp = q.pop()
    qp.unschedulable_plugins = {"X"}
    q.add_unschedulable_if_not_present(qp)
    back = mk("bk", "foreign")
    q.add(back)
    qp = q.pop()
    qp.consecutive_errors_count = 1
    q.add_unschedulable_if_not_present(qp)       # error-class -> backoff
    inflight = mk("infl", "foreign")
    q.add(inflight)
    assert q.pop().uid == "infl"                 # stays in flight
    q.add(mk("act", "foreign"))
    q.add(mk("keep", "default"))
    q.add(mk("gate", "foreign"))

    drained = {p.metadata.name
               for p in q.drain_unowned(
                   lambda p: p.metadata.namespace == "default")}
    # every pool swept; in-flight left to finish and fence at bind
    assert drained == {"u", "bk", "act", "gate"}, drained
    counts = q.pending_counts()
    assert counts["active"] == 1 and counts["gated"] == 0
    assert counts["backoff"] == 0 and counts["unschedulable"] == 0


def test_jobqueue_drain_unowned_rehomes_whole_unit():
    jq = JobQueue()

    def gpod(name, ns, gang=None, tenant="t"):
        p = MakePod().name(name).namespace(ns).uid(name).obj()
        p.metadata.labels[LABEL_QUEUE] = tenant
        if gang:
            p.metadata.labels[LABEL_POD_GROUP] = gang
        return p

    for i in range(3):
        jq.add(gpod(f"g-{i}", "mlns", gang="train"))
    jq.add(gpod("keep", "default"))
    assert len(jq) == 4
    drained = jq.drain_unowned(
        lambda p: p.metadata.namespace == "default")
    # the unit moves WHOLE — members never split across replicas
    assert {p.metadata.name for p in drained} == {"g-0", "g-1", "g-2"}
    assert len(jq) == 1 and jq.holds("keep")
    assert jq.drain_unowned(lambda p: True) == []


# ------------------------------------------------ journal bind audit


def test_audit_clean_journal_passes():
    hub = Hub()
    hub.create_node(MakeNode().name("n").label(LABEL_HOSTNAME, "n")
                    .capacity(cpu="8", memory="16Gi", pods="110").obj())
    uids = []
    for i in range(3):
        p = MakePod().name(f"p{i}").req(cpu="100m").obj()
        hub.create_pod(p)
        uids.append(p.metadata.uid)
        hub.bind(p, "n")
    report = audit_bind_journal(hub=hub, expected_uids=uids)
    assert report["ok"], report
    assert report["binds"] == 3 and not report["lost"]
    hub.close()


def _row(rv, uid, node, ctype="update"):
    return {"rv": rv, "kind": "pods", "type": ctype,
            "obj": {"metadata": {"uid": uid},
                    "spec": {"node_name": node}}}


def test_audit_flags_rebound_lost_and_too_old():
    rebound = audit_bind_journal(changes=[
        _row(1, "u1", ""), _row(2, "u1", "n1"), _row(3, "u1", "n2")])
    assert not rebound["ok"]
    assert rebound["double_binds"][0]["violation"] == "rebound"
    assert rebound["double_binds"][0]["second_node"] == "n2"

    unbound = audit_bind_journal(changes=[
        _row(1, "u1", "n1"), _row(2, "u1", "")])
    assert [v["violation"] for v in unbound["double_binds"]] == ["unbound"]

    lost = audit_bind_journal(changes=[_row(1, "u1", "n1")],
                              expected_uids=["u1", "u2"])
    assert lost["lost"] == ["u2"] and not lost["ok"]

    ok = audit_bind_journal(changes=[
        _row(1, "u1", "n1"), _row(2, "u1", "n1"),   # same-node re-apply
        _row(3, "u1", "", "delete")])
    assert ok["ok"] and ok["binds"] == 1

    compacted = audit_bind_journal(
        changes={"too_old": True, "rv": 9, "changes": [_row(9, "u", "n")]})
    assert compacted["too_old"] and not compacted["ok"]


# ------------------------------------------------ replicated sched ring


FAST = {"heartbeat_s": 0.05, "election_timeout_s": (0.25, 0.5)}


def test_sched_ring_survives_leader_failover(tmp_path):
    names = ["state-0", "state-1", "state-2"]
    replicas, servers = {}, {}
    for n in names:
        replicas[n] = StateReplica(n, pod_shards=["pods-0"],
                                   wal_path=str(tmp_path / f"{n}.wal"),
                                   **FAST)
        servers[n] = HubServer(replicas[n])
    peer_map = {n: servers[n].address for n in names}
    for n in names:
        replicas[n].set_peers(peer_map)
        servers[n].start()
    for n in names:
        replicas[n].start()

    def leader(alive):
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            for n in alive:
                if replicas[n].fabric_replica_status()["role"] == "leader":
                    return n
            time.sleep(0.05)
        raise AssertionError("no leader elected")

    try:
        first = leader(names)
        hub = RemoteHub(peer_map[first], timeout=5.0)
        try:
            reg = hub.fabric_register_scheduler("sched-a", "", 1)
            assert reg["ring"]["epoch"] == 0
            want = {"epoch": 1, "slots": ["sched-a"] * RING_SLOTS}
            assert hub.fabric_set_sched_ring(want, 0)
            assert not hub.fabric_set_sched_ring(
                {"epoch": 1, "slots": ["x"] * RING_SLOTS}, 0), \
                "the CAS must go through the log exactly once"
            assert hub.fabric_sched_ring() == want
        finally:
            hub.close()
        # kill -9 the leader: the ring is LOGGED state and must survive
        servers[first].stop()
        replicas[first].close()
        rest = [n for n in names if n != first]
        second = leader(rest)
        hub2 = RemoteHub(peer_map[second], timeout=5.0)
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                try:
                    if hub2.fabric_sched_ring() == want:
                        break
                except Exception:  # noqa: BLE001 — election settling
                    pass
                time.sleep(0.05)
            assert hub2.fabric_sched_ring() == want
            # the registry is soft state: gossiped, not logged — it may
            # or may not survive, but reads must serve
            assert isinstance(hub2.fabric_schedulers(), dict)
        finally:
            hub2.close()
    finally:
        for n in names:
            try:
                servers[n].stop()
            except Exception:  # noqa: BLE001 — already stopped
                pass
            try:
                replicas[n].close()
            except Exception:  # noqa: BLE001
                pass


# ------------------------------------------------ two-replica drain


def test_two_replicas_partition_and_bind_everything():
    hub = Hub()
    hub.create_node(MakeNode().name("n").label(LABEL_HOSTNAME, "n")
                    .capacity(cpu="64", memory="256Gi", pods="220").obj())
    cfg = default_config()
    cfg.batch_size = 8
    sm_a = SliceManager(hub, "sched-a", heartbeat_s=0.01, ttl_s=5.0)
    sm_b = SliceManager(hub, "sched-b", heartbeat_s=0.01, ttl_s=5.0)
    assert sm_a.tick() and _tick(sm_b) and _tick(sm_a)
    slots = hub.fabric_sched_ring()["slots"]
    ns_a = [ns for ns in (f"ns{i}" for i in range(64))
            if slots[ring_slot(ns, len(slots))] == "sched-a"][:4]
    ns_b = [ns for ns in (f"ns{i}" for i in range(64))
            if slots[ring_slot(ns, len(slots))] == "sched-b"][:4]
    assert len(ns_a) == 4 and len(ns_b) == 4

    sa = Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=256))
    sb = Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=256))
    sa.start(elector=sm_a)
    sb.start(elector=sm_b)
    uids = []
    try:
        for i in range(24):
            ns = (ns_a + ns_b)[i % 8]
            p = (MakePod().name(f"p{i}").namespace(ns)
                 .req(cpu="50m").obj())
            hub.create_pod(p)
            uids.append(p.metadata.uid)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            bound = sum(1 for u in uids
                        if hub.get_pod(u).spec.node_name)
            if bound == len(uids):
                break
            time.sleep(0.05)
        assert bound == len(uids), f"only {bound}/{len(uids)} bound"
        report = audit_bind_journal(hub=hub, expected_uids=uids)
        assert report["ok"], report
        # both replicas actually drained their own slices, and each
        # penned the other's pods instead of scheduling them (the
        # counters lag the hub commit by one result-drain, so poll)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if sa.stats["scheduled"] + sb.stats["scheduled"] == len(uids):
                break
            time.sleep(0.05)
        assert sa.stats["scheduled"] > 0 and sb.stats["scheduled"] > 0
        assert sa.stats["scheduled"] + sb.stats["scheduled"] == len(uids)
        assert sa.stats["foreign_stashed"] > 0
        assert sb.stats["foreign_stashed"] > 0
    finally:
        sa.stop()
        sb.stop()
        sa.close()
        sb.close()
        hub.close()


def test_undo_commit_survives_foreign_confirm_race():
    """Regression for the scaleout-storm flake: a sibling replica wins
    a post-rebalance race — its bind lands through our informer
    (add_pod replaces our ASSUMED entry with confirmed truth) while
    our own bind attempt is failing with Conflict. The failure path's
    forget_pod would raise KeyError("confirmed, cannot forget"); the
    guard must instead drop our claim and retire the pod unrequeued —
    the pod is placed, and it is the sibling's."""
    from kubernetes_tpu.backend.queue import QueuedPodInfo
    from kubernetes_tpu.framework.cycle_state import CycleState

    hub = Hub()
    hub.create_node(MakeNode().name("n1").capacity(cpu="64").obj())
    hub.create_node(MakeNode().name("n2").capacity(cpu="64").obj())
    cfg = default_config()
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=64))
    try:
        # the pod stays off the hub: creating it there would have the
        # informer enqueue it, muddying the requeue assertion below
        pod = MakePod().name("racy").req(cpu="100m").obj()
        assumed = pod.clone()
        assumed.spec.node_name = "n1"
        sched.cache.assume_pod(assumed)
        # the sibling's bind arrives via the informer: truth wins,
        # the assumed entry becomes a CONFIRMED placement on n2
        foreign = pod.clone()
        foreign.spec.node_name = "n2"
        sched.cache.add_pod(foreign)
        assert not sched.cache.is_assumed_pod(assumed)
        assert sched.cache.get_pod(assumed) is not None
        # now our own bind answers Conflict and unwinds — this raised
        # KeyError("confirmed, cannot forget") before the guard
        qp = QueuedPodInfo(pod=pod)
        sched._undo_commit(qp, CycleState(), assumed, "n1",
                           "bind failed: Conflict")
        # the foreign placement survived untouched, and the pod was
        # NOT requeued for a re-schedule of an already-bound pod
        assert sched.cache.get_pod(assumed).spec.node_name == "n2"
        assert sched.queue.pop_batch(8) == []
        # the timeline tells the story: this pod's /debug/pod (and any
        # autopsy bundle) shows WHO bound it, not a silent drop
        tl = sched.timelines.get(uid=pod.metadata.uid)
        evs = [e for e in tl["events"] if e["event"] == "foreign_bound"]
        assert len(evs) == 1
        assert "n2" in evs[0]["detail"]
        assert "undo-commit" in evs[0]["detail"]
    finally:
        sched.close()
        hub.close()


def test_commit_drops_attempt_when_foreign_bind_confirmed_first():
    """The commit-side half of the same race: the sibling's bind
    confirms through our informer BETWEEN the pop and _commit.
    assume_pod would raise KeyError("already in cache") — which took
    whole device batches down the host-fallback ladder in the storm —
    so _commit must drop the attempt instead of assuming."""
    from kubernetes_tpu.backend.queue import QueuedPodInfo

    hub = Hub()
    hub.create_node(MakeNode().name("n1").capacity(cpu="64").obj())
    hub.create_node(MakeNode().name("n2").capacity(cpu="64").obj())
    cfg = default_config()
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=64))
    try:
        pod = MakePod().name("racy2").req(cpu="100m").obj()
        foreign = pod.clone()
        foreign.spec.node_name = "n2"
        sched.cache.add_pod(foreign)       # sibling's confirmed bind
        qp = QueuedPodInfo(pod=pod)
        sched._commit(qp, "n1")            # raised KeyError before
        # no assumed state leaked, no binder-pool work was enqueued
        assumed = pod.clone()
        assumed.spec.node_name = "n1"
        assert not sched.cache.is_assumed_pod(assumed)
        assert sched.cache.get_pod(foreign).spec.node_name == "n2"
        assert sched.queue.pop_batch(8) == []
        # the pre-commit drop stamps the same foreign_bound story
        tl = sched.timelines.get(uid=pod.metadata.uid)
        evs = [e for e in tl["events"] if e["event"] == "foreign_bound"]
        assert len(evs) == 1
        assert "n2" in evs[0]["detail"]
        assert "pre-commit" in evs[0]["detail"]
    finally:
        sched.close()
        hub.close()


# ------------------------------------------------ the kill -9 storm


@pytest.mark.slow
def test_scaleout_storm_kill9_mid_wave():
    from kubernetes_tpu.chaos import run_scaleout_storm

    report = run_scaleout_storm(pods=120, nodes=8, replicas=3,
                                timeout_s=180.0)
    assert report["ok"], report
