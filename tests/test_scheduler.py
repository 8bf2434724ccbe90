"""End-to-end: Scheduler + in-process Hub (the rung-2 integration tests of
SURVEY.md §4 — real loop, real queue/cache/mirror, fake API hub; asserts on
bindings and conditions exactly like test/integration/scheduler)."""

import numpy as np

from kubernetes_tpu.api.objects import (
    Affinity,
    Container,
    LABEL_HOSTNAME,
    LABEL_ZONE,
    LabelSelector,
    Node,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodAffinityTerm,
    PodAntiAffinity,
    PodSchedulingGate,
    PodSpec,
    ResourceRequirements,
    Taint,
    TopologySpreadConstraint,
)
from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import Scheduler


class Clock:
    def __init__(self):
        self.t = 1000.0

    def now(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def mknode(i, zone="z1", cpu="16", taints=None):
    name = f"node-{i}"
    return Node(metadata=ObjectMeta(name=name, labels={
        LABEL_HOSTNAME: name, LABEL_ZONE: zone}),
        spec=NodeSpec(taints=taints or []),
        status=NodeStatus(allocatable={"cpu": cpu, "memory": "32Gi",
                                       "pods": "110"}))


def mkpod(name, cpu="500m", labels=None, affinity=None, tsc=None, gates=None):
    return Pod(metadata=ObjectMeta(name=name, labels=labels or {}),
               spec=PodSpec(
                   containers=[Container(name="c",
                                         resources=ResourceRequirements(
                                             requests={"cpu": cpu,
                                                       "memory": "256Mi"}))],
                   affinity=affinity,
                   topology_spread_constraints=tsc or [],
                   scheduling_gates=gates or []))


def mksched(hub, clock=None, batch=16):
    cfg = default_config()
    cfg.batch_size = batch
    clock = clock or Clock()
    return Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64),
                     now=clock.now), clock


def bound_node(hub, pod):
    return hub.get_pod(pod.metadata.uid).spec.node_name


def test_end_to_end_basic():
    hub = Hub()
    sched, _ = mksched(hub)
    for i in range(4):
        hub.create_node(mknode(i))
    pods = [mkpod(f"p{i}") for i in range(10)]
    for p in pods:
        hub.create_pod(p)
    sched.run_until_idle()
    assert sched.stats["scheduled"] == 10
    nodes = {bound_node(hub, p) for p in pods}
    assert all(n for n in nodes)
    # cache confirmed all bindings (no assumed leftovers)
    assert sched.cache.assumed_pod_count() == 0
    assert sched.cache.pod_count() == 10


def test_unschedulable_then_node_add_requeues():
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="1"))
    big = mkpod("big", cpu="8")
    hub.create_pod(big)
    sched.run_until_idle()
    assert sched.stats["unschedulable"] == 1
    assert bound_node(hub, big) == ""
    cond = hub.get_pod(big.metadata.uid).status.conditions[0]
    assert cond.reason == "Unschedulable"
    assert "NodeResourcesFit" in cond.message
    # a big node appears: the registered NodeResourcesFit event requeues
    hub.create_node(mknode(1, cpu="16"))
    clock.tick(2.0)  # clear backoff
    sched.queue.flush_backoff_completed()
    sched.run_until_idle()
    assert bound_node(hub, big) == "node-1"


def test_tainted_cluster_toleration():
    hub = Hub()
    sched, _ = mksched(hub)
    hub.create_node(mknode(0, taints=[Taint("dedicated", "infra",
                                            "NoSchedule")]))
    hub.create_node(mknode(1))
    p = mkpod("p")
    hub.create_pod(p)
    sched.run_until_idle()
    assert bound_node(hub, p) == "node-1"


def test_zone_anti_affinity_e2e():
    hub = Hub()
    sched, _ = mksched(hub)
    hub.create_node(mknode(0, zone="east"))
    hub.create_node(mknode(1, zone="east"))
    hub.create_node(mknode(2, zone="west"))
    anti = Affinity(pod_anti_affinity=PodAntiAffinity(required=[
        PodAffinityTerm(topology_key=LABEL_ZONE,
                        label_selector=LabelSelector(
                            match_labels={"app": "web"}))]))
    pods = [mkpod(f"w{i}", labels={"app": "web"}, affinity=anti)
            for i in range(3)]
    for p in pods:
        hub.create_pod(p)
    sched.run_until_idle()
    zones = {"node-0": "east", "node-1": "east", "node-2": "west"}
    placed = [bound_node(hub, p) for p in pods]
    ok = [n for n in placed if n]
    assert len(ok) == 2, "two zones -> only two such pods can run"
    assert {zones[n] for n in ok} == {"east", "west"}
    assert sched.stats["unschedulable"] >= 1


def test_spread_e2e():
    hub = Hub()
    sched, _ = mksched(hub)
    for i in range(3):
        hub.create_node(mknode(i))
    tsc = [TopologySpreadConstraint(
        max_skew=1, topology_key=LABEL_HOSTNAME,
        when_unsatisfiable="DoNotSchedule",
        label_selector=LabelSelector(match_labels={"app": "s"}))]
    pods = [mkpod(f"s{i}", labels={"app": "s"}, tsc=tsc) for i in range(3)]
    for p in pods:
        hub.create_pod(p)
    sched.run_until_idle()
    assert sorted(bound_node(hub, p) for p in pods) == [
        "node-0", "node-1", "node-2"]


def test_gated_pod_waits_for_gate_removal():
    hub = Hub()
    sched, _ = mksched(hub)
    hub.create_node(mknode(0))
    gated = mkpod("g", gates=[PodSchedulingGate("corp/hold")])
    hub.create_pod(gated)
    sched.run_until_idle()
    assert bound_node(hub, gated) == ""
    assert sched.queue.pending_counts()["gated"] == 1
    # remove the gate via pod update
    new = hub.get_pod(gated.metadata.uid).clone()
    new.spec.scheduling_gates = []
    hub.update_pod(new)
    sched.run_until_idle()
    assert bound_node(hub, gated) == "node-0"


def test_capacity_rebucket_grows_nodes():
    hub = Hub()
    sched, _ = mksched(hub)
    for i in range(20):  # exceeds the 16-node bucket
        hub.create_node(mknode(i))
    pods = [mkpod(f"p{i}") for i in range(30)]
    for p in pods:
        hub.create_pod(p)
    sched.run_until_idle()
    assert sched.stats["scheduled"] == 30
    assert sched.caps.nodes >= 20


def test_node_deleted_while_pods_pending():
    hub = Hub()
    sched, clock = mksched(hub)
    n = mknode(0)
    hub.create_node(n)
    p = mkpod("p")
    hub.create_pod(p)
    sched.run_until_idle()
    assert bound_node(hub, p) == "node-0"
    # delete the node; a new pod must go unschedulable
    hub.delete_node(n.metadata.uid)
    p2 = mkpod("p2")
    hub.create_pod(p2)
    sched.run_until_idle()
    assert bound_node(hub, p2) == ""
    assert sched.stats["unschedulable"] >= 1


def test_spread_drain_places_the_same_with_the_row_cache_cold_or_warm(
        monkeypatch):
    """A drain of zone-spreading pods binds the same pods to the same
    nodes whether the mirror's packed-row cache is cleared before every
    batch or left warm (ISSUE 29: a hit is the slow path's row), and the
    required maxSkew holds at the end."""
    from kubernetes_tpu.backend.mirror import Mirror

    zones = ["z1", "z2", "z3"]
    prepare = Mirror.prepare_launch

    def cold_prepare(mirror, pods, batch_size):
        mirror._pod_rows.clear()
        return prepare(mirror, pods, batch_size)

    def drain(cold):
        monkeypatch.setattr(Mirror, "prepare_launch",
                            cold_prepare if cold else prepare)
        hub = Hub()
        cfg = default_config()
        cfg.batch_size = 8
        cfg.tie_break_seed = 29
        sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=128))
        try:
            for i in range(12):
                hub.create_node(mknode(i, zone=zones[i % 3]))
            tsc = [TopologySpreadConstraint(
                max_skew=1, topology_key=LABEL_ZONE,
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": "web"}))]
            for i in range(60):
                hub.create_pod(mkpod(f"p{i}", cpu="100m",
                                     labels={"app": "web"}, tsc=tsc))
            sched.run_until_idle()
            return ({p.metadata.name: p.spec.node_name
                     for p in hub.list_pods()},
                    sched.mirror.row_cache_stats())
        finally:
            sched.close()

    warm, warm_stats = drain(cold=False)
    cold, cold_stats = drain(cold=True)
    assert warm == cold
    assert all(warm.values()) and len(warm) == 60
    per_zone = [sum(1 for n in warm.values()
                    if zones[int(n.split("-")[1]) % 3] == z) for z in zones]
    assert max(per_zone) - min(per_zone) <= 1
    # warm: one miss a field set; cold: one a batch at the least
    assert warm_stats["bypass"] == cold_stats["bypass"] == 0
    assert warm_stats["misses"] < 8 <= cold_stats["misses"]
    assert warm_stats["hits"] > cold_stats["hits"] > 0


# suite-tier discipline (tests/test_markers.py): area marker
import pytest  # noqa: E402
pytestmark = pytest.mark.core
