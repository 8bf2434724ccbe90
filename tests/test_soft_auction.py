"""ISSUE 15: soft-topology auction, daemonset pin fast path, batched
eviction waves, bucket hysteresis, and the device-dead preemption rung.

The differential discipline mirrors tests/test_dra_fuzz.py: the device
soft-score terms are pinned against (a) a plain-python host oracle of the
static (table) halves and (b) the serial commit scan — whose own parity
with the reference semantics tests/test_oracle.py already pins — over
randomized pods/nodes/tables.
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

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
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    PodSpec,
    ResourceRequirements,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)
from kubernetes_tpu.api.labels import label_selector_matches
from kubernetes_tpu.backend.cache import Cache
from kubernetes_tpu.backend.mirror import Mirror
from kubernetes_tpu.backend.snapshot import Snapshot
from kubernetes_tpu.models.pipeline import (
    default_weights,
    launch_batch,
)
from kubernetes_tpu.ops.features import Capacities

pytestmark = pytest.mark.core

CAPS = Capacities(nodes=32, pods=512)
WEIGHTS = default_weights()


def mknode(i, zones=3):
    name = f"node-{i}"
    return Node(
        metadata=ObjectMeta(name=name, labels={
            LABEL_HOSTNAME: name, LABEL_ZONE: f"z{i % zones}"}),
        spec=NodeSpec(),
        status=NodeStatus(allocatable={
            "cpu": "8", "memory": "16Gi", "pods": "110"}))


def soft_pod(name, rng, ns="default"):
    """A pod whose ONLY topology work is soft: preferred (anti)affinity
    and/or a ScheduleAnyway spread constraint."""
    labels = {"app": f"a{rng.randrange(3)}"}
    sel = LabelSelector(match_labels={"app": f"a{rng.randrange(3)}"})
    key = rng.choice([LABEL_HOSTNAME, LABEL_ZONE])
    kind = rng.random()
    aff = None
    tsc = []
    if kind < 0.35:
        aff = Affinity(pod_affinity=PodAffinity(preferred=[
            WeightedPodAffinityTerm(
                weight=rng.choice([1, 5, 10, 50]),
                pod_affinity_term=PodAffinityTerm(
                    topology_key=key, label_selector=sel))]))
    elif kind < 0.7:
        aff = Affinity(pod_anti_affinity=PodAntiAffinity(preferred=[
            WeightedPodAffinityTerm(
                weight=rng.choice([1, 5, 10, 50]),
                pod_affinity_term=PodAffinityTerm(
                    topology_key=key, label_selector=sel))]))
    else:
        tsc = [TopologySpreadConstraint(
            max_skew=rng.choice([1, 3, 5]), topology_key=key,
            when_unsatisfiable="ScheduleAnyway", label_selector=sel)]
    return Pod(
        metadata=ObjectMeta(name=name, labels=labels, namespace=ns),
        spec=PodSpec(
            containers=[Container(name="c", resources=ResourceRequirements(
                requests={"cpu": "100m", "memory": "200Mi"}))],
            affinity=aff, topology_spread_constraints=tsc))


def build(rng, n_nodes=12, n_table=8):
    cache, snap, m = Cache(), Snapshot(), Mirror(caps=CAPS)
    for i in range(n_nodes):
        cache.add_node(mknode(i))
    table = []
    for i in range(n_table):
        p = soft_pod(f"bound-{i}", rng)
        p.metadata.uid = f"bound-{i}"
        p.spec.node_name = f"node-{rng.randrange(n_nodes)}"
        cache.add_pod(p)
        table.append(p)
    cache.update_snapshot(snap)
    m.sync(snap)
    return table, snap, m


def host_ipa_static(pod, table_pods, node_zone_of, n_nodes):
    """Plain-python oracle of the TABLE half of the preferred IPA score
    (scoring.go processExistingPod, soft directions + existing preferred
    both kinds; no required terms exist in the soft-only fuzz)."""
    scores = np.zeros(n_nodes)

    def dom_nodes(key, value):
        if key == LABEL_HOSTNAME:
            return [int(value.split("-")[1])]
        return [n for n in range(n_nodes) if node_zone_of(n) == value]

    def terms(p, kind):
        a = p.spec.affinity
        if a is None:
            return []
        grp = a.pod_affinity if kind == "aff" else a.pod_anti_affinity
        return grp.preferred if grp is not None else []

    for tp in table_pods:
        node_i = int(tp.spec.node_name.split("-")[1])
        # incoming pod's preferred terms vs table pod tp
        for sign, kind in ((1.0, "aff"), (-1.0, "anti")):
            for w in terms(pod, kind):
                t = w.pod_affinity_term
                if tp.metadata.namespace != pod.metadata.namespace:
                    continue
                if not label_selector_matches(t.label_selector,
                                              tp.metadata.labels):
                    continue
                key = t.topology_key
                val = (tp.spec.node_name if key == LABEL_HOSTNAME
                       else f"z{node_i % 3}")
                for n in dom_nodes(key, val):
                    scores[n] += sign * w.weight
        # table pod tp's preferred terms vs the incoming pod
        for sign, kind in ((1.0, "aff"), (-1.0, "anti")):
            for w in terms(tp, kind):
                t = w.pod_affinity_term
                if tp.metadata.namespace != pod.metadata.namespace:
                    continue
                if not label_selector_matches(t.label_selector,
                                              pod.metadata.labels):
                    continue
                key = t.topology_key
                val = (tp.spec.node_name if key == LABEL_HOSTNAME
                       else f"z{node_i % 3}")
                for n in dom_nodes(key, val):
                    scores[n] += sign * w.weight
    return scores


SEEDS_T1 = range(8)
SEEDS_SLOW = range(8, 40)


@pytest.mark.parametrize("seed", SEEDS_T1)
def test_soft_static_ipa_matches_host_oracle(seed):
    """The _soft_statics table half == the python oracle, per node."""
    import jax

    import kubernetes_tpu.models.pipeline as P
    from kubernetes_tpu.ops.features import unpack_cluster, unpack_pods

    rng = random.Random(seed)
    table_pods, snap, m = build(rng)
    pods = [soft_pod(f"p-{i}", rng) for i in range(6)]
    for i, p in enumerate(pods):
        p.metadata.uid = f"p-{i}"
    spec = m.prepare_launch(pods, 8)
    assert spec.topo_soft
    ct = unpack_cluster(spec.cblobs, CAPS)
    pf = unpack_pods(spec.pblobs, CAPS, spec.pfields, spec.ptmpl)
    pods_rep = jax.tree.map(lambda x: x[spec.rep], pf)
    soft = P._soft_statics(
        ct, spec.cblobs.pods_i32, CAPS, pf, pods_rep, spec.gid, spec.g_cap,
        spec.d_cap, m.well_known(), (True,) * P.NUM_FILTER_PLUGINS,
        frozenset(P.ALL_FEATURES), True,
        lambda fn, tree, n: jax.vmap(fn)(tree))
    ipa_raw = np.asarray(soft.ipa_raw_g)
    gid = np.asarray(spec.gid)
    for b, pod in enumerate(pods):
        want = host_ipa_static(pod, table_pods,
                               lambda n: f"z{n % 3}", 12)
        got = ipa_raw[gid[b]]
        # mirror rows are allocated in node order for this build
        rows = [m.row_of(f"node-{n}") for n in range(12)]
        np.testing.assert_allclose(got[rows], want, atol=1e-4,
                                   err_msg=f"pod {b} seed {seed}")


def _compare_single_pod(seed):
    """B=1 batches: the auction IS as-if-serial, so soft-auction and
    serial-scan placements + winning scores must agree exactly."""
    rng = random.Random(seed)
    _table, snap, m = build(rng)
    pod = soft_pod("solo", rng)
    pod.metadata.uid = "solo"
    spec = m.prepare_launch([pod], 2)
    assert spec.topo_soft
    out_s = launch_batch(spec, m.well_known(), WEIGHTS, CAPS,
                         serial_scan=True)
    out_a = launch_batch(spec, m.well_known(), WEIGHTS, CAPS,
                         serial_scan=False)
    rs, ra = int(out_s.node_row[0]), int(out_a.node_row[0])
    assert rs == ra, (seed, rs, ra)
    np.testing.assert_allclose(float(out_s.score[0]),
                               float(out_a.score[0]), atol=1e-3)


@pytest.mark.parametrize("seed", SEEDS_T1)
def test_soft_auction_single_pod_parity(seed):
    _compare_single_pod(seed)


@pytest.mark.slow
@pytest.mark.parametrize("seed", SEEDS_SLOW)
def test_soft_auction_single_pod_parity_slow(seed):
    _compare_single_pod(seed)


@pytest.mark.parametrize("seed", SEEDS_T1)
def test_soft_auction_batch_places_everything(seed):
    """Multi-pod soft batches: every pod places, scores carry the soft
    terms (no NaN guard trips), and in-batch paff attraction shows up —
    colocation-seeking pods land in fewer distinct zones than spreading
    pods."""
    rng = random.Random(seed)
    _table, snap, m = build(rng)
    pods = [soft_pod(f"p-{i}", rng) for i in range(8)]
    for i, p in enumerate(pods):
        p.metadata.uid = f"p-{i}"
    spec = m.prepare_launch(pods, 8)
    out = launch_batch(spec, m.well_known(), WEIGHTS, CAPS,
                       serial_scan=False)
    rows = np.asarray(out.node_row)[:8]
    assert (rows >= 0).all()
    assert int(out.guard) == 0


def test_soft_auction_inbatch_affinity_colocates():
    """Strong preferred affinity toward existing matching pods PLUS the
    in-batch delta: the batch must colocate into the seeded zone. (A
    fully cold identical batch may scatter in round 1 — the auction
    scores against round-start state, its documented approximation; the
    realistic warm-table shape is what the preferred-band workloads
    run.)"""
    cache, snap, m = Cache(), Snapshot(), Mirror(caps=CAPS)
    for i in range(12):
        cache.add_node(mknode(i))
    term = WeightedPodAffinityTerm(weight=100, pod_affinity_term=(
        PodAffinityTerm(topology_key=LABEL_ZONE,
                        label_selector=LabelSelector(
                            match_labels={"team": "x"}))))

    def co_pod(name, bound_to=None):
        p = Pod(metadata=ObjectMeta(name=name, uid=name,
                                    labels={"team": "x"}),
                spec=PodSpec(
                    containers=[Container(
                        name="c", resources=ResourceRequirements(
                            requests={"cpu": "100m"}))],
                    affinity=Affinity(pod_affinity=PodAffinity(
                        preferred=[term]))))
        if bound_to:
            p.spec.node_name = bound_to
        return p

    # two matching pods already bound in zone z0 (nodes 0 and 3)
    cache.add_pod(co_pod("seed-0", "node-0"))
    cache.add_pod(co_pod("seed-1", "node-3"))
    cache.update_snapshot(snap)
    m.sync(snap)
    pods = [co_pod(f"co-{i}") for i in range(6)]
    spec = m.prepare_launch(pods, 8)
    assert spec.topo_soft
    out = launch_batch(spec, m.well_known(), WEIGHTS, CAPS,
                       serial_scan=False)
    rows = np.asarray(out.node_row)[:6]
    assert (rows >= 0).all()
    zones = [int(r) % 3 for r in rows]
    assert zones == [0] * 6, f"batch left the seeded zone: {zones}"


def test_required_terms_keep_serial_scan():
    """A batch with ANY required term is not soft-only."""
    m = Mirror(caps=CAPS)
    hard = Pod(metadata=ObjectMeta(name="h", uid="h",
                                   labels={"a": "b"}),
               spec=PodSpec(
                   containers=[Container(name="c")],
                   affinity=Affinity(pod_anti_affinity=PodAntiAffinity(
                       required=[PodAffinityTerm(
                           topology_key=LABEL_HOSTNAME,
                           label_selector=LabelSelector(
                               match_labels={"a": "b"}))]))))
    soft = Pod(metadata=ObjectMeta(name="s", uid="s"),
               spec=PodSpec(
                   containers=[Container(name="c")],
                   topology_spread_constraints=[TopologySpreadConstraint(
                       max_skew=1, topology_key=LABEL_ZONE,
                       when_unsatisfiable="ScheduleAnyway",
                       label_selector=LabelSelector(
                           match_labels={"a": "b"}))]))
    assert not m.batch_topology_soft_only([hard, soft])
    assert m.batch_topology_soft_only([soft])
    hard_tsc = Pod(metadata=ObjectMeta(name="t", uid="t"),
                   spec=PodSpec(
                       containers=[Container(name="c")],
                       topology_spread_constraints=[
                           TopologySpreadConstraint(
                               max_skew=1, topology_key=LABEL_ZONE,
                               when_unsatisfiable="DoNotSchedule",
                               label_selector=LabelSelector(
                                   match_labels={"a": "b"}))]))
    assert not m.batch_topology_soft_only([hard_tsc])


# ---------------------------- daemonset pin ----------------------------


def test_daemonset_pin_feature_and_placement():
    from kubernetes_tpu.perf.workloads import _daemonset_pod, _node

    cache, snap, m = Cache(), Snapshot(), Mirror(caps=CAPS)
    for i in range(16):
        cache.add_node(_node(i))
    cache.update_snapshot(snap)
    m.sync(snap)
    pods = [_daemonset_pod(i) for i in range(8)]
    spec = m.prepare_launch(pods, 8)
    assert spec.active == ("nodeaffinity_pin",)
    assert "aff_pin" in spec.pfields
    assert "sel_col" not in spec.pfields       # the selector kernels are out
    out = launch_batch(spec, m.well_known(), WEIGHTS, CAPS,
                       serial_scan=False)
    names = [m.name_of_row(int(r)) for r in np.asarray(out.node_row)[:8]]
    assert names == [f"node-{i}" for i in range(8)]


def test_pin_mixed_with_general_affinity_stays_full():
    """A batch mixing pins with a general selector keeps the full
    kernels — and the pin pod still lands on its pinned node."""
    from kubernetes_tpu.api.objects import (
        NodeAffinity,
        NodeSelector,
        NodeSelectorRequirement,
        NodeSelectorTerm,
    )
    from kubernetes_tpu.perf.workloads import _daemonset_pod, _node

    cache, snap, m = Cache(), Snapshot(), Mirror(caps=CAPS)
    for i in range(8):
        cache.add_node(_node(i, zones=["z1", "z2"]))
    cache.update_snapshot(snap)
    m.sync(snap)
    pin = _daemonset_pod(3)
    general = Pod(
        metadata=ObjectMeta(name="gen", uid="gen"),
        spec=PodSpec(
            containers=[Container(name="c", resources=ResourceRequirements(
                requests={"cpu": "100m"}))],
            affinity=Affinity(node_affinity=NodeAffinity(
                required=NodeSelector(node_selector_terms=[
                    NodeSelectorTerm(match_expressions=[
                        NodeSelectorRequirement(
                            key=LABEL_ZONE, operator="In",
                            values=["z2"])])])))))
    spec = m.prepare_launch([pin, general], 2)
    assert spec.active == ("nodeaffinity",)
    out = launch_batch(spec, m.well_known(), WEIGHTS, CAPS,
                       serial_scan=False)
    rows = np.asarray(out.node_row)
    assert m.name_of_row(int(rows[0])) == "node-3"
    assert int(rows[1]) % 2 == 1               # z2 nodes are odd rows


# ------------------------- batched eviction wave ------------------------


def test_delete_pods_wave():
    from kubernetes_tpu.hub import Hub

    hub = Hub()
    for i in range(5):
        hub.create_pod(Pod(metadata=ObjectMeta(name=f"v-{i}",
                                               uid=f"v-{i}"),
                           spec=PodSpec(containers=[Container(name="c")])))
    deletes = []
    from kubernetes_tpu.hub import EventHandlers

    hub.watch_pods(EventHandlers(on_delete=lambda p: deletes.append(
        p.metadata.uid)), replay=False)
    gone = hub.delete_pods(["v-0", "v-2", "missing", "v-4"])
    assert gone == ["v-0", "v-2", "v-4"]
    assert sorted(deletes) == ["v-0", "v-2", "v-4"]
    assert hub.get_pod("v-1") is not None
    # replay of the same wave is idempotent
    assert hub.delete_pods(["v-0", "v-2", "v-4"]) == []


def test_delete_pods_fenced():
    from kubernetes_tpu.hub import Fenced, Hub
    from kubernetes_tpu.leaderelection import Lease

    hub = Hub()
    hub.create_pod(Pod(metadata=ObjectMeta(name="v", uid="v"),
                       spec=PodSpec(containers=[Container(name="c")])))
    hub.leases.update(Lease(name="kube-scheduler",
                            holder_identity="other"), None)
    with pytest.raises(Fenced):
        hub.delete_pods(["v"], epoch=0)
    assert hub.get_pod("v") is not None


def test_flush_uses_one_delete_wave():
    """The preemption flush commits its victims through ONE delete_pods
    call instead of one delete_pod per victim."""
    from kubernetes_tpu.backend.nominator import Nominator
    from kubernetes_tpu.framework.preemption import Candidate, Evaluator
    from kubernetes_tpu.hub import Hub

    calls = {"delete_pod": 0, "delete_pods": 0}

    class SpyHub(Hub):
        def delete_pod(self, uid, epoch=None,
                       lease_name="kube-scheduler"):
            calls["delete_pod"] += 1
            return super().delete_pod(uid, epoch, lease_name)

        def delete_pods(self, uids, epoch=None,
                        lease_name="kube-scheduler"):
            calls["delete_pods"] += 1
            return super().delete_pods(uids, epoch, lease_name)

    hub = SpyHub()
    victims = []
    for i in range(6):
        p = Pod(metadata=ObjectMeta(name=f"v-{i}", uid=f"v-{i}"),
                spec=PodSpec(containers=[Container(name="c")]))
        p.spec.node_name = f"node-{i % 2}"
        hub.create_pod(p)
        victims.append(p)
    ev = Evaluator(hub, lambda: None, lambda: None, lambda pod=None: (),
                   Nominator())
    preemptor = Pod(metadata=ObjectMeta(name="hi", uid="hi"),
                    spec=PodSpec(containers=[Container(name="c")],
                                 priority=10))
    ev.prepare_candidate(Candidate(node_name="node-0", row=-1,
                                   victims=victims[:3],
                                   pdb_violations=0), preemptor)
    preemptor2 = Pod(metadata=ObjectMeta(name="hi2", uid="hi2"),
                     spec=PodSpec(containers=[Container(name="c")],
                                  priority=10))
    ev.prepare_candidate(Candidate(node_name="node-1", row=-1,
                                   victims=victims[3:],
                                   pdb_violations=0), preemptor2)
    n = ev.flush_evictions()
    assert n == 2
    assert calls["delete_pods"] == 1
    assert calls["delete_pod"] == 0
    assert all(hub.get_pod(v.metadata.uid) is None for v in victims)
    assert not ev.preempting


def test_queue_coalescing_window():
    """Inside a coalescing window a gated pod's PreEnqueue gate runs once
    per WAVE, not once per event, and requeues still land."""
    from kubernetes_tpu.backend.queue import PriorityQueue
    from kubernetes_tpu.framework.interface import (
        ActionType as A,
        ClusterEvent,
        ClusterEventWithHint,
        EventResource as R,
        Status,
    )

    probes = {"n": 0}
    gate_open = {"open": False}

    def pre_enqueue(pod):
        probes["n"] += 1
        return (Status() if gate_open["open"]
                else Status.unschedulable("gated", plugin="G",
                                          resolvable=False))

    q = PriorityQueue(less_fn=lambda a, b: a.timestamp < b.timestamp,
                      pre_enqueue=pre_enqueue,
                      queueing_hints={"G": [ClusterEventWithHint(
                          event=ClusterEvent(R.ASSIGNED_POD,
                                             A.DELETE))]})
    pod = Pod(metadata=ObjectMeta(name="p", uid="p"),
              spec=PodSpec(containers=[Container(name="c")]))
    q.add(pod)          # gated at add time
    assert q.pending_counts()["gated"] == 1
    probes["n"] = 0
    gate_open["open"] = True
    ev = ClusterEvent(R.ASSIGNED_POD, A.DELETE)
    with q.coalescing():
        for i in range(10):
            q.move_all_to_active_or_backoff(ev, None, None)
    # one gate probe by the batched pass + one by the re-enqueue of the
    # now-ungated pod — per-EVENT processing would have paid 2 per event
    assert probes["n"] == 2, probes["n"]
    assert q.pending_counts()["active"] == 1


# --------------------------- bucket hysteresis ---------------------------


def test_g_cap_oscillation_mints_no_new_shapes():
    """Alternating batch compositions (the churn-pod shape) must settle
    on a BOUNDED set of static shapes — each composition maps to ONE
    stable g_cap, so the oscillation compiles at most once per
    composition and then runs cached. (g_cap is deliberately NOT sticky:
    padding a homogeneous measure phase to a past heterogeneous batch's
    bucket would tax every launch with dead per-group statics.)"""
    rng = random.Random(0)
    _table, snap, m = build(rng, n_nodes=8, n_table=2)
    homog = [soft_pod(f"h-{i}", random.Random(1)) for i in range(4)]
    for i, p in enumerate(homog):
        p.metadata.uid = f"h-{i}"
    odd = [soft_pod(f"odd-{s}", random.Random(40 + s)) for s in range(3)]
    for s, p in enumerate(odd):
        p.metadata.uid = f"odd-{s}"
    mixed = homog[:1] + odd
    shapes = []
    for i in range(12):
        spec = m.prepare_launch(homog if i % 2 else mixed, 4)
        shapes.append((spec.g_cap, spec.d_cap))
    assert len(set(shapes)) <= 2, shapes
    # each composition's shape is STABLE across repeats (no drift that
    # would mint fresh compiles every swing)
    assert shapes[0::2] == [shapes[0]] * 6
    assert shapes[1::2] == [shapes[1]] * 6
    # and a homogeneous batch never pays a past heterogeneous batch's
    # group bucket
    assert shapes[1][0] < shapes[0][0]


def test_d_cap_hysteresis_survives_rebucket():
    rng = random.Random(0)
    _table, snap, m = build(rng)
    d1 = m.launch_d_cap(True)
    m2 = Mirror(caps=CAPS)
    m2.adopt_hysteresis(m)
    assert m2.launch_d_cap(True) >= d1


# ------------------- device-dead preemption mini-path -------------------


def test_device_dead_scheduler_still_preempts():
    """The fallback ladder's bottom rung: with the device path dead for
    EVERY batch, a high-priority pod on a full cluster must still evict
    a victim and bind (it used to park forever)."""
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.scheduler import Scheduler

    hub = Hub()
    for i in range(2):
        hub.create_node(Node(
            metadata=ObjectMeta(name=f"node-{i}",
                                labels={LABEL_HOSTNAME: f"node-{i}"}),
            spec=NodeSpec(),
            status=NodeStatus(allocatable={
                "cpu": "1", "memory": "4Gi", "pods": "10"})))
    sched = Scheduler(hub, caps=Capacities(nodes=8, pods=64))

    class DeviceDead:
        def on_pack(self, pods):
            raise RuntimeError("device dead (injected)")

        def on_result(self, out):
            return out

    sched.fault_injector = DeviceDead()
    try:
        # fill both nodes with low-priority 900m pods
        for i in range(2):
            hub.create_pod(Pod(
                metadata=ObjectMeta(name=f"low-{i}", uid=f"low-{i}"),
                spec=PodSpec(containers=[Container(
                    name="c", resources=ResourceRequirements(
                        requests={"cpu": "900m"}))], priority=0)))
        sched.run_until_idle()
        sched.run_maintenance()
        assert all(hub.get_pod(f"low-{i}").spec.node_name
                   for i in range(2))
        hub.create_pod(Pod(
            metadata=ObjectMeta(name="hi", uid="hi"),
            spec=PodSpec(containers=[Container(
                name="c", resources=ResourceRequirements(
                    requests={"cpu": "900m"}))], priority=100)))
        import time as _time

        bound = False
        for _ in range(30):
            sched.run_until_idle()
            sched.run_maintenance()
            sched.queue.flush_backoff_completed()
            p = hub.get_pod("hi")
            if p is not None and p.spec.node_name:
                bound = True
                break
            _time.sleep(0.2)    # let the unschedulable backoff expire
        assert bound, "high-priority pod never bound on the host rung"
        assert sched.stats.get("preemptions", 0) >= 1
        live = [p.metadata.name for p in hub.list_pods()
                if p.spec.node_name]
        assert len(live) == 2, live       # one victim evicted
    finally:
        sched.close()
