"""Required inter-pod affinity where it decides something. The cell
`affinity-5k.required` keeps upstream's one zone, in which any placement
satisfies the term, so the system is tied to benchmark/reference_affinity.py
here: the production Scheduler over an in-process Hub on small clusters
built by hand from a seed (three zones and a few nodes with no zone label),
its end state held to the reference; the reference alone on placements that
are each wrong in one way; and the pod-table slot of a pod with terms, kept
over a confirmation, against what a full pack writes."""

import copy
import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import objects, reference_affinity  # noqa: E402

ZONES = ("z-a", "z-b", "z-c")
ZONE = objects.ZONE_KEY
MEASURED = objects.load_template("pod-with-pod-affinity")
INIT = objects.load_template("pod-with-pod-affinity-init")
TERMS = reference_affinity.required_terms(MEASURED)
SEEDS = (3_000_000_019, 2_147_483_659)


def test_reference_affinity_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference_affinity.py")) as f:
        lines = [ln.split() for ln in f.read().splitlines()]
    imported = [ln[1] for ln in lines if ln[:1] in (["import"], ["from"])]
    assert imported == ["__future__"]
    assert TERMS == [{"topology_key": ZONE,
                      "match_labels": {"color": "blue"},
                      "namespaces": ["sched-1", "sched-0"]}]
    assert reference_affinity.required_terms(
        objects.load_template("pod-default")) == []


# ------------------------------------------------- the reference alone

NODE_LABELS = {"a0": {ZONE: "z-a"}, "a1": {ZONE: "z-a"}, "b0": {ZONE: "z-b"},
               "bare": {}}
BLUE = {"color": "blue"}


def _unsatisfied(pods, judged):
    return reference_affinity.affinity_unsatisfied(TERMS, NODE_LABELS, pods,
                                                   judged)


def test_reference_passes_a_pod_beside_a_match_of_either_listed_namespace():
    pods = [("i0", "a0", "sched-0", BLUE), ("m0", "a1", "sched-1", BLUE),
            ("m1", "a0", "sched-1", BLUE)]
    assert _unsatisfied(pods, ["m0", "m1"]) == 0
    # a judged pod that is not bound is not judged; no terms, nothing held
    assert _unsatisfied(pods, ["m0", "pending"]) == 0
    assert reference_affinity.affinity_unsatisfied(
        [], NODE_LABELS, pods, ["m0"]) == 0


WRONG = {
    # placement of m0 (the others right), and how many pods it breaks
    "the node has no topology key": (
        [("i0", "a0", "sched-0", BLUE), ("m0", "bare", "sched-1", BLUE)], 1),
    "the match is in another zone": (
        [("i0", "a0", "sched-0", BLUE), ("m0", "b0", "sched-1", BLUE)], 1),
    "the match is in a namespace the term does not list": (
        [("i0", "a0", "elsewhere", BLUE), ("i1", "b0", "sched-0", BLUE),
         ("m0", "a1", "sched-1", BLUE)], 1),
    "the match has another label": (
        [("i0", "a0", "sched-0", {"color": "red"}),
         ("i1", "b0", "sched-0", BLUE), ("m0", "a1", "sched-1", BLUE)], 1),
    "the only match in the zone is the pod itself": (
        [("i0", "a0", "sched-0", BLUE), ("m0", "b0", "sched-1", BLUE),
         ("m1", "a1", "sched-1", BLUE)], 1),
    "the node is not in the cluster": (
        [("i0", "a0", "sched-0", BLUE), ("m0", "gone", "sched-1", BLUE)], 1),
    "two pods, each alone in its zone": (
        [("m0", "a0", "sched-1", BLUE), ("m1", "b0", "sched-1", BLUE)], 2),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_reference_counts_a_placement_that_is_wrong_in_one_way(wrong):
    pods, bad = WRONG[wrong]
    judged = [uid for uid, *_rest in pods if uid.startswith("m")]
    assert _unsatisfied(pods, judged) == bad


def test_reference_keeps_upstreams_exception_for_the_first_pod_only():
    # no other pod anywhere selects: the pod that selects itself may be
    # first, on a node that carries the key, and nowhere else
    alone = [("m0", "a0", "sched-1", BLUE),
             ("x", "b0", "elsewhere", BLUE)]
    assert _unsatisfied(alone, ["m0"]) == 0
    assert _unsatisfied([("m0", "bare", "sched-1", BLUE)], ["m0"]) == 1
    # a pod that does not select itself is never the first
    assert _unsatisfied([("m0", "a0", "sched-1", {"color": "red"})],
                        ["m0"]) == 1
    assert _unsatisfied([("m0", "a0", "elsewhere", BLUE)], ["m0"]) == 1
    # a term without namespaces means the pod's own
    own = [dict(TERMS[0], namespaces=[])]
    pods = [("i0", "a0", "sched-0", BLUE), ("m0", "a1", "sched-1", BLUE),
            ("m1", "a0", "sched-1", BLUE)]
    assert reference_affinity.affinity_unsatisfied(
        own, NODE_LABELS, pods, ["m0", "m1"]) == 0
    assert reference_affinity.affinity_unsatisfied(
        own, NODE_LABELS, pods[:2], ["m0"]) == 0      # first of sched-1
    assert reference_affinity.affinity_unsatisfied(
        own, NODE_LABELS, pods[:2] + [("m1", "b0", "sched-1", BLUE)],
        ["m0", "m1"]) == 2


# ------------------------------------------------- the production scheduler


def _run(seed, existing, offered, cpu="4"):
    """A cluster by hand: nine nodes over three zones and three with no
    zone label, created in the seed's order; `existing` = [(template,
    zone or None, count)] pods created already bound on the seed's nodes of
    that zone (None: the unlabelled nodes); `offered` measured pods of
    pod-with-pod-affinity through Scheduler.run_until_idle. Returns
    (home zones drawn, node labels, bound pods as the reference takes them,
    measured uids, zone of each bound measured pod)."""
    from kubernetes_tpu.api.objects import Namespace, ObjectMeta
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    rng = random.Random(seed)
    tmpl = dict(objects.load_template("node-default"),
                allocatable={"cpu": cpu, "memory": "32Gi", "pods": "110"})
    nodes = [objects.make_node(tmpl, i, list(ZONES)) for i in range(9)] \
        + [objects.make_node(tmpl, i, []) for i in range(9, 12)]
    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 16
    cfg.tie_break_seed = seed & 0xffffffff
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=256))
    try:
        for n in rng.sample(nodes, len(nodes)):
            hub.create_node(n)
        for ns in ("sched-0", "sched-1", "elsewhere"):
            hub.create_namespace(Namespace(
                metadata=ObjectMeta(name=ns, uid=f"ns-{ns}")))
        zones = rng.sample(ZONES, len(ZONES))     # home, second, third
        made = 0
        for template, where, count in existing:
            zone = None if where is None else zones[where]
            pool = [n.metadata.name for n in nodes
                    if n.metadata.labels.get(ZONE) == zone]
            maker = objects.PodMaker(template)
            for _ in range(count):
                hub.create_pod(maker.make(f"init-{made}",
                                          node_name=rng.choice(pool)))
                made += 1
        maker = objects.PodMaker(MEASURED)
        measured = []
        for i in range(offered):
            pod = maker.make(f"m-{seed}-{i}")
            measured.append(pod.metadata.uid)
            hub.create_pod(pod)
        sched.run_until_idle()
        labels = {n.metadata.name: n.metadata.labels
                  for n in hub.list_nodes()}
        bound = [(p.metadata.uid, p.spec.node_name, p.metadata.namespace,
                  p.metadata.labels) for p in hub.list_pods()
                 if p.spec.node_name]
        assert sched.stats["device_fallbacks"] == 0
    finally:
        sched.close()
    where = {uid: labels[node].get(ZONE) for uid, node, _ns, _l in bound
             if uid in set(measured)}
    return zones, labels, bound, measured, where


def _other(template, **keys):
    return dict(copy.deepcopy(template), **keys)


CLUSTERS = {
    # blue pods of sched-0 in the home zone only; plain pods everywhere
    "matches in one zone only": [
        (INIT, 0, 4), (objects.load_template("pod-default"), 1, 3),
        (objects.load_template("pod-default"), None, 2)],
    # more blue pods, in a namespace the term does not list, in the second
    # zone and on the unlabelled nodes: they must not count
    "a foreign namespace holds more matches elsewhere": [
        (INIT, 0, 2), (_other(INIT, namespace="elsewhere"), 1, 6),
        (_other(INIT, namespace="elsewhere"), None, 3)],
    # red pods of sched-0 in the second zone: the label has to match
    "another colour in a listed namespace": [
        (INIT, 0, 3), (_other(INIT, labels={"color": "red"}), 1, 5)],
}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cluster", sorted(CLUSTERS))
def test_scheduler_binds_affinity_pods_only_into_the_zone_that_holds_a_match(
        cluster, seed):
    zones, labels, bound, measured, where = _run(seed, CLUSTERS[cluster], 40)
    assert len(where) == 40, "every measured pod is bound"
    assert set(where.values()) == {zones[0]}, "and in the one eligible zone"
    assert reference_affinity.affinity_unsatisfied(
        TERMS, labels, bound, measured) == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_first_pod_of_the_series_lands_on_a_zone_and_the_rest_follow(seed):
    """No pod the term selects anywhere (blue pods only in a namespace it
    does not list): upstream lets the first pod through on any node that
    carries the zone key, and every later one has to join it."""
    _zones, labels, bound, measured, where = _run(
        seed, [(_other(INIT, namespace="elsewhere"), 1, 4),
               (_other(INIT, namespace="elsewhere"), None, 2)], 24)
    assert len(where) == 24
    assert len(set(where.values())) == 1 and None not in where.values()
    assert reference_affinity.affinity_unsatisfied(
        TERMS, labels, bound, measured) == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_pod_stays_pending_where_no_node_is_eligible(seed):
    """The home zone's three nodes hold ten of these pods each (1 cpu): 4
    are there, 26 more fit, and the rest wait rather than go to a zone
    without a match or to a node without the key."""
    zones, labels, bound, measured, where = _run(
        seed, [(INIT, 0, 4)], 34, cpu="1")
    assert len(where) == 26 and set(where.values()) == {zones[0]}
    assert reference_affinity.affinity_unsatisfied(
        TERMS, labels, bound, measured) == 0
    assert len(bound) == 30, "the pending pods are bound nowhere"


# ------------------------------------------------- the slot of a pod with terms


def test_kept_slot_of_a_pod_with_terms_is_what_a_full_pack_writes():
    """PR 31's slots_kept path with pods whose slots carry terms: the
    informer's confirmation swaps the Pod object in, the next sync of the
    node keeps the slot without a write, and the slot still reads what
    _pack_pod_slot writes for the new object (a fresh mirror's full pack).
    The slow arm is counted and timed; a pod without terms is not."""
    from kubernetes_tpu.backend.cache import Cache
    from kubernetes_tpu.backend.mirror import Mirror
    from kubernetes_tpu.backend.snapshot import Snapshot
    from kubernetes_tpu.ops.features import Capacities
    from tests.test_mirror import _assert_same_tables, _fresh_mirror

    cache, snap = Cache(), Snapshot()
    node = objects.make_node(objects.load_template("node-default"), 0,
                             ["zone1"])
    cache.add_node(node)
    m = Mirror(caps=Capacities(nodes=16, pods=128))
    makers = [objects.PodMaker(INIT), objects.PodMaker(MEASURED)]
    assumed = []
    for i in range(6):
        a = makers[i % 2].make(f"t-{i}", node_name=node.metadata.name)
        cache.assume_pod(a)
        assumed.append(a)
    plain = objects.PodMaker(objects.load_template("pod-default")).make(
        "plain", node_name=node.metadata.name)
    cache.assume_pod(plain)
    cache.update_snapshot(snap)
    m.sync(snap)
    st = m.sync_stats()
    assert (st["slots_packed"], st["slots_packed_terms"]) == (7, 6)
    assert m.slot_terms_s > 0.0
    terms_s = m.slot_terms_s
    m.to_blobs()
    for a in assumed:
        cache.add_pod(a.clone())            # the informer's new object
    one_more = makers[1].make("t-late", node_name=node.metadata.name)
    cache.assume_pod(one_more)              # the node's next change
    cache.update_snapshot(snap)
    m.sync(snap)
    after = m.sync_stats()
    assert after["slots_kept"] == st["slots_kept"] + 6
    assert after["slots_packed"] == st["slots_packed"] + 1
    assert after["slots_packed_terms"] == st["slots_packed_terms"] + 1
    assert after["slots_released"] == st["slots_released"]
    assert m._dirty_slots == {m._pod_slot[one_more.metadata.uid]}
    assert m.slot_terms_s > terms_s
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))
    grown = Mirror(caps=Capacities(nodes=16, pods=256))
    grown.adopt_hysteresis(m)
    assert grown.sync_stats() == after
    assert grown.slot_terms_s == m.slot_terms_s


def test_slot_pack_terms_view_and_counter_through_the_scheduler():
    """The view is reported once a sync (0.0 where no slot took the slow
    arm), and slots_packed_terms reaches /metrics as its own counter."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.utils.tracing import (
        LOOP_VIEW_PHASES, UNCOUNTED_PHASES, VIEW_PHASES)

    assert "slot_pack_terms" in LOOP_VIEW_PHASES
    assert "slot_pack_terms" in VIEW_PHASES
    assert "slot_pack_terms" in UNCOUNTED_PHASES
    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 8
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=128))
    try:
        hub.create_node(objects.make_node(
            objects.load_template("node-default"), 0, ["zone1"]))
        seen = []
        observe = sched.flight.observe_phase
        sched.flight.observe_phase = lambda p, s: (
            seen.append((p, s)), observe(p, s))[1]
        plain = objects.PodMaker(objects.load_template("pod-spread-required"))
        for i in range(4):          # a topology launch syncs; no terms
            hub.create_pod(plain.make(f"s-{i}"))
        sched.run_until_idle()
        views = [s for p, s in seen if p == "slot_pack_terms"]
        assert views and all(s == 0.0 for s in views)
        assert sched.mirror.sync_stats()["slots_packed_terms"] == 0
        maker = objects.PodMaker(INIT)
        for rnd in range(3):
            for i in range(4):
                hub.create_pod(maker.make(f"a-{rnd}-{i}"))
            sched.run_until_idle()
        sched.run_maintenance()
        views = [s for p, s in seen if p == "slot_pack_terms"]
        assert sum(1 for s in views if s > 0.0) >= 2
        st = sched.mirror.sync_stats()
        assert st["slots_packed_terms"] >= 8
        assert st["slots_packed"] >= st["slots_packed_terms"] + 4
        m = sched.metrics
        assert m.mirror_slot_terms.value() == st["slots_packed_terms"]
        text = m.registry.render_text()
        assert (f'scheduler_mirror_slot_terms_total '
                f'{st["slots_packed_terms"]}') in text
        # a counter of its own: the result values still part the slots
        assert 'result="packed_terms"' not in text
        # the sums land in the phase histogram, where the readers look
        sums = {k.split("'")[3]: rec["sum"] for k, rec
                in m.phase_duration.snapshot().items() if k.count("'") >= 4}
        assert 0.0 < sums["slot_pack_terms"] <= sums["mirror_sync"]
    finally:
        sched.close()
