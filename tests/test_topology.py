"""InterPodAffinity + PodTopologySpread kernel parity.

Scenarios mirror the reference's plugin unit-test tables
(interpodaffinity/filtering_test.go, scoring_test.go,
podtopologyspread/filtering_test.go) — built with real objects through the
Cache -> Snapshot -> Mirror path, evaluated via the batched pipeline."""

import numpy as np

from kubernetes_tpu.api.objects import (
    Affinity,
    Container,
    LABEL_HOSTNAME,
    LABEL_ZONE,
    LabelSelector,
    Node,
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
from kubernetes_tpu.backend.cache import Cache
from kubernetes_tpu.backend.mirror import Mirror
from kubernetes_tpu.backend.snapshot import Snapshot
from kubernetes_tpu.models.pipeline import (
    FILTER_PLUGINS,
    default_weights,
    launch_batch,
)
from kubernetes_tpu.ops.features import Capacities

CAPS = Capacities(nodes=16, pods=64, domains=16)


def mknode(name, zone):
    return Node(metadata=ObjectMeta(name=name, labels={
        LABEL_HOSTNAME: name, LABEL_ZONE: zone}),
        status=NodeStatus(allocatable={"cpu": "32", "memory": "64Gi",
                                       "pods": "110"}))


def mkpod(name, labels=None, node=None, affinity=None, tsc=None, ns="default"):
    return Pod(
        metadata=ObjectMeta(name=name, namespace=ns, labels=labels or {}),
        spec=PodSpec(
            node_name=node or "",
            containers=[Container(name="c", resources=ResourceRequirements(
                requests={"cpu": "100m", "memory": "64Mi"}))],
            affinity=affinity,
            topology_spread_constraints=tsc or [],
        ))


def anti(topokey, **match):
    return Affinity(pod_anti_affinity=PodAntiAffinity(required=[
        PodAffinityTerm(topology_key=topokey,
                        label_selector=LabelSelector(match_labels=match))]))


def aff(topokey, **match):
    return Affinity(pod_affinity=PodAffinity(required=[
        PodAffinityTerm(topology_key=topokey,
                        label_selector=LabelSelector(match_labels=match))]))


class Cluster:
    def __init__(self, nodes, scheduled=()):
        self.cache = Cache()
        for n in nodes:
            self.cache.add_node(n)
        for p in scheduled:
            self.cache.add_pod(p)
        self.snap = Snapshot()
        self.cache.update_snapshot(self.snap)
        self.mirror = Mirror(caps=CAPS)
        self.mirror.sync(self.snap)

    def run(self, pods):
        spec = self.mirror.prepare_launch(pods, 8)
        out = launch_batch(spec, self.mirror.well_known(),
                           default_weights(), CAPS)
        names = [self.mirror.name_of_row(int(r)) if r >= 0 else None
                 for r in np.asarray(out.node_row)[: len(pods)]]
        return names, out


ZONES = [mknode("n1", "z1"), mknode("n2", "z1"), mknode("n3", "z2")]


def test_incoming_anti_affinity_zone():
    """Pod with zone anti-affinity to app=web avoids all of z1."""
    cl = Cluster(ZONES, [mkpod("w", {"app": "web"}, node="n1")])
    names, out = cl.run([mkpod("p", affinity=anti(LABEL_ZONE, app="web"))])
    assert names == ["n3"]
    ipa_idx = FILTER_PLUGINS.index("InterPodAffinity")
    assert np.asarray(out.reject_counts)[0, ipa_idx] == 2


def test_incoming_anti_affinity_hostname():
    cl = Cluster(ZONES, [mkpod("w", {"app": "web"}, node="n1")])
    names, _ = cl.run([mkpod("p", affinity=anti(LABEL_HOSTNAME, app="web"))])
    assert names[0] in ("n2", "n3")


def test_existing_pod_anti_affinity_blocks():
    """An existing pod's anti-affinity term keeps matching pods out of its
    whole zone (satisfyExistingPodsAntiAffinity)."""
    guard = mkpod("guard", {"team": "a"}, node="n1",
                  affinity=anti(LABEL_ZONE, app="web"))
    cl = Cluster(ZONES, [guard])
    names, _ = cl.run([mkpod("p", {"app": "web"})])
    assert names == ["n3"]


def test_required_affinity_follows():
    cl = Cluster(ZONES, [mkpod("w", {"app": "db"}, node="n3")])
    names, out = cl.run([mkpod("p", affinity=aff(LABEL_ZONE, app="db"))])
    assert names == ["n3"]


def test_required_affinity_first_pod_of_group():
    """No matching pod anywhere, but the pod matches its own term: allowed
    (the first pod of a self-affine group must be schedulable)."""
    cl = Cluster(ZONES)
    names, _ = cl.run([mkpod("p", {"app": "db"},
                             affinity=aff(LABEL_ZONE, app="db"))])
    assert names[0] is not None


def test_required_affinity_unsatisfiable_when_not_self_matching():
    cl = Cluster(ZONES)
    names, _ = cl.run([mkpod("p", affinity=aff(LABEL_ZONE, app="db"))])
    assert names == [None]


def test_in_batch_anti_affinity():
    """As-if-serial: two self-anti-affine pods in ONE batch must land in
    different zones, and a third must be unschedulable (2 zones)."""
    cl = Cluster(ZONES)
    pods = [mkpod(f"p{i}", {"app": "web"},
                  affinity=anti(LABEL_ZONE, app="web")) for i in range(3)]
    names, out = cl.run(pods)
    z = {"n1": "z1", "n2": "z1", "n3": "z2"}
    assert names[0] is not None and names[1] is not None
    assert z[names[0]] != z[names[1]]
    assert names[2] is None, "only two zones exist"


def test_in_batch_anti_affinity_matches_sequential():
    """One batch == sequential single-pod batches with host resync between."""
    def run_seq(cl, pods):
        placed = []
        for p in pods:
            names, _ = cl.run([p])
            placed.append(names[0])
            if names[0] is not None:
                bound = p.clone()
                bound.spec.node_name = names[0]
                cl.cache.add_pod(bound)
                cl.cache.update_snapshot(cl.snap)
                cl.mirror.sync(cl.snap)
        return placed

    mk = lambda i: mkpod(f"p{i}", {"app": "web"},
                         affinity=anti(LABEL_HOSTNAME, app="web"))
    batched, _ = Cluster(ZONES).run([mk(i) for i in range(4)])
    sequential = run_seq(Cluster(ZONES), [mk(i) for i in range(4)])
    assert batched == sequential


def test_in_batch_affinity_follows_batch_commit():
    """Pod 2's required affinity is satisfied by pod 1's in-batch commit."""
    cl = Cluster(ZONES)
    leader = mkpod("leader", {"app": "grp"},
                   affinity=aff(LABEL_ZONE, app="grp"))  # self-match rule
    follower = mkpod("follower", affinity=aff(LABEL_ZONE, app="grp"))
    names, _ = cl.run([leader, follower])
    z = {"n1": "z1", "n2": "z1", "n3": "z2"}
    assert names[0] is not None and names[1] is not None
    assert z[names[0]] == z[names[1]]


def test_in_batch_spread_counts():
    """Hard hostname spread within one batch: 3 pods, 3 nodes, one each."""
    cl = Cluster(ZONES)
    pods = [mkpod(f"p{i}", {"app": "s"},
                  tsc=[hard_spread(LABEL_HOSTNAME, app="s")])
            for i in range(4)]
    names, _ = cl.run(pods)
    assert sorted(names[:3]) == ["n1", "n2", "n3"]
    # 4th pod: every node at count 1, min 1 -> skew 1+1-1 = 1 <= 1: fits
    assert names[3] is not None


def hard_spread(key, max_skew=1, **sel):
    return TopologySpreadConstraint(
        max_skew=max_skew, topology_key=key, when_unsatisfiable="DoNotSchedule",
        label_selector=LabelSelector(match_labels=sel))


def soft_spread(key, max_skew=1, **sel):
    return TopologySpreadConstraint(
        max_skew=max_skew, topology_key=key, when_unsatisfiable="ScheduleAnyway",
        label_selector=LabelSelector(match_labels=sel))


def test_spread_filter_zone():
    """2 matching pods in z1, 0 in z2, maxSkew=1: z1 nodes rejected."""
    cl = Cluster(ZONES, [mkpod("a", {"app": "s"}, node="n1"),
                         mkpod("b", {"app": "s"}, node="n2")])
    names, out = cl.run([mkpod("p", {"app": "s"},
                               tsc=[hard_spread(LABEL_ZONE, app="s")])])
    assert names == ["n3"]
    sp_idx = FILTER_PLUGINS.index("PodTopologySpread")
    assert np.asarray(out.reject_counts)[0, sp_idx] == 2


def test_spread_filter_allows_balanced():
    cl = Cluster(ZONES, [mkpod("a", {"app": "s"}, node="n1"),
                         mkpod("b", {"app": "s"}, node="n3")])
    names, _ = cl.run([mkpod("p", {"app": "s"},
                             tsc=[hard_spread(LABEL_ZONE, app="s")])])
    assert names[0] is not None


def test_spread_hostname_sequential():
    """Hostname spreading drains one pod per node as the table fills."""
    cl = Cluster(ZONES)
    seen = []
    for i in range(3):
        p = mkpod(f"p{i}", {"app": "s"},
                  tsc=[hard_spread(LABEL_HOSTNAME, app="s")])
        names, _ = cl.run([p])
        assert names[0] is not None
        seen.append(names[0])
        bound = mkpod(f"p{i}", {"app": "s"}, node=names[0],
                      tsc=[hard_spread(LABEL_HOSTNAME, app="s")])
        cl.cache.add_pod(bound)
        cl.cache.update_snapshot(cl.snap)
        cl.mirror.sync(cl.snap)
    assert sorted(seen) == ["n1", "n2", "n3"]


def test_spread_soft_scores_less_crowded():
    """ScheduleAnyway: prefers the zone with fewer matching pods."""
    cl = Cluster(ZONES, [mkpod("a", {"app": "s"}, node="n1"),
                         mkpod("b", {"app": "s"}, node="n2")])
    names, _ = cl.run([mkpod("p", {"app": "s"},
                             tsc=[soft_spread(LABEL_ZONE, app="s")])])
    assert names == ["n3"]


def test_min_domains():
    """minDomains=3 with only 2 zones: global min treated as 0, so any node
    with matchNum >= maxSkew is rejected."""
    t = hard_spread(LABEL_ZONE, app="s")
    t.min_domains = 3
    cl = Cluster(ZONES, [mkpod("a", {"app": "s"}, node="n1")])
    names, _ = cl.run([mkpod("p", {"app": "s"}, tsc=[t])])
    # z1 has 1 matching pod: skew = 1 + 1 - 0 = 2 > 1 -> n1/n2 rejected;
    # z2 has 0: skew = 0 + 1 - 0 = 1 <= 1 -> n3 allowed
    assert names == ["n3"]


def test_preferred_affinity_scores():
    """Preferred zone affinity pulls the pod toward the matching zone."""
    w = Affinity(pod_affinity=PodAffinity(preferred=[
        WeightedPodAffinityTerm(weight=100, pod_affinity_term=PodAffinityTerm(
            topology_key=LABEL_ZONE,
            label_selector=LabelSelector(match_labels={"app": "db"})))]))
    cl = Cluster(ZONES, [mkpod("db", {"app": "db"}, node="n3")])
    names, _ = cl.run([mkpod("p", affinity=w)])
    assert names == ["n3"]


def test_new_topology_key_first_launch():
    """A topology key first referenced by the batch itself (not
    pre-registered) must be live on device for that same launch — the
    prepare_launch ordering guarantee (topo_dom backfill)."""
    nodes = [mknode("n1", "z1"), mknode("n2", "z2")]
    nodes[0].metadata.labels["rack"] = "r1"
    nodes[1].metadata.labels["rack"] = "r2"
    cl = Cluster(nodes, [mkpod("db", {"app": "db"}, node="n1")])
    names, _ = cl.run([mkpod("p", affinity=aff("rack", app="db"))])
    assert names == ["n1"]


def test_soft_spread_on_unlabeled_key_keeps_hard_filtering():
    """A ScheduleAnyway constraint on a key no node carries must not disable
    a DoNotSchedule constraint (eligibility sets are per-hardness)."""
    cl = Cluster(ZONES, [mkpod("a", {"app": "s"}, node="n1"),
                         mkpod("b", {"app": "s"}, node="n2")])
    names, out = cl.run([mkpod("p", {"app": "s"},
                               tsc=[hard_spread(LABEL_ZONE, app="s"),
                                    soft_spread("rack", app="s")])])
    assert names == ["n3"]
    sp_idx = FILTER_PLUGINS.index("PodTopologySpread")
    assert np.asarray(out.reject_counts)[0, sp_idx] == 2


def test_nil_spread_selector_matches_nothing():
    """labelSelector=None on a spread constraint selects no pods
    (labels.Nothing()): no rejects anywhere."""
    t = TopologySpreadConstraint(max_skew=1, topology_key=LABEL_ZONE,
                                 when_unsatisfiable="DoNotSchedule",
                                 label_selector=None)
    cl = Cluster(ZONES, [mkpod("a", {"app": "s"}, node="n1"),
                         mkpod("b", {"app": "s"}, node="n1"),
                         mkpod("c", {"app": "s"}, node="n1")])
    names, out = cl.run([mkpod("p", {"app": "s"}, tsc=[t])])
    sp_idx = FILTER_PLUGINS.index("PodTopologySpread")
    assert np.asarray(out.reject_counts)[0, sp_idx] == 0
    assert names[0] is not None


def test_preferred_anti_affinity_scores():
    w = Affinity(pod_anti_affinity=PodAntiAffinity(preferred=[
        WeightedPodAffinityTerm(weight=100, pod_affinity_term=PodAffinityTerm(
            topology_key=LABEL_ZONE,
            label_selector=LabelSelector(match_labels={"app": "db"})))]))
    cl = Cluster(ZONES, [mkpod("db", {"app": "db"}, node="n1")])
    names, _ = cl.run([mkpod("p", affinity=w)])
    assert names == ["n3"]


# suite-tier discipline (tests/test_markers.py): area marker
import pytest  # noqa: E402
pytestmark = pytest.mark.core


# ---- phase 1b's table passes, folded over the live blocks (PR 38) ----
#
# table_statics runs the passes over blocks of the pod table up to the last
# block that holds a live slot. ORs of booleans and f32 sums of whole
# numbers give the same bits in any order, so every map must equal the
# one pass over the whole table (inter_pod_affinity_static,
# inter_pod_affinity_score, spread_cnt) bit for bit, whatever is live.

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import kubernetes_tpu.ops.topology as T  # noqa: E402
from kubernetes_tpu.ops.features import (  # noqa: E402
    ClusterBlobs,
    unpack_cluster,
    unpack_pods,
)
from kubernetes_tpu.utils.interner import NONE  # noqa: E402

FOLD_CAPS = Capacities(nodes=16, pods=64, domains=16)   # 16 blocks of 4
APPS = ("web", "db", "cache")


def _weighted(weight, topokey, **match):
    return WeightedPodAffinityTerm(weight=weight, pod_affinity_term=(
        PodAffinityTerm(topology_key=topokey,
                        label_selector=LabelSelector(match_labels=match))))


def _table_pod(i):
    """Slot i's pod: every kind of term the passes read, a weight of 1 to
    100, two namespaces, 16 nodes in three zones."""
    app = APPS[i % 3]
    key = LABEL_ZONE if i % 2 else LABEL_HOSTNAME
    kinds = (None,
             anti(key, app=APPS[(i + 1) % 3]),
             aff(key, app=app),
             Affinity(pod_affinity=PodAffinity(preferred=[
                 _weighted(1 + 33 * (i % 4), key, app="web")])),
             Affinity(pod_anti_affinity=PodAntiAffinity(preferred=[
                 _weighted(100, LABEL_ZONE, app=app)])))
    return mkpod(f"t{i}", {"app": app}, node=f"n{i % 16}",
                 affinity=kinds[i % 5], ns="default" if i % 7 else "other")


def _incoming():
    """Three groups: each reads the table through another term."""
    spread = [TopologySpreadConstraint(
        max_skew=1, topology_key=LABEL_ZONE,
        when_unsatisfiable="DoNotSchedule",
        label_selector=LabelSelector(match_labels={"app": "web"}))]
    both = anti(LABEL_ZONE, app="db")
    both.pod_affinity = PodAffinity(
        required=[PodAffinityTerm(
            topology_key=LABEL_HOSTNAME,
            label_selector=LabelSelector(match_labels={"app": "cache"}))],
        preferred=[_weighted(37, LABEL_ZONE, app="web")])
    soft = Affinity(pod_anti_affinity=PodAntiAffinity(preferred=[
        _weighted(100, LABEL_HOSTNAME, app="db")]))
    return [mkpod("in-web", {"app": "web"}, affinity=both, tsc=spread),
            mkpod("in-db", {"app": "db"}, affinity=soft, tsc=spread),
            mkpod("in-plain", {"app": "cache"})]


@functools.lru_cache(maxsize=1)
def _fold_inputs():
    """A full 64-slot table, the incoming pods' features and d_cap."""
    nodes = [mknode(f"n{i}", f"z{i % 3}") for i in range(16)]
    cl = Cluster(nodes, [_table_pod(i) for i in range(64)])
    assert cl.mirror.slots_hi == 64
    spec = cl.mirror.prepare_launch(_incoming(), 8)
    pods = unpack_pods(spec.pblobs, FOLD_CAPS, spec.pfields, spec.ptmpl)
    return (np.asarray(spec.cblobs.pods_i32), spec.cblobs, pods,
            spec.d_cap, cl.mirror._slot_valid_off)


@functools.partial(jax.jit, static_argnames=("d_cap",))
def _fold_and_reference(cblobs, pods, d_cap):
    ct = unpack_cluster(cblobs, FOLD_CAPS)
    tds = T.slot_topo_dom(ct)
    hw = jnp.float32(100.0)

    def one(pod):
        el = ct.node_valid[:, None] & (pod.tsc_tk != NONE)[None]
        anti_ok, present, any_match = T.inter_pod_affinity_static(
            ct, pod, tds, d_cap)
        ref = (anti_ok, present, any_match,
               T.inter_pod_affinity_score(ct, pod, tds, d_cap, hw),
               T.spread_cnt(ct, pod, tds, el, d_cap))
        ts = T.table_statics(ct, cblobs.pods_i32, FOLD_CAPS, pod, d_cap,
                             forbid=True, presence=True, hard_weight=hw,
                             spread_el=el)
        return ref, (ts.anti_ok, ts.present, ts.any_match, ts.ipa_raw,
                     ts.cnt)

    return jax.vmap(one)(pods), T.table_blocks(ct)


FOLD_TABLES = {
    "empty": (),
    "one_slot_at_0": (0,),
    "prefix_inside_block_0": range(3),
    "prefix_across_blocks": range(23),
    "holes_below_the_mark": sorted(set(range(41))
                                   - {3, 5, 16, 17, 18, 19, 33}),
    "only_the_last_slot": (63,),
    "full": range(64),
}


@pytest.mark.parametrize("table", sorted(FOLD_TABLES))
def test_the_folded_table_passes_equal_the_full_ones_bit_for_bit(table):
    full, cblobs, pods, d_cap, valid_off = _fold_inputs()
    live = sorted(FOLD_TABLES[table])
    i32 = full.copy()
    # a dead slot keeps its fields: only pod_valid tells it from a live one
    i32[np.setdiff1d(np.arange(64), live), valid_off] = 0
    cb = ClusterBlobs(node_f32=cblobs.node_f32, node_i32=cblobs.node_i32,
                      pods_i32=jnp.asarray(i32))
    (ref, fold), blocks = _fold_and_reference(cb, pods, d_cap)
    hi = live[-1] + 1 if live else 0
    assert int(blocks) == T.table_blocks_for(hi, 64) == -(-hi // 4)
    names = ("anti_ok", "present", "any_match", "ipa_raw", "cnt")
    for name, r, f in zip(names, ref, fold, strict=True):
        r, f = np.asarray(r), np.asarray(f)
        assert r.dtype == f.dtype, name
        np.testing.assert_array_equal(r.view(np.uint8), f.view(np.uint8),
                                      err_msg=name)
    if table == "full":
        # the table decides something here: forbidden nodes, a present
        # domain, scores of both signs, counts
        anti_ok, present, _any, ipa_raw, cnt = map(np.asarray, ref)
        assert not anti_ok[0].all() and present[0].any()
        assert (ipa_raw > 0).any() and (ipa_raw < 0).any() and cnt.any()
