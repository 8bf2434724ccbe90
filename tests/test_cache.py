import pytest

from kubernetes_tpu.api.objects import (
    LABEL_ZONE,
    Affinity,
    Container,
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
)
from kubernetes_tpu.backend.cache import Cache
from kubernetes_tpu.backend.node_info import NodeInfo
from kubernetes_tpu.backend.snapshot import Snapshot


def mknode(name, zone=None, cpu="4", mem="8Gi"):
    labels = {LABEL_ZONE: zone} if zone else {}
    return Node(metadata=ObjectMeta(name=name, labels=labels),
                status=NodeStatus(allocatable={"cpu": cpu, "memory": mem, "pods": "110"}))


def mkpod(name, node="", cpu="100m", uid=None):
    meta = ObjectMeta(name=name)
    if uid:
        meta.uid = uid
    return Pod(metadata=meta,
               spec=PodSpec(node_name=node, containers=[
                   Container(resources=ResourceRequirements(requests={"cpu": cpu}))]))


def test_node_info_aggregates():
    ni = NodeInfo(mknode("n1"))
    assert ni.allocatable.milli_cpu == 4000
    p = mkpod("p1", "n1", cpu="500m")
    ni.add_pod(p)
    assert ni.requested.milli_cpu == 500
    assert len(ni.pods) == 1
    assert ni.remove_pod(p)
    assert ni.requested.milli_cpu == 0
    assert not ni.pods


def test_assume_confirm_flow():
    c = Cache()
    c.add_node(mknode("n1"))
    p = mkpod("p1", "n1", cpu="1")
    c.assume_pod(p)
    assert c.is_assumed_pod(p)
    assert c.pod_count() == 1
    c.finish_binding(p)
    # informer confirms
    c.add_pod(p)
    assert not c.is_assumed_pod(p)
    assert c.pod_count() == 1
    c.remove_pod(p)
    assert c.pod_count() == 0


def test_forget_pod():
    c = Cache()
    c.add_node(mknode("n1"))
    p = mkpod("p1", "n1")
    c.assume_pod(p)
    c.forget_pod(p)
    assert c.pod_count() == 0
    assert not c.is_assumed_pod(p)


def test_assumed_pod_ttl_expiry():
    t = [100.0]
    c = Cache(ttl=30.0, now=lambda: t[0])
    c.add_node(mknode("n1"))
    p = mkpod("p1", "n1")
    c.assume_pod(p)
    c.finish_binding(p)
    assert c.cleanup_assumed_pods() == []
    t[0] = 131.0
    expired = c.cleanup_assumed_pods()
    assert [e.metadata.uid for e in expired] == [p.metadata.uid]
    assert c.pod_count() == 0


def test_snapshot_incremental():
    c = Cache()
    snap = Snapshot()
    c.add_node(mknode("n1"))
    c.add_node(mknode("n2"))
    c.update_snapshot(snap)
    assert snap.num_nodes() == 2
    gen1 = snap.generation

    # adding a pod touches only n1's row
    c.add_pod(mkpod("p1", "n1", cpu="2"))
    c.update_snapshot(snap)
    assert snap.generation > gen1
    assert snap.get("n1").requested.milli_cpu == 2000
    assert snap.get("n2").requested.milli_cpu == 0

    # removing a node shrinks the list
    c.remove_node(mknode("n2"))
    c.update_snapshot(snap)
    assert snap.num_nodes() == 1
    assert snap.get("n2") is None


def test_snapshot_is_immutable_view():
    c = Cache()
    snap = Snapshot()
    c.add_node(mknode("n1"))
    c.update_snapshot(snap)
    before = snap.get("n1").requested.milli_cpu
    c.add_pod(mkpod("p1", "n1", cpu="3"))
    # cache changed, snapshot not yet refreshed
    assert snap.get("n1").requested.milli_cpu == before


def test_zone_interleaving():
    c = Cache()
    snap = Snapshot()
    for i in range(4):
        c.add_node(mknode(f"a{i}", zone="za"))
    for i in range(2):
        c.add_node(mknode(f"b{i}", zone="zb"))
    c.update_snapshot(snap)
    order = [ni.name for ni in snap.node_info_list]
    # round-robin: zones alternate while both have nodes
    first_four = order[:4]
    assert {first_four[0][0], first_four[1][0]} == {"a", "b"}
    assert {first_four[2][0], first_four[3][0]} == {"a", "b"}


def test_remove_node_with_pods_keeps_info():
    c = Cache()
    n = mknode("n1")
    c.add_node(n)
    c.add_pod(mkpod("p1", "n1"))
    c.remove_node(n)
    snap = Snapshot()
    c.update_snapshot(snap)
    # node-less info is excluded from the snapshot list
    assert snap.num_nodes() == 0
    # but pod removal later fully cleans up
    assert c.pod_count() == 1


def test_imaginary_node_from_early_pod():
    c = Cache()
    c.add_pod(mkpod("p1", "ghost"))
    assert c.pod_count() == 1
    snap = Snapshot()
    c.update_snapshot(snap)
    assert snap.num_nodes() == 0
    c.add_node(mknode("ghost"))
    c.update_snapshot(snap)
    assert snap.num_nodes() == 1
    assert snap.get("ghost").requested.milli_cpu == 100


def test_host_port_conflicts():
    from kubernetes_tpu.backend.node_info import HostPortInfo

    h = HostPortInfo()
    h.add("", "TCP", 8080)
    assert h.conflicts("", "TCP", 8080)
    assert h.conflicts("10.0.0.1", "TCP", 8080)  # wildcard clashes with any ip
    assert not h.conflicts("", "UDP", 8080)
    assert not h.conflicts("", "TCP", 8081)
    h2 = HostPortInfo()
    h2.add("10.0.0.1", "TCP", 443)
    assert h2.conflicts("0.0.0.0", "TCP", 443)
    assert h2.conflicts("10.0.0.1", "TCP", 443)
    assert not h2.conflicts("10.0.0.2", "TCP", 443)
    h2.remove("10.0.0.1", "TCP", 443)
    assert not h2.conflicts("0.0.0.0", "TCP", 443)


def test_incremental_device_push_matches_full_upload():
    """After incremental syncs, the scattered device buffers must equal a
    fresh full pack (the device half of UpdateSnapshot integrity,
    cache.go:266-277 snapshot-recovery invariant)."""
    import numpy as np

    from kubernetes_tpu.backend.mirror import Mirror
    from kubernetes_tpu.models.testbed import build_cluster, make_node, make_pod
    from kubernetes_tpu.ops.features import Capacities

    caps = Capacities(nodes=32, pods=64)
    cache, snap, mirror = build_cluster(10, caps=caps)
    _ = mirror.to_blobs()  # first full upload
    # churn: add pods, remove a node, add a node
    for i in range(5):
        p = make_pod(i)
        p.spec.node_name = f"node-{i}"
        cache.add_pod(p)
    cache.remove_node(cache._nodes["node-7"].info.node)
    cache.add_node(make_node(20))
    cache.update_snapshot(snap)
    mirror.sync(snap)
    blobs = mirror.to_blobs()  # incremental scatter path
    np.testing.assert_array_equal(np.asarray(blobs.node_f32), mirror.node_f32)
    np.testing.assert_array_equal(np.asarray(blobs.node_i32), mirror.node_i32)
    np.testing.assert_array_equal(np.asarray(blobs.pods_i32), mirror.pods_i32)


@pytest.mark.parametrize("dirty", [1, 2, 3, 5, 8, 13, 21, 40, 64])
def test_no_dirty_set_meets_a_scatter_bucket_for_the_first_time(dirty):
    """The first upload of a buffer compiles its row scatter at every pow2
    bucket a launch's dirty set can take (up to SCATTER_WARM_ROWS, here up
    to where a push becomes a full upload), with writes that change
    nothing; after it a push of any such number of dirty rows compiles
    nothing (a bucket first met mid-drain was a compile inside a benchmark
    window, PERF.md 7 fault 3)."""
    import numpy as np

    from kubernetes_tpu.backend.mirror import Mirror, _scatter_rows_jit
    from kubernetes_tpu.models.testbed import build_cluster
    from kubernetes_tpu.ops.features import Capacities

    caps = Capacities(nodes=128, pods=256)      # pushes scatter up to 64 rows
    _cache, _snap, mirror = build_cluster(10, caps=caps)
    blobs = mirror.to_blobs()                   # first upload, and the warm
    for got, host in ((blobs.node_f32, mirror.node_f32),
                      (blobs.node_i32, mirror.node_i32),
                      (blobs.pods_i32, mirror.pods_i32)):
        np.testing.assert_array_equal(np.asarray(got), host)
    compiled = _scatter_rows_jit._cache_size()
    rows = list(range(60, 60 + dirty))
    mirror.node_f32[rows, 0] = 7.0
    mirror.pods_i32[rows, 0] = 7
    mirror._dirty_rows.update(rows)
    mirror._dirty_slots.update(rows)
    blobs = mirror.to_blobs()
    assert _scatter_rows_jit._cache_size() == compiled
    np.testing.assert_array_equal(np.asarray(blobs.node_f32), mirror.node_f32)
    np.testing.assert_array_equal(np.asarray(blobs.pods_i32), mirror.pods_i32)
    # a second mirror of the same shapes warms from the programs that exist
    Mirror(caps=caps).to_blobs()
    assert _scatter_rows_jit._cache_size() == compiled


def test_scatter_warm_stops_at_its_bound(monkeypatch):
    """Buckets over SCATTER_WARM_ROWS are left to the push that needs one:
    with the bound at 8 a fresh mirror compiles four buckets a buffer, a
    push of 5 dirty rows compiles nothing and a push of 13 its own."""
    from kubernetes_tpu.backend import mirror as mirror_mod
    from kubernetes_tpu.ops.features import Capacities

    monkeypatch.setattr(mirror_mod, "SCATTER_WARM_ROWS", 8)
    before = mirror_mod._scatter_rows_jit._cache_size()
    m = mirror_mod.Mirror(caps=Capacities(nodes=320, pods=640))  # own shapes
    m.to_blobs()
    warmed = mirror_mod._scatter_rows_jit._cache_size()
    assert warmed - before == 3 * 4             # 1, 2, 4, 8 rows; 3 buffers
    for dirty, grew in ((5, 0), (13, 3)):
        m._dirty_rows.update(range(dirty))
        m._dirty_slots.update(range(dirty))
        m.to_blobs()
        assert mirror_mod._scatter_rows_jit._cache_size() - warmed == grew


def test_cache_comparer_against_hub():
    """backend/cache/debugger/comparer.go CompareNodes/ComparePods."""
    from kubernetes_tpu.hub import Hub

    hub = Hub()
    cache = Cache()
    n = mknode("n0")
    hub.create_node(n)
    cache.add_node(n)
    p = mkpod("p", node="n0")
    hub.create_pod(p)
    cache.add_pod(p)
    assert cache.compare_with_hub(hub) == [], "consistent views"
    # a node the cache never learned about
    hub.create_node(mknode("n1"))
    problems = cache.compare_with_hub(hub)
    assert any("n1 in apiserver but not in cache" in s for s in problems)
    cache.add_node(mknode("n1"))
    # a pod bound in the hub the cache missed
    q = mkpod("q", node="n1")
    hub.create_pod(q)
    problems = cache.compare_with_hub(hub)
    assert any("bound in apiserver but not in cache" in s
               for s in problems)
    # assumed pods lead the API: not a discrepancy
    cache.add_pod(q)
    a = mkpod("a")
    assumed = a.clone()
    assumed.spec.node_name = "n0"
    cache.assume_pod(assumed)
    assert cache.compare_with_hub(hub) == []


def _term_pod(name, node, kind, app):
    """A pod on ``node`` labelled app=<app>, carrying a required term of
    ``kind`` ("affinity", "anti" or None) over app=<app>."""
    pod = mkpod(name, node)
    pod.metadata.labels = {"app": app}
    term = PodAffinityTerm(topology_key="kubernetes.io/hostname",
                           label_selector=LabelSelector(
                               match_labels={"app": app}))
    if kind == "affinity":
        pod.spec.affinity = Affinity(pod_affinity=PodAffinity(
            required=[term]))
    elif kind == "anti":
        pod.spec.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(
            required=[term]))
    return pod


def _snapshot_view(snap):
    """What a refreshed snapshot holds, by value: the list's name order,
    and per node its generation, requests and pods."""
    def info(ni):
        return (ni.generation, ni.requested.milli_cpu, ni.requested.memory,
                [pi.pod.metadata.uid for pi in ni.pods],
                [pi.pod.metadata.uid for pi in ni.pods_with_affinity],
                [pi.pod.metadata.uid
                 for pi in ni.pods_with_required_anti_affinity])
    return ([ni.name for ni in snap.node_info_list],
            {name: info(ni) for name, ni in snap.node_info_map.items()},
            snap.namespaces)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_incremental_snapshot_matches_a_fresh_one(seed):
    """A seeded random run of node adds, updates (zone moves among them)
    and removals (of nodes that still hold pods too), pods with and
    without (anti-)affinity terms, assumes, confirmations, forgets and
    namespace changes. After every refresh the incrementally refreshed
    snapshot equals a fresh snapshot refreshed once; its list entries are
    its map's objects at the positions node_index names; changed_nodes
    is None after a node add or removal, else the nodes whose NodeInfo
    moved; and a list taken before the refresh is left as it was."""
    import random

    rng = random.Random(seed)
    c = Cache()
    snap = Snapshot()
    nodes: dict[str, Node] = {}
    assumed: dict[str, Pod] = {}
    bound: dict[str, Pod] = {}
    names = [f"n{i}" for i in range(10)]
    zones = ["za", "zb", "zc"]
    for step in range(300):
        op = rng.choice(["add_node", "update_node", "remove_node",
                         "assume", "assume", "assume", "confirm", "forget",
                         "remove_pod", "namespace"])
        set_moved = False
        if op == "add_node":
            name = rng.choice(names)
            if name not in nodes:
                nodes[name] = mknode(name, zone=rng.choice(zones))
                c.add_node(nodes[name])
                set_moved = True
        elif op == "update_node" and nodes:
            name = rng.choice(sorted(nodes))
            new = mknode(name, zone=rng.choice(zones),
                         cpu=rng.choice(["4", "8"]))
            c.update_node(nodes[name], new)
            nodes[name] = new
        elif op == "remove_node" and nodes:
            name = rng.choice(sorted(nodes))
            c.remove_node(nodes.pop(name))
            set_moved = True
        elif op == "assume":
            # now and then on a node the cache has not seen (yet)
            node = rng.choice(names)
            pod = _term_pod(f"p{step}", node,
                            rng.choice([None, None, "affinity", "anti"]),
                            rng.choice(["web", "db"]))
            c.assume_pod(pod)
            assumed[pod.metadata.uid] = pod
        elif op == "confirm" and assumed:
            pod = assumed.pop(rng.choice(sorted(assumed)))
            c.add_pod(pod)
            bound[pod.metadata.uid] = pod
        elif op == "forget" and assumed:
            c.forget_pod(assumed.pop(rng.choice(sorted(assumed))))
        elif op == "remove_pod" and bound:
            c.remove_pod(bound.pop(rng.choice(sorted(bound))))
        elif op == "namespace":
            if rng.random() < 0.5:
                c.set_namespace(rng.choice(["ns-a", "ns-b"]),
                                {"team": rng.choice(["x", "y"])})
            else:
                c.remove_namespace(rng.choice(["ns-a", "ns-b"]))

        held = snap.node_info_list
        held_items = list(held)
        before = {n: ni.generation for n, ni in snap.node_info_map.items()}
        version = snap.version
        c.update_snapshot(snap)
        fresh = Snapshot()
        c.update_snapshot(fresh)

        assert len(held) == len(held_items)
        assert all(a is b for a, b in zip(held, held_items)), \
            "a published list was written in place"
        assert _snapshot_view(snap) == _snapshot_view(fresh)
        assert snap.node_index == {ni.name: i for i, ni
                                   in enumerate(snap.node_info_list)}
        assert all(snap.node_info_map[ni.name] is ni
                   for ni in snap.node_info_list)
        if snap.version == version:
            # nothing changed: the no-change path left everything
            assert {n: ni.generation for n, ni
                    in snap.node_info_map.items()} == before
            continue
        if set_moved:
            assert snap.changed_nodes is None
        if snap.changed_nodes is not None:
            moved = {n for n, ni in snap.node_info_map.items()
                     if before.get(n) != ni.generation}
            assert set(snap.changed_nodes) == moved
            assert set(snap.node_info_map) == set(before)


def test_snapshot_stats_count_refreshes_by_path():
    """A pod-only change takes the delta path and re-clones its node
    alone; a node add takes the full path; a refresh with nothing new is
    a noop."""
    c = Cache()
    snap = Snapshot()
    for i in range(4):
        c.add_node(mknode(f"n{i}"))
    c.update_snapshot(snap)
    assert c.snapshot_stats() == {"noop": 0, "delta": 0, "full": 1,
                                  "cloned": 4}
    c.update_snapshot(snap)
    assert c.snapshot_stats()["noop"] == 1
    c.assume_pod(mkpod("p1", "n2"))
    c.update_snapshot(snap)
    stats = c.snapshot_stats()
    assert (stats["delta"], stats["full"], stats["cloned"]) == (1, 1, 5)
    assert snap.changed_nodes == ["n2"]
    c.add_node(mknode("n4"))
    c.update_snapshot(snap)
    stats = c.snapshot_stats()
    assert (stats["delta"], stats["full"], stats["cloned"]) == (1, 2, 6)
    assert snap.changed_nodes is None
    assert snap.index_of("n4") == snap.num_nodes() - 1
    assert snap.index_of("gone") == -1


# suite-tier discipline (tests/test_markers.py): area marker
pytestmark = pytest.mark.core
