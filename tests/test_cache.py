import pytest

from kubernetes_tpu.api.objects import (
    LABEL_ZONE,
    Container,
    Node,
    NodeStatus,
    ObjectMeta,
    Pod,
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


# suite-tier discipline (tests/test_markers.py): area marker
pytestmark = pytest.mark.core
