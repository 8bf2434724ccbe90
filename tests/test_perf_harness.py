"""Perf harness unit tests (tiny scales, fake-free real clock): op DSL
execution, collector windowing/percentiles, churn injection — the rung
the reference covers with scheduler_perf's own integration-test label
(misc/performance-config.yaml workloads labeled integration-test run
tiny through the same driver)."""

from kubernetes_tpu.perf.collector import ThroughputCollector, percentile
from kubernetes_tpu.perf.harness import (
    Churn,
    CreateNodes,
    CreatePods,
    Workload,
    run_workload,
)
from kubernetes_tpu.perf.workloads import (
    ALL_WORKLOADS,
    _anti_affinity_pod,
    _node,
    _pod,
    preemption_async,
    scheduling_basic,
)


def small(w: Workload) -> Workload:
    w.node_capacity = 64
    w.pod_capacity = 256
    w.batch_size = 16
    return w


def test_percentile_nearest_rank():
    vals = sorted([10.0, 20.0, 30.0, 40.0])
    assert percentile(vals, 50) == 20.0
    assert percentile(vals, 99) == 40.0
    assert percentile([], 50) == 0.0


def test_collector_windows():
    t = [0.0]
    col = ThroughputCollector({"a", "b", "c"}, now=lambda: t[0])
    col.begin()

    class P:
        def __init__(self, uid, node):
            self.metadata = type("M", (), {"uid": uid})()
            self.spec = type("S", (), {"node_name": node})()

    col.on_update(None, P("a", "n1"))
    t[0] = 0.5
    col.on_update(None, P("b", "n1"))
    t[0] = 1.5
    col.on_update(None, P("c", "n1"))
    assert col.done()
    s = col.summarize(end=2.0)
    assert s.pods_scheduled == 3
    assert s.windows == [2, 1]
    assert s.pods_per_sec == 3 / 2.0


def test_scheduling_basic_tiny():
    w = small(scheduling_basic(init_nodes=4, init_pods=2, measure_pods=10))
    r = run_workload(w)
    assert r["pods_scheduled"] == 10
    assert r["stats"]["scheduled"] == 12
    assert r["pods_per_sec"] > 0


def test_all_workload_defs_have_thresholds():
    for factory in ALL_WORKLOADS:
        w = factory()
        assert w.threshold > 0
        assert w.ops, w.name


def test_preemption_tiny_evicts_and_schedules():
    # 2 nodes x 4 low-priority 900m fillers; churn interval so large no
    # churn pod fires; measured pods fit in the 400m leftover
    w = small(preemption_async(init_nodes=2, init_pods=8, measure_pods=4))
    r = run_workload(w)
    assert r["pods_scheduled"] == 4


def test_churn_injects_by_clock():
    # a churn op + measured pods that need the churn pod NOT to exist:
    # verify injection happens on the interval clock
    t = [1000.0]

    def now():
        return t[0]

    def sleep(dt):
        t[0] += dt

    w = small(Workload(
        name="churn-test", threshold=1,
        ops=[
            CreateNodes(2, _node),
            Churn([lambda i: _pod(f"c{i}")], interval_ms=100),
            CreatePods(5, lambda i: _pod(f"m-{i}"), collect_metrics=True),
        ]))
    r = run_workload(w, now=now, sleep=sleep)
    assert r["pods_scheduled"] == 5
    # time passed during the drain => at least one churn pod was created
    # (created beyond the 5 measured + any init)
    assert r["stats"]["attempts"] >= 5


def test_anti_affinity_workload_tiny():
    from kubernetes_tpu.perf.workloads import scheduling_pod_anti_affinity

    w = small(scheduling_pod_anti_affinity(
        init_nodes=6, init_pods=2, measure_pods=3))
    r = run_workload(w)
    # 6 hosts, 5 green pods with hostname anti-affinity: all schedule
    assert r["pods_scheduled"] == 3
    assert r["stats"]["unschedulable"] == 0


def test_anti_affinity_pod_template():
    p = _anti_affinity_pod(0, "sched-1")
    assert p.metadata.namespace == "sched-1"
    terms = p.spec.affinity.pod_anti_affinity.required
    assert terms[0].namespaces == ["sched-1", "sched-0"]


def test_unschedulable_workload_tiny():
    """Parked unschedulable churn pods must not block the measured flow."""
    from kubernetes_tpu.perf.workloads import unschedulable

    w = small(unschedulable(init_nodes=4, init_pods=2, measure_pods=10))
    r = run_workload(w)
    assert r["pods_scheduled"] == 10


def test_mixed_churn_workload_tiny():
    from kubernetes_tpu.perf.workloads import mixed_churn

    w = small(mixed_churn(init_nodes=4, measure_pods=10))
    r = run_workload(w)
    assert r["pods_scheduled"] == 10


def test_churn_recreate_keeps_one_alive():
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.perf.harness import Churn, _ChurnState

    t = [1000.0]
    hub = Hub()
    st = _ChurnState(Churn([lambda i: _pod(f"c{i}")], interval_ms=100,
                           mode="recreate"), now=lambda: t[0])
    t[0] = 1000.55
    st.inject(hub, t[0])
    assert len(hub.list_pods()) == 1, "recreate keeps exactly one copy"


def test_daemonset_workload_tiny():
    from kubernetes_tpu.perf.workloads import scheduling_daemonset

    w = small(scheduling_daemonset(init_nodes=6, measure_pods=6))
    w.warm_full_nodes = False
    r = run_workload(w)
    assert r["pods_scheduled"] == 6
    # daemonset pinning: pod i landed exactly on node-i (matchFields)
    assert r["stats"]["scheduled"] == 6


def test_while_gated_workload_tiny():
    from kubernetes_tpu.perf.workloads import scheduling_while_gated

    w = small(scheduling_while_gated(gated_pods=8, measure_pods=10))
    r = run_workload(w)
    # measured pods all bound; gated pods parked, never scheduled
    assert r["pods_scheduled"] == 10
    assert r["stats"]["scheduled"] == 10
    assert r["stats"]["unschedulable"] == 0


def test_preferred_affinity_workloads_tiny():
    from kubernetes_tpu.perf.workloads import (
        preferred_pod_affinity,
        preferred_pod_anti_affinity,
    )

    for factory in (preferred_pod_affinity, preferred_pod_anti_affinity):
        w = small(factory(init_nodes=6, init_pods=2, measure_pods=8))
        r = run_workload(w)
        assert r["pods_scheduled"] == 8, w.name
        assert r["stats"]["unschedulable"] == 0


def test_ns_selector_anti_affinity_tiny():
    from kubernetes_tpu.perf.workloads import ns_selector_anti_affinity

    w = small(ns_selector_anti_affinity(init_nodes=8, init_pods=3,
                                        measure_pods=5, namespaces=2))
    w.warm_full_nodes = False
    r = run_workload(w)
    # hostname anti-affinity across ns-selected namespaces: all 8 pods
    # must land on distinct nodes
    assert r["pods_scheduled"] == 5
    assert r["stats"]["scheduled"] == 8


def test_dra_steady_state_tiny():
    from kubernetes_tpu.perf.workloads import dra_steady_state

    w = small(dra_steady_state(init_nodes=4, measure_pods=6))
    r = run_workload(w)
    assert r["pods_scheduled"] == 6
    assert r["stats"]["unschedulable"] == 0


def test_dra_cel_in_tiny():
    """The CEL `in` membership variant: half the fleet's devices match
    the selector, every pod still places (device allocator path)."""
    from kubernetes_tpu.perf.workloads import dra_steady_state_cel_in

    w = small(dra_steady_state_cel_in(init_nodes=4, measure_pods=6))
    r = run_workload(w)
    assert r["pods_scheduled"] == 6
    assert r["stats"]["unschedulable"] == 0


def test_dra_multi_request_tiny():
    """The two-request claim variant: 3 devices per pod across a class
    match + an attribute selector, greedy multi-request walk."""
    from kubernetes_tpu.perf.workloads import dra_multi_request

    w = small(dra_multi_request(init_nodes=4, measure_pods=6))
    r = run_workload(w)
    assert r["pods_scheduled"] == 6
    assert r["stats"]["unschedulable"] == 0


def test_run_workload_profile_breakdown():
    """The result carries the flight recorder's per-phase p50/p99 (incl.
    the dra_* views when DRA plugins ran) and the host-tail share."""
    w = small(scheduling_basic(init_nodes=4, init_pods=2, measure_pods=10))
    r = run_workload(w)
    fl = r["flight"]
    assert fl["enabled"] and fl["cycles_recorded"] >= 1
    for phase in ("queue_pop", "device_launch", "commit"):
        assert phase in fl["phases"], phase
        assert fl["phases"][phase]["count"] >= 1
        assert fl["phases"][phase]["p99_ms"] >= fl["phases"][phase]["p50_ms"]
    assert fl["plugins"], "per-plugin timings present"
    assert 0.0 <= fl["host_tail_share"] <= 1.0


def test_qhints_variant_tiny():
    from kubernetes_tpu.perf.workloads import scheduling_basic_qhints

    w = small(scheduling_basic_qhints(init_nodes=4, init_pods=2,
                                      measure_pods=10))
    assert w.feature_gates == {"SchedulerQueueingHints": True}
    r = run_workload(w)
    assert r["pods_scheduled"] == 10


def test_preemption_async_enabled_variant_tiny():
    from kubernetes_tpu.perf.workloads import preemption_async_enabled

    w = small(preemption_async_enabled(init_nodes=2, init_pods=8,
                                       measure_pods=4))
    assert w.feature_gates == {"SchedulerAsyncPreemption": True}
    r = run_workload(w)
    assert r["pods_scheduled"] == 4


def test_ns_selector_preferred_anti_affinity_tiny():
    from kubernetes_tpu.perf.workloads import (
        ns_selector_preferred_anti_affinity,
    )

    w = small(ns_selector_preferred_anti_affinity(
        init_nodes=8, init_pods=3, measure_pods=5, namespaces=2))
    w.warm_full_nodes = False
    r = run_workload(w)
    # PREFERRED anti-affinity: soft avoidance only, everything schedules
    assert r["pods_scheduled"] == 5
    assert r["stats"]["unschedulable"] == 0


def test_gang_topology_packing_tiny():
    """The co-location workload's validate hook passes under the device
    packer: every gang lands in ONE zone (ISSUE-12 acceptance)."""
    from kubernetes_tpu.perf.workloads import gang_topology_packing

    w = small(gang_topology_packing(init_nodes=16, zones=4, gangs=3))
    w.batch_size = 64       # a gang unit must fit one pop batch
    r = run_workload(w)
    col = r["colocation"]
    assert col["gangs"] == 3
    assert col["mean_zone_spans"] == 1.0
    assert r["gangs"]["device_admitted"] == 3


def test_gang_topology_packing_validate_rejects_scatter():
    """The validate hook is a real gate: a scattered placement raises."""
    from kubernetes_tpu.api.objects import (
        LABEL_POD_GROUP,
        LABEL_ZONE,
    )
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.perf.workloads import _colocation_validate
    from kubernetes_tpu.testing import MakeNode, MakePod

    hub = Hub()
    for i in range(4):
        n = MakeNode().name(f"n{i}").capacity(cpu="4", memory="8Gi",
                                              pods="10").obj()
        n.metadata.labels[LABEL_ZONE] = f"z{i}"
        hub.create_node(n)
    for i in range(4):
        p = MakePod().name(f"m{i}").req(cpu="100m").obj()
        p.metadata.labels[LABEL_POD_GROUP] = "scattered"
        hub.create_pod(p)
        hub.bind(p, f"n{i}")
    with pytest.raises(AssertionError):
        _colocation_validate(hub, {})


def test_measurement_path_refuses_a_device_fallback():
    """The containment ladder is right for a daemon; on a measurement
    path it is a silent CPU fallback. A run in which a batch degraded to
    the host path still binds every pod — and must be refused, naming
    the contained exception."""
    from kubernetes_tpu.chaos import DeviceChaos, DeviceChaosConfig
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.perf.harness import DeviceFallback, assert_device_path
    from kubernetes_tpu.scheduler import Scheduler

    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    try:
        for i in range(4):
            hub.create_node(_node(i))
        pods = [_pod(f"p-{i}") for i in range(8)]
        for p in pods:
            hub.create_pod(p)
        sched.fault_injector = DeviceChaos(DeviceChaosConfig(
            seed=1, launch_error_rate=1.0))
        sched.run_until_idle()
    finally:
        sched.close()
    # the host path carried the batch: nothing LOOKS wrong from outside
    assert all(hub.get_pod(p.metadata.uid).spec.node_name for p in pods)
    assert sched.stats["device_fallbacks"] >= 1
    with pytest.raises(DeviceFallback) as e:
        assert_device_path(sched)
    assert sched.last_device_fault in str(e.value)
    # a clean run passes
    clean = Scheduler(Hub(), cfg, caps=Capacities(nodes=16, pods=64))
    clean.close()
    assert_device_path(clean)


# suite-tier discipline (tests/test_markers.py): area marker
import pytest  # noqa: E402
pytestmark = pytest.mark.perf
