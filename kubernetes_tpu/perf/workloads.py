"""The reference scheduler_perf workloads, mirroring performance-config
shapes (node/pod templates from test/integration/scheduler_perf/templates;
op sequences and thresholds from the per-suite performance-config.yaml).
Every thresholded row of BASELINE.md is implemented — the 5 BASELINE.json
headliners plus the affinity suite (required/preferred, NSSelector
variants, MixedSchedulingBasePod, gated-with-affinity), the topology
suite (required/preferred spreading, node-inclusion policy), churn,
daemonset, gated, unschedulable (hints on/off), DRA steady state
(direct claims + claim templates with CEL selectors), and the
feature-gate variants (QueueingHints, AsyncPreemption, preferred
NSSelector anti-affinity) — 25 configs; ``perf/run_one.py`` runs one by
its function's name.

Node template (node-default.yaml): cpu 4, memory 32Gi, pods 110.
Pod template (pod-default.yaml): requests cpu 100m, memory 500Mi.
"""

from __future__ import annotations

from kubernetes_tpu.api.objects import (
    Affinity,
    Container,
    LABEL_HOSTNAME,
    LABEL_POD_GROUP,
    LABEL_QUEUE,
    LABEL_ZONE,
    LabelSelector,
    Node,
    NodeAffinity,
    NodeSelector,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    PodGroup,
    PodSpec,
    ResourceRequirements,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)
from kubernetes_tpu.perf.harness import (
    Churn,
    CreateNamespaces,
    CreateNodes,
    CreateObjects,
    CreatePods,
    Workload,
)


def _node(i: int, zones: list[str] | None = None) -> Node:
    """node-default.yaml + labelNodePrepareStrategy zone labels."""
    name = f"node-{i}"
    labels = {LABEL_HOSTNAME: name}
    if zones:
        labels[LABEL_ZONE] = zones[i % len(zones)]
    return Node(
        metadata=ObjectMeta(name=name, labels=labels),
        spec=NodeSpec(),
        status=NodeStatus(allocatable={
            "cpu": "4", "memory": "32Gi", "pods": "110"}))


def _pod(name: str, cpu: str = "100m", mem: str = "500Mi",
         namespace: str = "default", labels: dict | None = None,
         affinity: Affinity | None = None, tsc: list | None = None,
         priority: int | None = None) -> Pod:
    # cpu/mem "0" = a request-less pod (pod-with-label.yaml: fit consumes
    # only a pod slot; scoring sees the NonZeroRequested defaults)
    requests = {}
    if cpu != "0":
        requests["cpu"] = cpu
    if mem != "0":
        requests["memory"] = mem
    return Pod(
        metadata=ObjectMeta(name=name, namespace=namespace,
                            labels=labels or {}),
        spec=PodSpec(
            containers=[Container(
                name="pause",
                resources=ResourceRequirements(requests=requests))],
            affinity=affinity,
            topology_spread_constraints=tsc or [],
            priority=priority))


# ------------------------------------------------- 1. SchedulingBasic
# misc/performance-config.yaml:40-66 (5000Nodes_10000Pods, threshold 270)

def scheduling_basic(init_nodes=5000, init_pods=1000,
                     measure_pods=10000) -> Workload:
    return Workload(
        name="SchedulingBasic/5000Nodes_10000Pods",
        threshold=270,
        batch_size=4096,   # auction path: bigger launches amortize better
        ops=[
            CreateNodes(init_nodes, _node),
            CreatePods(init_pods, lambda i: _pod(f"init-{i}")),
            CreatePods(measure_pods, lambda i: _pod(f"measure-{i}"),
                       collect_metrics=True),
        ])


# ------------------------------------------- 2. SchedulingNodeAffinity
# affinity/performance-config.yaml:280-330 (5000Nodes_10000Pods, 220):
# nodes labeled zone1; measured pods require zone In [zone1, zone2]
# (pod-with-node-affinity.yaml); scoring includes BalancedAllocation via
# the default plugin set.

def _node_affinity_pod(i: int) -> Pod:
    aff = Affinity(node_affinity=NodeAffinity(required=NodeSelector(
        node_selector_terms=[NodeSelectorTerm(match_expressions=[
            NodeSelectorRequirement(key=LABEL_ZONE, operator="In",
                                    values=["zone1", "zone2"])])])))
    return _pod(f"na-{i}", affinity=aff)


def scheduling_node_affinity(init_nodes=5000, init_pods=5000,
                             measure_pods=10000) -> Workload:
    return Workload(
        name="SchedulingNodeAffinity/5000Nodes_10000Pods",
        threshold=220,
        pod_capacity=32768,
        batch_size=4096,   # auction path
        ops=[
            CreateNodes(init_nodes, lambda i: _node(i, zones=["zone1"])),
            CreatePods(init_pods, lambda i: _pod(f"init-{i}")),
            CreatePods(measure_pods, _node_affinity_pod,
                       collect_metrics=True),
        ])


# --------------------------------------- 3. SchedulingPodAntiAffinity
# affinity/performance-config.yaml:20-70 (5000Nodes_2000Pods, 60):
# 2 namespaces; pods labeled color=green with required hostname
# anti-affinity across both namespaces
# (pod-with-pod-anti-affinity.yaml).

def _anti_affinity_pod(i: int, ns: str) -> Pod:
    aff = Affinity(pod_anti_affinity=PodAntiAffinity(required=[
        PodAffinityTerm(
            topology_key=LABEL_HOSTNAME,
            label_selector=LabelSelector(match_labels={"color": "green"}),
            namespaces=["sched-1", "sched-0"])]))
    return _pod(f"anti-{ns}-{i}", namespace=ns,
                labels={"color": "green"}, affinity=aff)


def scheduling_pod_anti_affinity(init_nodes=5000, init_pods=1000,
                                 measure_pods=2000) -> Workload:
    return Workload(
        name="SchedulingPodAntiAffinity/5000Nodes_2000Pods",
        threshold=60,
        warm_full_nodes=True,   # hostname anti-affinity: domains = nodes
        ops=[
            CreateNodes(init_nodes, _node),
            CreateNamespaces("sched", 2),
            CreatePods(init_pods,
                       lambda i: _anti_affinity_pod(i, "sched-0")),
            CreatePods(measure_pods,
                       lambda i: _anti_affinity_pod(i, "sched-1"),
                       collect_metrics=True),
        ])


# ------------------------------------------- 4. TopologySpreading
# topology_spreading/performance-config.yaml:21-70 (5000Nodes_5000Pods,
# 85): nodes across 3 zones; measured pods spread maxSkew=5 on zone
# (pod-with-topology-spreading.yaml).

def _spreading_pod(i: int) -> Pod:
    tsc = [TopologySpreadConstraint(
        max_skew=5, topology_key=LABEL_ZONE,
        when_unsatisfiable="DoNotSchedule",
        label_selector=LabelSelector(match_labels={"color": "blue"}))]
    return _pod(f"spread-{i}", labels={"color": "blue"}, tsc=tsc)


def topology_spreading(init_nodes=5000, init_pods=5000,
                       measure_pods=5000) -> Workload:
    return Workload(
        name="TopologySpreading/5000Nodes_5000Pods",
        threshold=85,
        pod_capacity=32768,
        ops=[
            CreateNodes(init_nodes, lambda i: _node(
                i, zones=["moon-1", "moon-2", "moon-3"])),
            CreatePods(init_pods, lambda i: _pod(f"init-{i}")),
            CreatePods(measure_pods, _spreading_pod, collect_metrics=True),
        ])


# ------------------------------------------- 5. PreemptionAsync
# misc/performance-config.yaml:195-250 (5000Nodes, 160): 20k low-priority
# 900m fillers (4 per 4-CPU node), churn creating a 3000m priority-10 pod
# every 200ms (each must preempt 3 fillers), 5000 always-schedulable
# 100m measured pods.

def _low_priority_pod(i: int) -> Pod:
    return _pod(f"low-{i}", cpu="900m", mem="500Mi")


def _high_priority_pod(i: int) -> Pod:
    return _pod(f"high-{i}", cpu="3000m", mem="500Mi", priority=10)


def preemption_async(init_nodes=5000, init_pods=20000,
                     measure_pods=5000) -> Workload:
    return Workload(
        name="PreemptionAsync/5000Nodes",
        threshold=160,
        pod_capacity=32768,
        ops=[
            CreateNodes(init_nodes, _node),
            CreatePods(init_pods, _low_priority_pod),
            Churn([_high_priority_pod], interval_ms=200),
            CreatePods(measure_pods, lambda i: _pod(f"measure-{i}"),
                       collect_metrics=True),
        ])


# ------------------------------------------- 6. Unschedulable
# misc/performance-config.yaml:280+ (5kNodes/100Init/10kPods, 140): a
# 200ms churn of 9-CPU high-priority pods that can NEVER fit a 4-CPU node
# parks in the unschedulable pool; the measured default pods must flow
# past them (the queueing-hint discipline this workload exists to test).

def _large_cpu_pod(i: int) -> Pod:
    return _pod(f"big-{i}", cpu="9", mem="500Mi", priority=10)


def unschedulable(init_nodes=5000, init_pods=100,
                  measure_pods=10000) -> Workload:
    return Workload(
        name="Unschedulable/5kNodes_100Init_10kPods",
        threshold=140,
        # the 140 floor is the reference's hints-OFF row
        # (misc/performance-config.yaml:315); the QHints variant re-enables
        feature_gates={"SchedulerQueueingHints": False},
        ops=[
            CreateNodes(init_nodes, _node),
            CreatePods(init_pods, lambda i: _pod(f"init-{i}")),
            Churn([_large_cpu_pod], interval_ms=200),
            CreatePods(measure_pods, lambda i: _pod(f"measure-{i}"),
                       collect_metrics=True),
        ])


# ------------------------------------- 7. SchedulingWithMixedChurn
# misc/performance-config.yaml:360+ (5000Nodes_10000Pods, 265): a 1s
# recreate-churn of {node, unschedulable high-priority pod} while 10k
# default pods schedule (the reference's template set also recreates a
# Service, which has no scheduler-visible effect here).

def _churn_node(i: int) -> object:
    return _node(100000 + i)


def mixed_churn(init_nodes=5000, measure_pods=10000) -> Workload:
    return Workload(
        name="SchedulingWithMixedChurn/5000Nodes_10000Pods",
        # under pipelined waves the churn patches the device-resident
        # free/nzr chain across the 1s recreate-churn (no whole-chain
        # invalidation + resync); the measured phase must not recompile
        threshold=265,
        ops=[
            CreateNodes(init_nodes, _node),
            Churn([_churn_node, _large_cpu_pod], interval_ms=1000,
                  mode="recreate"),
            CreatePods(measure_pods, lambda i: _pod(f"measure-{i}"),
                       collect_metrics=True),
        ])


# --------------------------------------------- 8. SchedulingDaemonset
# misc/performance-config.yaml:100-128 (15000Nodes, 390): one pod per node,
# pinned the way the daemonset controller pins them — a required
# nodeAffinity matchFields term on metadata.name (the scheduler still runs
# the full pipeline; NodeAffinity's PreFilter narrows to the one node).

def _daemonset_pod(i: int) -> Pod:
    aff = Affinity(node_affinity=NodeAffinity(required=NodeSelector(
        node_selector_terms=[NodeSelectorTerm(match_fields=[
            NodeSelectorRequirement(key="metadata.name", operator="In",
                                    values=[f"node-{i}"])])])))
    return _pod(f"ds-{i}", cpu="100m", mem="200Mi", affinity=aff)


def scheduling_daemonset(init_nodes=15000, measure_pods=15000) -> Workload:
    return Workload(
        name="SchedulingDaemonset/15000Nodes",
        threshold=390,
        node_capacity=16384,
        pod_capacity=32768,
        ops=[
            CreateNodes(init_nodes, _node),
            CreatePods(measure_pods, _daemonset_pod,
                       collect_metrics=True),
        ],
        # matchFields pin per pod: every pod is its own topology-free spec;
        # warmup must see the same node bucket so the full-size node table
        # compiles up front
        warm_full_nodes=True)


# ------------------------------------------- 9. SchedulingWhileGated
# misc/performance-config.yaml:425-460 (1Node_10000GatedPods, 130): 10k
# permanently gated pods park in unschedulablePods; 10k plain pods then
# schedule onto one huge node — measures that the gated pool costs the
# hot path nothing (PreEnqueue gate + no requeue events).

def _gated_pod(i: int) -> Pod:
    from kubernetes_tpu.api.objects import PodSchedulingGate

    p = _pod(f"gated-{i}", cpu="1m", mem="1Mi")
    p.spec.scheduling_gates = [PodSchedulingGate(name="example.com/hold")]
    return p


def _big_node(i: int) -> Node:
    name = f"node-{i}"
    return Node(
        metadata=ObjectMeta(name=name, labels={LABEL_HOSTNAME: name}),
        spec=NodeSpec(),
        status=NodeStatus(allocatable={
            "cpu": "4000", "memory": "64Ti", "pods": "30000"}))


def scheduling_while_gated(gated_pods=10000, measure_pods=10000) -> Workload:
    return Workload(
        name="SchedulingWhileGated/1Node_10000GatedPods",
        threshold=130,
        node_capacity=64,
        pod_capacity=32768,
        ops=[
            CreateNodes(1, _big_node),
            CreatePods(gated_pods, _gated_pod, wait=False),
            CreatePods(measure_pods, lambda i: _pod(f"measure-{i}",
                                                    cpu="1m", mem="1Mi"),
                       collect_metrics=True),
        ])


# -------------------------------- 10/11. Preferred pod (anti)affinity
# affinity/performance-config.yaml:141-198 / :204-261
# (SchedulingPreferredPodAffinity / ...AntiAffinity, 5000Nodes_5000Pods,
# both 90): soft zone-level terms — pure Score work, the weighted
# preferred-term kernel (scoring.go:35) rather than the Filter path.

def _preferred_affinity_pod(i: int, anti: bool) -> Pod:
    term = WeightedPodAffinityTerm(weight=10, pod_affinity_term=(
        PodAffinityTerm(
            topology_key=LABEL_ZONE,
            label_selector=LabelSelector(match_labels={"team": "perf"}))))
    aff = (Affinity(pod_anti_affinity=PodAntiAffinity(preferred=[term]))
           if anti else
           Affinity(pod_affinity=PodAffinity(preferred=[term])))
    kind = "panti" if anti else "paff"
    return _pod(f"{kind}-{i}", labels={"team": "perf"}, affinity=aff)


def preferred_pod_affinity(init_nodes=5000, init_pods=1000,
                           measure_pods=5000) -> Workload:
    return Workload(
        name="SchedulingPreferredPodAffinity/5000Nodes_5000Pods",
        threshold=90,
        pod_capacity=32768,
        ops=[
            CreateNodes(init_nodes,
                        lambda i: _node(i, zones=["z1", "z2", "z3"])),
            CreatePods(init_pods, lambda i: _pod(f"init-{i}")),
            CreatePods(measure_pods,
                       lambda i: _preferred_affinity_pod(i, anti=False),
                       collect_metrics=True),
        ])


def preferred_pod_anti_affinity(init_nodes=5000, init_pods=1000,
                                measure_pods=5000) -> Workload:
    return Workload(
        name="SchedulingPreferredPodAntiAffinity/5000Nodes_5000Pods",
        threshold=90,
        pod_capacity=32768,
        ops=[
            CreateNodes(init_nodes,
                        lambda i: _node(i, zones=["z1", "z2", "z3"])),
            CreatePods(init_pods, lambda i: _pod(f"init-{i}")),
            CreatePods(measure_pods,
                       lambda i: _preferred_affinity_pod(i, anti=True),
                       collect_metrics=True),
        ])


# ------------------- 12. RequiredPodAntiAffinityWithNSSelector
# affinity/performance-config.yaml:425-480 (5000Nodes_2000Pods, 24 — the
# LOWEST floor in the reference's affinity suite): measured pods carry
# required hostname anti-affinity whose namespaceSelector picks out the
# team's namespaces, so the match set spans namespaces selected by LABEL.

def _ns_selector_anti_pod(i: int, ns: str) -> Pod:
    aff = Affinity(pod_anti_affinity=PodAntiAffinity(required=[
        PodAffinityTerm(
            topology_key=LABEL_HOSTNAME,
            label_selector=LabelSelector(match_labels={"color": "teal"}),
            namespace_selector=LabelSelector(
                match_labels={"team": "sched"}))]))
    return _pod(f"nsanti-{ns}-{i}", namespace=ns,
                labels={"color": "teal"}, affinity=aff)


def ns_selector_anti_affinity(init_nodes=5000, init_pods=1000,
                              measure_pods=2000, namespaces=10) -> Workload:
    return Workload(
        name="SchedulingRequiredPodAntiAffinityWithNSSelector"
             "/5000Nodes_2000Pods",
        threshold=24,
        warm_full_nodes=True,   # hostname topology: domains = nodes
        ops=[
            CreateNodes(init_nodes, _node),
            CreateNamespaces("team", namespaces,
                             labels=lambda i: {"team": "sched"}),
            CreatePods(init_pods,
                       lambda i: _ns_selector_anti_pod(
                           i, f"team-{i % namespaces}")),
            CreatePods(measure_pods,
                       lambda i: _ns_selector_anti_pod(
                           i + 10**6, f"team-{i % namespaces}"),
                       collect_metrics=True),
        ])


# --------------------------- 13. DRA steady-state claim scheduling
# dra/performance-config.yaml:60-110 (SteadyStateClusterClaimTemplate,
# ~100 nodes, floor ~50): every node publishes a ResourceSlice of
# devices; each measured pod carries its own single-device ResourceClaim
# which the DynamicResources host plugin allocates at Reserve and
# persists through PreBind — the reference's own accelerator path.

def _dra_node(i: int) -> Node:
    name = f"node-{i}"
    return Node(metadata=ObjectMeta(name=name,
                                    labels={LABEL_HOSTNAME: name}),
                spec=NodeSpec(),
                status=NodeStatus(allocatable={
                    "cpu": "16", "memory": "64Gi", "pods": "110"}))


def _dra_slice(i: int):
    from kubernetes_tpu.api.objects import Device, ResourceSlice

    node = f"node-{i}"
    return ResourceSlice(
        metadata=ObjectMeta(name=f"slice-{node}"),
        node_name=node, driver="tpu.example.com", pool=node,
        devices=[Device(name=f"dev-{d}", device_class_name="tpu")
                 for d in range(8)])


def _dra_claim(i: int):
    from kubernetes_tpu.api.objects import (
        DeviceRequest,
        ResourceClaim,
        ResourceClaimSpec,
    )

    return ResourceClaim(
        metadata=ObjectMeta(name=f"dra-claim-{i}"),
        spec=ResourceClaimSpec(device_requests=[
            DeviceRequest(name="accel", device_class_name="tpu",
                          count=1)]))


def _dra_pod(i: int) -> Pod:
    from kubernetes_tpu.api.objects import PodResourceClaim

    p = _pod(f"dra-{i}", cpu="100m", mem="200Mi")
    p.spec.resource_claims = [PodResourceClaim(
        name="accel", resource_claim_name=f"dra-claim-{i}")]
    return p


def dra_steady_state(init_nodes=100, measure_pods=500) -> Workload:
    return Workload(
        name="DRASteadyState/100Nodes_500Pods",
        threshold=50,
        node_capacity=128,
        pod_capacity=2048,
        batch_size=256,
        ops=[
            CreateNodes(init_nodes, _dra_node),
            CreateObjects(init_nodes, _dra_slice,
                          create_verb="create_resource_slice"),
            CreateObjects(measure_pods, _dra_claim,
                          create_verb="create_resource_claim"),
            CreatePods(measure_pods, _dra_pod, collect_metrics=True),
        ])


# --------------- 13b. DRA steady-state via claim TEMPLATES + CEL
# dra/performance-config.yaml SteadyStateClusterClaimTemplate (+
# resourceclaim-with-selector.yaml): pods reference a
# ResourceClaimTemplate; the resourceclaim controller stamps a per-pod
# claim whose request carries a CEL device selector; the structured
# allocator matches attributes/capacity per device.

def _dra_attr_slice(i: int):
    from kubernetes_tpu.api.objects import Device, ResourceSlice

    node = f"node-{i}"
    return ResourceSlice(
        metadata=ObjectMeta(name=f"slice-{node}"),
        node_name=node, driver="tpu.example.com", pool=node,
        devices=[Device(name=f"dev-{d}",
                        attributes={"preallocate": d % 2 == 0},
                        capacity={"counters": "2"})
                 for d in range(8)])


def _dra_template(i: int):
    from kubernetes_tpu.api.objects import (
        DeviceRequest,
        DeviceSelector,
        ResourceClaimSpec,
        ResourceClaimTemplate,
    )

    expr = ("device.capacity['tpu.example.com'].counters"
            ".compareTo(quantity('2')) >= 0 && "
            "device.attributes['tpu.example.com'].preallocate")
    return ResourceClaimTemplate(
        metadata=ObjectMeta(name="perf-claim-template"),
        spec=ResourceClaimSpec(device_requests=[
            DeviceRequest(name="accel", selectors=[
                DeviceSelector(cel_expression=expr)])]))


def _dra_template_pod(i: int) -> Pod:
    from kubernetes_tpu.api.objects import PodResourceClaim

    p = _pod(f"drat-{i}", cpu="100m", mem="200Mi")
    p.spec.resource_claims = [PodResourceClaim(
        name="accel", resource_claim_template_name="perf-claim-template")]
    return p


def dra_steady_state_templates(init_nodes=100,
                               measure_pods=400) -> Workload:
    return Workload(
        name="DRASteadyStateClaimTemplates/100Nodes_400Pods",
        threshold=40,   # dra/performance-config.yaml:97 (template variant)
        node_capacity=128,
        pod_capacity=2048,
        batch_size=256,
        dra_claim_controller=True,
        ops=[
            CreateNodes(init_nodes, _dra_node),
            CreateObjects(init_nodes, _dra_attr_slice,
                          create_verb="create_resource_slice"),
            CreateObjects(1, _dra_template,
                          create_verb="create_resource_claim_template"),
            CreatePods(measure_pods, _dra_template_pod,
                       collect_metrics=True),
        ])


# --------------- 13c. DRA steady-state with CEL `in` membership
# the first of the previously-unmeasured DRA variants ROADMAP item 1
# sequences behind the batched allocator: the selector corpus's
# membership test (dra/performance-config.yaml's attribute-selector
# shapes) over a heterogeneous device fleet — half the devices match.

def _dra_model_slice(i: int):
    from kubernetes_tpu.api.objects import Device, ResourceSlice

    node = f"node-{i}"
    models = ("v4", "v5e", "v5p", "v6e")
    return ResourceSlice(
        metadata=ObjectMeta(name=f"slice-{node}"),
        node_name=node, driver="tpu.example.com", pool=node,
        devices=[Device(name=f"dev-{d}",
                        attributes={"model": models[d % 4]})
                 for d in range(8)])


def _dra_cel_in_template(i: int):
    from kubernetes_tpu.api.objects import (
        DeviceRequest,
        DeviceSelector,
        ResourceClaimSpec,
        ResourceClaimTemplate,
    )

    expr = ("device.attributes['tpu.example.com'].model"
            " in ['v5e', 'v5p']")
    return ResourceClaimTemplate(
        metadata=ObjectMeta(name="perf-claim-template"),
        spec=ResourceClaimSpec(device_requests=[
            DeviceRequest(name="accel", selectors=[
                DeviceSelector(cel_expression=expr)])]))


def dra_steady_state_cel_in(init_nodes=100, measure_pods=300) -> Workload:
    return Workload(
        name="DRASteadyStateCELIn/100Nodes_300Pods",
        threshold=40,   # template-variant floor: same shape, `in` selector
        node_capacity=128,
        pod_capacity=2048,
        batch_size=256,
        dra_claim_controller=True,
        ops=[
            CreateNodes(init_nodes, _dra_node),
            CreateObjects(init_nodes, _dra_model_slice,
                          create_verb="create_resource_slice"),
            CreateObjects(1, _dra_cel_in_template,
                          create_verb="create_resource_claim_template"),
            CreatePods(measure_pods, _dra_template_pod,
                       collect_metrics=True),
        ])


# --------------- 13d. DRA multi-request claims
# the second unmeasured variant: each claim carries TWO requests (a
# class-matched pair + one attribute-selected device, 3 devices per
# pod), exercising the allocator's greedy multi-request walk — on
# device, the carried `taken` mask across request slots.

def _dra_multi_slice(i: int):
    from kubernetes_tpu.api.objects import Device, ResourceSlice

    node = f"node-{i}"
    return ResourceSlice(
        metadata=ObjectMeta(name=f"slice-{node}"),
        node_name=node, driver="tpu.example.com", pool=node,
        devices=[Device(name=f"dev-{d}", device_class_name="tpu",
                        attributes={"preallocate": d % 2 == 0})
                 for d in range(16)])


def _dra_multi_template(i: int):
    from kubernetes_tpu.api.objects import (
        DeviceRequest,
        DeviceSelector,
        ResourceClaimSpec,
        ResourceClaimTemplate,
    )

    expr = "device.attributes['tpu.example.com'].preallocate"
    return ResourceClaimTemplate(
        metadata=ObjectMeta(name="perf-claim-template"),
        spec=ResourceClaimSpec(device_requests=[
            DeviceRequest(name="pair", device_class_name="tpu", count=2),
            DeviceRequest(name="probe", count=1, selectors=[
                DeviceSelector(cel_expression=expr)]),
        ]))


def dra_multi_request(init_nodes=100, measure_pods=250) -> Workload:
    return Workload(
        name="DRAMultiRequest/100Nodes_250Pods",
        threshold=40,   # template-variant floor: 3 devices per pod
        node_capacity=128,
        pod_capacity=2048,
        batch_size=256,
        dra_claim_controller=True,
        ops=[
            CreateNodes(init_nodes, _dra_node),
            CreateObjects(init_nodes, _dra_multi_slice,
                          create_verb="create_resource_slice"),
            CreateObjects(1, _dra_multi_template,
                          create_verb="create_resource_claim_template"),
            CreatePods(measure_pods, _dra_template_pod,
                       collect_metrics=True),
        ])


# -------------------------------------- 14. SchedulingPodAffinity
# affinity/performance-config.yaml:83-148 (5000Nodes_5000Pods, 35 — the
# reference's SLOWEST headline shape): every node in ONE zone; init and
# measured pods carry required zone-level podAffinity on color=blue
# across namespaces sched-0/sched-1 (pod-with-pod-affinity.yaml), so
# every placement updates the single shared affinity domain.

def _pod_affinity_pod(i: int, ns: str) -> Pod:
    aff = Affinity(pod_affinity=PodAffinity(required=[
        PodAffinityTerm(
            topology_key=LABEL_ZONE,
            label_selector=LabelSelector(match_labels={"color": "blue"}),
            namespaces=["sched-1", "sched-0"])]))
    return _pod(f"aff-{ns}-{i}", namespace=ns, labels={"color": "blue"},
                affinity=aff)


def scheduling_pod_affinity(init_nodes=5000, init_pods=5000,
                            measure_pods=5000) -> Workload:
    return Workload(
        name="SchedulingPodAffinity/5000Nodes_5000Pods",
        threshold=35,
        pod_capacity=32768,
        ops=[
            CreateNodes(init_nodes, lambda i: _node(i, zones=["zone1"])),
            CreateNamespaces("sched", 2),
            CreatePods(init_pods,
                       lambda i: _pod_affinity_pod(i, "sched-0")),
            CreatePods(measure_pods,
                       lambda i: _pod_affinity_pod(i, "sched-1"),
                       collect_metrics=True),
        ])


# -------------------------------------- 15. MixedSchedulingBasePod
# affinity/performance-config.yaml:338-418 (5000Nodes_5000Pods, 140):
# one zone; 2000 init pods of EACH of five templates — plain, required
# zone affinity (blue), required hostname anti-affinity (green),
# preferred hostname affinity (red), preferred hostname anti-affinity
# (yellow) — then 5000 plain measured pods scored against that mixture.

def _mixed_init_pod(i: int) -> Pod:
    kind = i % 5
    j = i // 5
    if kind == 0:
        return _pod(f"mix-plain-{j}", namespace="sched-0")
    if kind == 1:
        aff = Affinity(pod_affinity=PodAffinity(required=[
            PodAffinityTerm(
                topology_key=LABEL_ZONE,
                label_selector=LabelSelector(
                    match_labels={"color": "blue"}),
                namespaces=["sched-1", "sched-0"])]))
        return _pod(f"mix-aff-{j}", namespace="sched-0",
                    labels={"color": "blue"}, affinity=aff)
    if kind == 2:
        aff = Affinity(pod_anti_affinity=PodAntiAffinity(required=[
            PodAffinityTerm(
                topology_key=LABEL_HOSTNAME,
                label_selector=LabelSelector(
                    match_labels={"color": "green"}),
                namespaces=["sched-1", "sched-0"])]))
        return _pod(f"mix-anti-{j}", namespace="sched-0",
                    labels={"color": "green"}, affinity=aff)
    term = WeightedPodAffinityTerm(weight=1, pod_affinity_term=(
        PodAffinityTerm(
            topology_key=LABEL_HOSTNAME,
            label_selector=LabelSelector(match_labels={
                "color": "red" if kind == 3 else "yellow"}),
            namespaces=["sched-1", "sched-0"])))
    if kind == 3:
        aff = Affinity(pod_affinity=PodAffinity(preferred=[term]))
        return _pod(f"mix-paff-{j}", namespace="sched-0",
                    labels={"color": "red"}, affinity=aff)
    aff = Affinity(pod_anti_affinity=PodAntiAffinity(preferred=[term]))
    return _pod(f"mix-panti-{j}", namespace="sched-0",
                labels={"color": "yellow"}, affinity=aff)


def mixed_scheduling_base_pod(init_nodes=5000, init_pods_each=2000,
                              measure_pods=5000) -> Workload:
    return Workload(
        name="MixedSchedulingBasePod/5000Nodes_5000Pods",
        threshold=140,
        pod_capacity=32768,
        warm_full_nodes=True,   # hostname terms: domains = nodes
        ops=[
            CreateNodes(init_nodes, lambda i: _node(i, zones=["zone1"])),
            CreateNamespaces("sched", 1),
            CreatePods(init_pods_each * 5, _mixed_init_pod),
            CreatePods(measure_pods,
                       lambda i: _pod(f"measure-{i}", namespace="sched-0"),
                       collect_metrics=True),
        ])


# ------------------ 16. RequiredPodAffinityWithNSSelector
# affinity/performance-config.yaml:574-648 (5000Nodes_2000Pods, 35):
# one zone (labelNodePrepareStrategy zone1); 100 team=devops namespaces
# x 50 init pods; measured pods carry required zone-level podAffinity
# whose namespaceSelector picks team=devops — every placement feeds the
# one shared domain through namespace-unrolled terms.

def _ns_selector_aff_pod(i: int, ns: str) -> Pod:
    aff = Affinity(pod_affinity=PodAffinity(required=[
        PodAffinityTerm(
            topology_key=LABEL_ZONE,
            label_selector=LabelSelector(match_labels={"color": "blue"}),
            namespace_selector=LabelSelector(
                match_labels={"team": "devops"}))]))
    return _pod(f"nsaff-{ns}-{i}", namespace=ns, labels={"color": "blue"},
                affinity=aff)


def ns_selector_pod_affinity(init_nodes=5000, init_namespaces=100,
                             init_pods_per_ns=50,
                             measure_pods=2000) -> Workload:
    return Workload(
        name="SchedulingRequiredPodAffinityWithNSSelector"
             "/5000Nodes_2000Pods",
        threshold=35,
        pod_capacity=32768,
        ops=[
            CreateNodes(init_nodes, lambda i: _node(i, zones=["zone1"])),
            CreateNamespaces("init-ns", init_namespaces,
                             labels=lambda i: {"team": "devops"}),
            CreateNamespaces("measure-ns", 1,
                             labels=lambda i: {"team": "devops"}),
            CreatePods(init_namespaces * init_pods_per_ns,
                       lambda i: _ns_selector_aff_pod(
                           i, f"init-ns-{i % init_namespaces}")),
            CreatePods(measure_pods,
                       lambda i: _ns_selector_aff_pod(
                           i + 10**6, "measure-ns-0"),
                       collect_metrics=True),
        ])


# ------------------ 17. PreferredAffinityWithNSSelector
# affinity/performance-config.yaml:650-728 (5000Nodes_5000Pods, 90):
# same namespace layout; measured pods carry a weight-1 PREFERRED
# hostname affinity (red) with the devops namespaceSelector — pure Score
# work over namespace-unrolled terms.

def _ns_selector_pref_pod(i: int, ns: str) -> Pod:
    term = WeightedPodAffinityTerm(weight=1, pod_affinity_term=(
        PodAffinityTerm(
            topology_key=LABEL_HOSTNAME,
            label_selector=LabelSelector(match_labels={"color": "red"}),
            namespace_selector=LabelSelector(
                match_labels={"team": "devops"}))))
    aff = Affinity(pod_affinity=PodAffinity(preferred=[term]))
    return _pod(f"nspref-{ns}-{i}", namespace=ns, labels={"color": "red"},
                affinity=aff)


def ns_selector_preferred_affinity(init_nodes=5000, init_namespaces=100,
                                   init_pods_per_ns=50,
                                   measure_pods=5000) -> Workload:
    return Workload(
        name="SchedulingPreferredAffinityWithNSSelector"
             "/5000Nodes_5000Pods",
        threshold=90,
        pod_capacity=32768,
        warm_full_nodes=True,   # hostname topology: domains = nodes
        ops=[
            CreateNodes(init_nodes, _node),
            CreateNamespaces("init-ns", init_namespaces,
                             labels=lambda i: {"team": "devops"}),
            CreateNamespaces("measure-ns", 1,
                             labels=lambda i: {"team": "devops"}),
            CreatePods(init_namespaces * init_pods_per_ns,
                       lambda i: _ns_selector_pref_pod(
                           i, f"init-ns-{i % init_namespaces}")),
            CreatePods(measure_pods,
                       lambda i: _ns_selector_pref_pod(
                           i + 10**6, "measure-ns-0"),
                       collect_metrics=True),
        ])


# ---------- 18. SchedulingGatedPodsWithPodAffinityImpactForThroughput
# affinity/performance-config.yaml:731-800 (1Node_10000GatedPods, 110):
# 10k gated pods carrying required hostname affinity on the measured
# pods' label park in the gated pool; 20k app=scheduler-perf pods then
# bind to the single 90000-pod node (node-with-name.yaml). Every bind
# fires an AssignedPodAdd the gated pods' affinity COULD match — the
# throughput must survive the event volume (the park-index discipline).

def _gated_affinity_pod(i: int) -> Pod:
    from kubernetes_tpu.api.objects import PodSchedulingGate

    aff = Affinity(pod_affinity=PodAffinity(required=[
        PodAffinityTerm(
            topology_key=LABEL_HOSTNAME,
            label_selector=LabelSelector(
                match_labels={"app": "scheduler-perf"}))]))
    p = _pod(f"gated-{i}", cpu="0", mem="0",
             labels={"app": "scheduler-perf"}, affinity=aff)
    p.spec.scheduling_gates = [PodSchedulingGate(name="scheduling-gate-1")]
    return p


def _perf_node(i: int) -> Node:
    name = "scheduler-perf-node"
    return Node(
        metadata=ObjectMeta(name=name, labels={LABEL_HOSTNAME: name}),
        spec=NodeSpec(),
        status=NodeStatus(allocatable={
            "cpu": "4", "memory": "32Gi", "pods": "90000"}))


def gated_pods_with_pod_affinity(gated_pods=10000,
                                 measure_pods=20000) -> Workload:
    return Workload(
        name="SchedulingGatedPodsWithPodAffinityImpactForThroughput"
             "/1Node_10000GatedPods",
        threshold=110,
        node_capacity=64,
        pod_capacity=65536,
        batch_size=4096,
        ops=[
            CreateNodes(1, _perf_node),
            CreatePods(gated_pods, _gated_affinity_pod, wait=False),
            CreatePods(measure_pods,
                       lambda i: _pod(f"measure-{i}", cpu="0", mem="0",
                                      labels={"app": "scheduler-perf"}),
                       collect_metrics=True),
        ])


# ------------------------------ 19. PreferredTopologySpreading
# topology_spreading/performance-config.yaml:83-145 (5000Nodes_5000Pods,
# 125): three zones; measured pods carry a maxSkew=5 ScheduleAnyway zone
# constraint (pod-with-preferred-topology-spreading.yaml) — the SOFT
# spread Score path rather than the DoNotSchedule Filter.

def _preferred_spreading_pod(i: int) -> Pod:
    return _pod(f"pspread-{i}", labels={"color": "blue"}, tsc=[
        TopologySpreadConstraint(
            max_skew=5, topology_key=LABEL_ZONE,
            when_unsatisfiable="ScheduleAnyway",
            label_selector=LabelSelector(match_labels={"color": "blue"}))])


def preferred_topology_spreading(init_nodes=5000, init_pods=5000,
                                 measure_pods=5000) -> Workload:
    return Workload(
        name="PreferredTopologySpreading/5000Nodes_5000Pods",
        threshold=125,
        pod_capacity=32768,
        ops=[
            CreateNodes(init_nodes, lambda i: _node(
                i, zones=["moon-1", "moon-2", "moon-3"])),
            CreatePods(init_pods, lambda i: _pod(f"init-{i}")),
            CreatePods(measure_pods, _preferred_spreading_pod,
                       collect_metrics=True),
        ])


# --------------------------- 20. SchedulingWithNodeInclusionPolicy
# topology_spreading/performance-config.yaml:210-273 (5000Nodes, 68):
# 4000 normal + 1000 tainted (foo:NoSchedule) nodes; measured pods carry
# a hostname DoNotSchedule spread with Honor/Honor inclusion policies
# (pod-with-node-inclusion-policy.yaml), so tainted nodes drop out of
# both the domain set and the skew accounting.

def _tainted_node(i: int) -> Node:
    from kubernetes_tpu.api.objects import Taint

    name = f"taint-node-{i}"
    return Node(
        metadata=ObjectMeta(name=name, labels={LABEL_HOSTNAME: name}),
        spec=NodeSpec(taints=[Taint(key="foo", value="",
                                    effect="NoSchedule")]),
        status=NodeStatus(allocatable={
            "cpu": "4", "memory": "32Gi", "pods": "110"}))


def _inclusion_policy_pod(i: int) -> Pod:
    from kubernetes_tpu.api.objects import POLICY_HONOR

    return _pod(f"incl-{i}", labels={"foo": "bar"}, tsc=[
        TopologySpreadConstraint(
            max_skew=1, topology_key=LABEL_HOSTNAME,
            when_unsatisfiable="DoNotSchedule",
            node_affinity_policy=POLICY_HONOR,
            node_taints_policy=POLICY_HONOR,
            label_selector=LabelSelector(match_labels={"foo": "bar"}))])


def scheduling_with_node_inclusion_policy(normal_nodes=4000,
                                          taint_nodes=1000,
                                          measure_pods=4000) -> Workload:
    return Workload(
        name="SchedulingWithNodeInclusionPolicy/5000Nodes",
        threshold=68,
        pod_capacity=16384,
        warm_full_nodes=True,   # hostname topology: domains = nodes
        ops=[
            CreateNodes(normal_nodes, _node),
            CreateNodes(taint_nodes, _tainted_node),
            CreatePods(measure_pods, _inclusion_policy_pod,
                       collect_metrics=True),
        ])


# ------------------------------ 21. Unschedulable (QHints enabled)
# misc/performance-config.yaml:324 (170 with SchedulerQueueingHints):
# same shape as Unschedulable, floor raised — the hints must prove they
# keep the parked 9-CPU pods from re-entering on irrelevant events.

def unschedulable_qhints(init_nodes=5000, init_pods=100,
                         measure_pods=10000) -> Workload:
    w = unschedulable(init_nodes, init_pods, measure_pods)
    w.name = "Unschedulable/5kNodes_100Init_10kPods_QueueingHintsEnabled"
    w.threshold = 170
    w.feature_gates = {"SchedulerQueueingHints": True}
    return w


# ------------------------------ 22. SchedulingBasic (QHints enabled)
# misc/performance-config.yaml:72 (270): the headline shape with
# SchedulerQueueingHints pinned on — its own thresholded reference row
# (the gate defaults on here, but the variant is measured separately so
# a hints regression shows up against its own floor).

def scheduling_basic_qhints(init_nodes=5000, init_pods=1000,
                            measure_pods=10000) -> Workload:
    w = scheduling_basic(init_nodes, init_pods, measure_pods)
    w.name = "SchedulingBasic/5000Nodes_10000Pods_QueueingHintsEnabled"
    w.threshold = 270
    w.feature_gates = {"SchedulerQueueingHints": True}
    return w


# ------------------------------ 23. PreemptionAsync (async enabled)
# misc/performance-config.yaml:247 (160): the preemption shape with
# SchedulerAsyncPreemption pinned on — victims are evicted between
# cycles (kep 4832) instead of inside the failure handler.

def preemption_async_enabled(init_nodes=5000, init_pods=20000,
                             measure_pods=5000) -> Workload:
    w = preemption_async(init_nodes, init_pods, measure_pods)
    w.name = "PreemptionAsync/5000Nodes_AsyncPreemptionEnabled"
    w.feature_gates = {"SchedulerAsyncPreemption": True}
    return w


# ------------------ 24. PreferredAntiAffinityWithNSSelector
# affinity/performance-config.yaml:488-557 (5000Nodes_2000Pods, 55):
# the namespace-selector layout with a weight-1 PREFERRED hostname
# ANTI-affinity term — soft avoidance Score work over
# namespace-unrolled terms.

def _ns_selector_pref_anti_pod(i: int, ns: str) -> Pod:
    term = WeightedPodAffinityTerm(weight=1, pod_affinity_term=(
        PodAffinityTerm(
            topology_key=LABEL_HOSTNAME,
            label_selector=LabelSelector(match_labels={"color": "teal"}),
            namespace_selector=LabelSelector(
                match_labels={"team": "sched"}))))
    aff = Affinity(pod_anti_affinity=PodAntiAffinity(preferred=[term]))
    return _pod(f"nspanti-{ns}-{i}", namespace=ns,
                labels={"color": "teal"}, affinity=aff)


def ns_selector_preferred_anti_affinity(init_nodes=5000, init_pods=1000,
                                        measure_pods=2000,
                                        namespaces=10) -> Workload:
    return Workload(
        name="SchedulingPreferredAntiAffinityWithNSSelector"
             "/5000Nodes_2000Pods",
        threshold=55,
        pod_capacity=32768,
        warm_full_nodes=True,   # hostname topology: domains = nodes
        ops=[
            CreateNodes(init_nodes, _node),
            CreateNamespaces("team", namespaces,
                             labels=lambda i: {"team": "sched"}),
            CreatePods(init_pods,
                       lambda i: _ns_selector_pref_anti_pod(
                           i, f"team-{i % namespaces}")),
            CreatePods(measure_pods,
                       lambda i: _ns_selector_pref_anti_pod(
                           i + 10**6, f"team-{i % namespaces}"),
                       collect_metrics=True),
        ])


# ------------------------------------- 26-28. gang / multi-tenant (ISSUE 6)
# The multi-tenant job-storm workload class the gang subsystem opens
# (Kant, PAPERS.md): PodGroups with mixed gang sizes 2-64 across weighted
# tenants, quota exhaustion that must not starve other tenants, and
# priority preemption of whole gangs. No reference floors exist for
# these — the thresholds are OUR floors, set from the first measured
# round. All three carry a
# ``rescale`` hook: op counts must stay gang-aligned, so the harness's
# uniform per-op warmup scaling would strand partial gangs behind
# min_member; the factory rebuilds the whole workload at the requested
# scale instead (capacities/batch stay identical, preserving jit shapes).

GANG_SIZES = (2, 4, 8, 16, 32, 64)


def _gang_member(name: str, gang: str, tenant: str, cpu: str = "100m",
                 priority: int | None = None) -> Pod:
    p = _pod(name, cpu=cpu, mem="200Mi", priority=priority)
    p.metadata.labels[LABEL_POD_GROUP] = gang
    p.metadata.labels[LABEL_QUEUE] = tenant
    return p


def _tenant_pod(name: str, tenant: str, cpu: str = "100m") -> Pod:
    p = _pod(name, cpu=cpu, mem="200Mi")
    p.metadata.labels[LABEL_QUEUE] = tenant
    return p


def multi_tenant_gang_storm(init_nodes=500,
                            gangs_per_tenant=24) -> Workload:
    """Two weighted tenants (2:1), mixed gang sizes 2-64: every gang
    admits whole through the DRR queue and commits through Permit; the
    artifact's per-tenant ``contended_admitted`` ratio is the fairness
    number (≈ the weight ratio while both tenants have backlog)."""
    plan = []        # (gang name, tenant, size)
    for tenant in ("tenant-a", "tenant-b"):
        for g in range(gangs_per_tenant):
            plan.append((f"{tenant}-job-{g}", tenant,
                         GANG_SIZES[g % len(GANG_SIZES)]))
    members = [(f"{gang}-m{m}", gang, tenant)
               for gang, tenant, size in plan for m in range(size)]

    def mkgroup(i: int) -> PodGroup:
        gang, tenant, size = plan[i]
        return PodGroup(metadata=ObjectMeta(name=gang),
                        min_member=size, queue=tenant,
                        schedule_timeout_seconds=120.0)

    def mkpod(i: int) -> Pod:
        name, gang, tenant = members[i]
        return _gang_member(name, gang, tenant)

    return Workload(
        name="MultiTenantGangStorm/500Nodes",
        threshold=25,
        node_capacity=512,     # tracks the 500-node cluster (ISSUE-12)
        batch_size=1024,
        tenants={"tenant-a": {"weight": 2.0},
                 "tenant-b": {"weight": 1.0}},
        ops=[
            CreateNodes(init_nodes, _node),
            CreateObjects(len(plan), mkgroup,
                          create_verb="create_pod_group"),
            CreatePods(len(members), mkpod, collect_metrics=True),
        ],
        rescale=lambda s: multi_tenant_gang_storm(
            init_nodes=max(8, int(init_nodes * s)),
            gangs_per_tenant=max(1, int(gangs_per_tenant * s))))


def quota_exhaustion_churn(init_nodes=200, blocked_pods=400,
                           quota_pods=100, measure_pods=2000) -> Workload:
    """A burst tenant whose demand exceeds its pod quota (only
    ``quota_pods`` admit; the rest hold in its job queue) while an
    unconstrained steady tenant's measured pods must flow at full rate —
    the "blocked tenants don't starve others" criterion."""
    return Workload(
        name="QuotaExhaustionChurn/200Nodes",
        threshold=150,
        # bucket tracks the 200-node cluster: a 1024-row bucket made
        # every [B, N] auction round pay 5x dead-row work (ISSUE-12)
        node_capacity=256,
        batch_size=1024,
        tenants={"burst": {"quota": {"pods": str(quota_pods)}},
                 "steady": {}},
        ops=[
            CreateNodes(init_nodes, _node),
            CreatePods(blocked_pods,
                       lambda i: _tenant_pod(f"burst-{i}", "burst"),
                       wait=False),    # over-quota tail never schedules
            CreatePods(measure_pods,
                       lambda i: _tenant_pod(f"steady-{i}", "steady"),
                       collect_metrics=True),
        ],
        rescale=lambda s: quota_exhaustion_churn(
            init_nodes=max(8, int(init_nodes * s)),
            blocked_pods=max(4, int(blocked_pods * s)),
            quota_pods=max(1, int(quota_pods * s)),
            measure_pods=max(4, int(measure_pods * s))))


def gang_preemption(init_nodes=128, high_gangs=24) -> Workload:
    """Whole-gang priority preemption: low-priority gangs of 4 saturate
    the cluster's CPU; measured high-priority gangs of 4 must evict
    ENTIRE lower gangs (never a slice) to land — the eviction path runs
    through the fenced flush + _expand_gang_victims."""
    low_gangs = init_nodes               # 4 x 900m per 4-cpu node
    low = [(f"low-{g}-m{m}", f"low-{g}") for g in range(low_gangs)
           for m in range(4)]
    high = [(f"high-{g}-m{m}", f"high-{g}") for g in range(high_gangs)
            for m in range(4)]

    def mkgroup(i: int) -> PodGroup:
        if i < low_gangs:
            name, prio = f"low-{i}", 0
        else:
            name, prio = f"high-{i - low_gangs}", 10
        return PodGroup(metadata=ObjectMeta(name=name), min_member=4,
                        queue="jobs", priority=prio,
                        schedule_timeout_seconds=120.0)

    return Workload(
        name="GangPreemption/128Nodes",
        # preemptor re-probes ride the next wave the moment the
        # eviction flush fires (activation instead of backoff routing),
        # so a gang does not wait out a backoff for its victims' drain
        threshold=30,
        node_capacity=256,
        batch_size=512,
        ops=[
            CreateNodes(init_nodes, _node),
            CreateObjects(low_gangs + high_gangs, mkgroup,
                          create_verb="create_pod_group"),
            CreatePods(len(low),
                       lambda i: _gang_member(low[i][0], low[i][1],
                                              "jobs", cpu="900m")),
            CreatePods(len(high),
                       lambda i: _gang_member(high[i][0], high[i][1],
                                              "jobs", cpu="900m",
                                              priority=10),
                       collect_metrics=True),
        ],
        rescale=lambda s: gang_preemption(
            init_nodes=max(4, int(init_nodes * s)),
            high_gangs=max(1, int(high_gangs * s))))


def _colocation_validate(hub, result) -> None:
    """GangTopologyPacking's acceptance criterion: members of each gang
    land topology-close. Computes per-gang zone spans from the final
    placements and RAISES when the mean strays — the device packer's
    domain-major fill keeps each fitting gang inside one zone, while a
    per-member spreading placement would scatter it."""
    node_zone = {n.metadata.name: n.metadata.labels.get(LABEL_ZONE)
                 for n in hub.list_nodes()}
    by_gang: dict[str, set] = {}
    for p in hub.list_pods():
        g = p.metadata.labels.get(LABEL_POD_GROUP)
        if g and p.spec.node_name:
            by_gang.setdefault(g, set()).add(node_zone.get(p.spec.node_name))
    spans = sorted(len(z) for z in by_gang.values())
    assert spans, "no gang placed anything"
    mean = sum(spans) / len(spans)
    result["colocation"] = {
        "gangs": len(spans),
        "mean_zone_spans": round(mean, 3),
        "max_zone_spans": spans[-1],
        "one_zone_frac": round(
            sum(1 for s in spans if s == 1) / len(spans), 3),
    }
    assert mean <= 1.5, \
        f"gang members not topology-close: mean zone spans {mean:.2f}"


def gang_topology_packing(init_nodes=96, zones=8, gangs=8) -> Workload:
    """Zoned cluster, gangs sized to FIT one zone, cluster at half
    demand: every gang must land topology-close (the validate hook
    asserts mean zone spans <= 1.5 — the device packer's domain-major
    fill puts each gang in ONE zone, where per-member least-allocated
    spreading would scatter it across the cluster)."""
    nodes_per_zone = max(1, init_nodes // zones)
    zone_cap = nodes_per_zone * 4           # 900m members on 4-cpu nodes
    size = max(2, zone_cap // 2)            # each gang fits half a zone
    zone_names = [f"zone-{z}" for z in range(zones)]

    def mkgroup(i: int) -> PodGroup:
        return PodGroup(metadata=ObjectMeta(name=f"pack-{i}"),
                        min_member=size, queue="jobs",
                        schedule_timeout_seconds=120.0)

    def mkpod(i: int) -> Pod:
        return _gang_member(f"pack-{i // size}-m{i % size}",
                            f"pack-{i // size}", "jobs", cpu="900m")

    return Workload(
        name="GangTopologyPacking/96Nodes",
        # our own floor (first-round cpu measurement ~570 pods/s; the
        # real acceptance gate is the validate hook's co-location bound)
        threshold=150,
        node_capacity=128,
        batch_size=512,
        ops=[
            CreateNodes(init_nodes, lambda i: _node(i, zone_names)),
            CreateObjects(gangs, mkgroup,
                          create_verb="create_pod_group"),
            CreatePods(gangs * size, mkpod, collect_metrics=True),
        ],
        validate=_colocation_validate,
        rescale=lambda s: gang_topology_packing(
            init_nodes=max(zones * 2, int(init_nodes * s)),
            zones=zones,
            gangs=max(2, int(gangs * s))))


# every thresholded reference workload; the first five are the
# BASELINE.json headline configs
ALL_WORKLOADS = (
    scheduling_basic,
    scheduling_node_affinity,
    scheduling_pod_anti_affinity,
    topology_spreading,
    preemption_async,
    unschedulable,
    unschedulable_qhints,
    mixed_churn,
    scheduling_daemonset,
    scheduling_while_gated,
    preferred_pod_affinity,
    preferred_pod_anti_affinity,
    ns_selector_anti_affinity,
    dra_steady_state,
    dra_steady_state_templates,
    dra_steady_state_cel_in,
    dra_multi_request,
    scheduling_pod_affinity,
    mixed_scheduling_base_pod,
    ns_selector_pod_affinity,
    ns_selector_preferred_affinity,
    gated_pods_with_pod_affinity,
    preferred_topology_spreading,
    scheduling_with_node_inclusion_policy,
    scheduling_basic_qhints,
    preemption_async_enabled,
    ns_selector_preferred_anti_affinity,
    multi_tenant_gang_storm,
    quota_exhaustion_churn,
    gang_preemption,
    gang_topology_packing,
)
