"""In-tree plugin set: descriptors binding names to extension points,
device-kernel slots, events-to-register, and host implementations.

Equivalent of the reference's plugin registry
(/root/reference/pkg/scheduler/framework/plugins/registry.go:48-92), with
one structural difference: plugins whose Filter/Score is fused into the
device pipeline (models.pipeline) are DESCRIPTORS — their per-node logic
lives in ops/* kernels keyed by their FILTER_PLUGINS / SCORE_PLUGINS slot —
while queue/bind/lifecycle plugins are ordinary host classes implementing
the framework interfaces.

EventsToRegister sets mirror each reference plugin's EventsToRegister
(e.g. noderesources/fit.go:265, interpodaffinity/plugin.go:62,
podtopologyspread/plugin.go:139, nodeaffinity/node_affinity.go:89,
tainttoleration, nodeports, nodename, nodeunschedulable,
schedulinggates.go, defaultbinder/default_binder.go:52,
queuesort/priority_sort.go:44).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

from kubernetes_tpu.api.objects import Pod
from kubernetes_tpu.hub import Fenced, Unavailable
from kubernetes_tpu.plugins import hints
from kubernetes_tpu.framework.interface import (
    ActionType,
    BindPlugin,
    ClusterEvent,
    ClusterEventWithHint,
    EventResource,
    PreEnqueuePlugin,
    QueueSortPlugin,
    Status,
)

A = ActionType
R = EventResource


def _ev(resource: R, action: A, hint=None) -> ClusterEventWithHint:
    return ClusterEventWithHint(event=ClusterEvent(resource, action),
                                queueing_hint_fn=hint)


@dataclass
class PluginDescriptor:
    """Metadata for one in-tree plugin."""

    name: str
    points: tuple[str, ...]
    default_weight: float = 0.0
    # slot names into pipeline.FILTER_PLUGINS / SCORE_PLUGINS when the
    # plugin's Filter/Score math runs on device
    device_filter: bool = False
    device_score: bool = False
    events: list[ClusterEventWithHint] = field(default_factory=list)
    # factory for plugins with host-side behavior (queue sort, gates, bind…)
    factory: Optional[Callable[[dict], object]] = None


class SchedulingGates(PreEnqueuePlugin):
    """Holds pods with non-empty spec.schedulingGates out of the activeQ
    (plugins/schedulinggates/scheduling_gates.go)."""

    NAME = "SchedulingGates"

    def pre_enqueue(self, pod: Pod) -> Status:
        if not pod.spec.scheduling_gates:
            return Status()
        gates = ", ".join(g.name for g in pod.spec.scheduling_gates)
        return Status.unschedulable(
            f"waiting for scheduling gates: {gates}",
            plugin=self.NAME, resolvable=False)


class PrioritySort(QueueSortPlugin):
    """(priority desc, queue-time asc) (queuesort/priority_sort.go:44)."""

    NAME = "PrioritySort"

    def less(self, a, b) -> bool:
        pa, pb = a.pod.priority(), b.pod.priority()
        if pa != pb:
            return pa > pb
        return a.timestamp < b.timestamp


class DefaultBinder(BindPlugin):
    """POSTs the Binding (defaultbinder/default_binder.go:52); the hub/client
    is injected by the scheduler."""

    NAME = "DefaultBinder"

    def __init__(self, binder: Optional[Callable[[Pod, str], None]] = None):
        self._binder = binder

    def bind(self, state, pod: Pod, node_name: str) -> Status:
        if self._binder is None:
            return Status.error("no binder client configured", self.NAME)
        try:
            self._binder(pod, node_name)
        except Unavailable:
            raise    # transport outage: degraded mode parks, not errors
        except Fenced:
            raise    # deposed epoch: the scheduler releases the claim
        except Exception as e:  # noqa: BLE001 — surfaced as Status
            return Status.error(str(e), self.NAME)
        return Status()


def _default_preemption_factory(args: dict):
    """Binds the PostFilter to the scheduler's Evaluator (injected via
    extra_args); absent outside a full scheduler (kernel tests)."""
    ev = args.get("preemption_evaluator")
    if ev is None:
        return None
    from kubernetes_tpu.framework.preemption import DefaultPreemption

    return DefaultPreemption(ev)


def in_tree_registry() -> dict[str, PluginDescriptor]:
    """name -> descriptor for every in-tree plugin (registry.go:48)."""
    pod_del = _ev(R.ASSIGNED_POD, A.DELETE | A.UPDATE_POD_SCALE_DOWN)
    node_alloc = _ev(R.NODE, A.ADD | A.UPDATE_NODE_ALLOCATABLE)
    descriptors = [
        PluginDescriptor(
            name="SchedulingGates", points=("pre_enqueue",),
            factory=lambda args: SchedulingGates(),
            # gated pods live in the queue's _gated pool and re-probe
            # PreEnqueue directly on gate events — queueing-hint fns are
            # never consulted for them, so no hint is registered here
            events=[_ev(R.POD,
                        A.UPDATE_POD_SCHEDULING_GATES_ELIMINATED)]),
        PluginDescriptor(
            name="PrioritySort", points=("queue_sort",),
            factory=lambda args: PrioritySort()),
        PluginDescriptor(
            name="NodeUnschedulable", points=("filter",), device_filter=True,
            events=[_ev(R.NODE, A.ADD | A.UPDATE_NODE_TAINT)]),
        PluginDescriptor(
            name="NodeName", points=("filter",), device_filter=True,
            events=[_ev(R.NODE, A.ADD)]),
        PluginDescriptor(
            name="TaintToleration", points=("filter", "score"),
            device_filter=True, device_score=True, default_weight=3,
            events=[_ev(R.NODE, A.ADD | A.UPDATE_NODE_TAINT,
                        hints.taint_toleration_hint)]),
        PluginDescriptor(
            name="NodeAffinity", points=("filter", "score"),
            device_filter=True, device_score=True, default_weight=2,
            events=[_ev(R.NODE, A.ADD | A.UPDATE_NODE_LABEL,
                        hints.node_affinity_hint)]),
        PluginDescriptor(
            name="NodePorts", points=("filter",), device_filter=True,
            events=[_ev(R.ASSIGNED_POD, A.DELETE,
                        hints.node_ports_hint),
                    _ev(R.NODE, A.ADD | A.UPDATE_NODE_ALLOCATABLE,
                        hints.node_ports_hint)]),
        PluginDescriptor(
            name="NodeResourcesFit", points=("filter", "score"),
            device_filter=True, device_score=True, default_weight=1,
            events=[_ev(R.ASSIGNED_POD,
                        A.DELETE | A.UPDATE_POD_SCALE_DOWN,
                        hints.fit_hint),
                    _ev(R.NODE, A.ADD | A.UPDATE_NODE_ALLOCATABLE,
                        hints.fit_hint)]),
        PluginDescriptor(
            name="PodTopologySpread", points=("filter", "score"),
            device_filter=True, device_score=True, default_weight=2,
            events=[_ev(R.ASSIGNED_POD,
                        A.ADD | A.DELETE | A.UPDATE_POD_LABEL,
                        hints.topology_spread_hint),
                    _ev(R.NODE, A.ADD | A.DELETE | A.UPDATE_NODE_LABEL
                        | A.UPDATE_NODE_TAINT,
                        hints.topology_spread_hint)]),
        PluginDescriptor(
            name="InterPodAffinity", points=("filter", "score"),
            device_filter=True, device_score=True, default_weight=2,
            events=[_ev(R.ASSIGNED_POD,
                        A.ADD | A.DELETE | A.UPDATE_POD_LABEL,
                        hints.inter_pod_affinity_hint),
                    _ev(R.NODE, A.ADD | A.UPDATE_NODE_LABEL,
                        hints.inter_pod_affinity_hint)]),
        PluginDescriptor(
            name="NodeResourcesBalancedAllocation", points=("score",),
            device_score=True, default_weight=1,
            events=[pod_del, node_alloc]),
        PluginDescriptor(
            name="ImageLocality", points=("score",), device_score=True,
            default_weight=1,
            events=[_ev(R.NODE, A.ADD | A.UPDATE_NODE_LABEL)]),
        PluginDescriptor(
            name="DefaultPreemption", points=("post_filter", "pre_enqueue"),
            factory=_default_preemption_factory,
            events=[_ev(R.ASSIGNED_POD, A.DELETE)]),
        PluginDescriptor(
            name="DefaultBinder", points=("bind",),
            factory=lambda args: DefaultBinder(args.get("binder"))),
        # gang scheduling: PreFilter capacity bound + Permit quorum
        # assembly + unreserve-driven atomic rollback (plugins/gang.py);
        # the shared coordinator is injected by the scheduler
        PluginDescriptor(
            name="GangScheduling", points=("filter", "reserve", "permit"),
            factory=lambda args: args.get("gang_shared"),
            events=[_ev(R.POD_GROUP, A.ADD | A.UPDATE),
                    # ADD: a peer's bind advances a parked member's
                    # quorum (the permit-timeout retry path after
                    # failover); DELETE: freed capacity + shrunk gangs
                    _ev(R.ASSIGNED_POD, A.ADD | A.DELETE),
                    _ev(R.NODE, A.ADD | A.UPDATE_NODE_ALLOCATABLE)]),
        # --- volume family: host Filter plugins (plugins/volume.py) ---
        PluginDescriptor(
            name="VolumeZone", points=("filter",),
            factory=_volume_factory("VolumeZone"),
            events=[_ev(R.PV, A.ADD | A.UPDATE,
                        hints.volume_binding_hint),
                    _ev(R.PVC, A.ADD | A.UPDATE,
                        hints.volume_binding_hint),
                    _ev(R.NODE, A.ADD | A.UPDATE_NODE_LABEL),
                    _ev(R.STORAGE_CLASS, A.ADD)]),
        PluginDescriptor(
            name="VolumeRestrictions", points=("filter",),
            factory=_volume_factory("VolumeRestrictions"),
            events=[_ev(R.ASSIGNED_POD, A.DELETE,
                        hints.volume_restrictions_hint),
                    _ev(R.PVC, A.ADD | A.UPDATE,
                        hints.volume_restrictions_hint)]),
        PluginDescriptor(
            name="NodeVolumeLimits", points=("filter",),
            factory=_volume_factory("NodeVolumeLimits"),
            events=[_ev(R.CSI_NODE, A.ADD | A.UPDATE),
                    _ev(R.ASSIGNED_POD, A.DELETE,
                        hints.node_volume_limits_hint),
                    _ev(R.PVC, A.ADD),
                    _ev(R.PV, A.ADD)]),
        PluginDescriptor(
            name="DynamicResources",
            points=("filter", "reserve", "pre_bind"),
            factory=_dra_factory,
            events=[_ev(R.RESOURCE_CLAIM, A.ADD | A.UPDATE | A.DELETE,
                        hints.dra_hint),
                    _ev(R.RESOURCE_SLICE, A.ADD | A.DELETE,
                        hints.dra_hint),
                    _ev(R.NODE, A.ADD)]),
        PluginDescriptor(
            name="VolumeBinding",
            points=("filter", "score", "reserve", "pre_bind"),
            default_weight=1,
            factory=_volume_factory("VolumeBinding"),
            events=[_ev(R.PVC, A.ADD | A.UPDATE,
                        hints.volume_binding_hint),
                    _ev(R.PV, A.ADD | A.UPDATE,
                        hints.volume_binding_hint),
                    _ev(R.NODE, A.ADD | A.UPDATE_NODE_LABEL
                        | A.UPDATE_NODE_TAINT),
                    _ev(R.STORAGE_CLASS, A.ADD,
                        hints.volume_binding_hint),
                    _ev(R.CSI_STORAGE_CAPACITY, A.ADD | A.UPDATE,
                        hints.volume_binding_hint),
                    _ev(R.ASSIGNED_POD, A.DELETE)]),
    ]
    return {d.name: d for d in descriptors}


def _dra_factory(args: dict):
    hub = args.get("hub")
    if hub is None:
        return None
    # ONE instance per scheduler, shared across profiles (the reference's
    # SharedDRAManager, scheduler.go:311-333): the assume overlay must see
    # every profile's in-flight allocations or two same-batch pods from
    # different profiles could double-book a device
    shared = args.get("dra_shared")
    if shared is not None:
        return shared
    from kubernetes_tpu.plugins.dra import DynamicResources

    return DynamicResources(hub)


def _volume_factory(name: str):
    """Volume plugins need the hub (API views); absent outside a full
    scheduler (kernel tests) the plugin is skipped."""
    def make(args: dict):
        hub = args.get("hub")
        if hub is None:
            return None
        from kubernetes_tpu.plugins import volume

        return getattr(volume, name)(hub)
    return make


DEVICE_FILTER_PLUGINS = tuple(
    d.name for d in in_tree_registry().values() if d.device_filter)
DEVICE_SCORE_PLUGINS = tuple(
    d.name for d in in_tree_registry().values() if d.device_score)
