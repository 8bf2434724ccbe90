"""The perf-harness op DSL + workload runner.

TPU-native equivalent of the reference's scheduler_perf test driver
(test/integration/scheduler_perf/scheduler_perf.go:82-97 op registry,
:819+ churnOp; util.go:442-630 collector wiring). A Workload is a list of
ops executed in order against a fresh Hub + production Scheduler:

- CreateNodes / CreateNamespaces: populate the cluster.
- CreatePods: create pods through hub.create_pod and drain the scheduler
  until every pod of the op is bound (the reference's
  waitUntilPodsScheduled); with collect_metrics=True the drain is timed
  by a ThroughputCollector observing the hub watch stream.
- Churn: from this point on, create pods from the given templates at a
  fixed interval while later ops drain (scheduler_perf.go:819 churnOp,
  mode=create).
- Barrier: wait for all currently-pending pods to schedule.

The drain drives Scheduler.run_until_idle — the production batched loop
(queue pop -> mirror pack -> device launch -> framework commit -> hub
bind) — NOT a raw launch_batch drain, so measured pods/s is
production-path throughput.
"""

from __future__ import annotations

import copy
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from kubernetes_tpu.api.objects import Namespace, ObjectMeta, Pod
from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.hub import EventHandlers, Hub
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.perf.collector import ThroughputCollector
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.telemetry.slo import time_to_bind_stats

# ---------------------------------------------------------------- op DSL


@dataclass
class CreateNodes:
    """createNodes op. ``make_node(i)`` -> Node; zones (if set) are applied
    by the factory (labelNodePrepareStrategy equivalent is the factory's
    business — the DSL just counts)."""

    count: int
    make_node: Callable[[int], object]


@dataclass
class CreateNamespaces:
    prefix: str
    count: int
    labels: Optional[Callable[[int], dict]] = None


@dataclass
class CreatePods:
    """createPods op: create ``count`` pods via ``make_pod(i)`` and wait
    for all of them to schedule (waitUntilPodsScheduled). When
    ``collect_metrics`` the phase is timed."""

    count: int
    make_pod: Callable[[int], Pod]
    collect_metrics: bool = False
    # maximum wall-clock seconds to wait for the phase to finish before
    # declaring the workload stuck (the reference fails the test case)
    timeout_s: float = 600.0
    # wait=False: create without draining (pods that are NOT expected to
    # schedule — e.g. permanently gated pods parked by PreEnqueue)
    wait: bool = True


@dataclass
class CreateObjects:
    """Generic typed-object create op (the reference DSL's createAny:
    scheduler_perf.go createAny op for ResourceSlices/Claims/classes):
    calls hub.<create_verb>(make(i)) count times."""

    count: int
    make: Callable[[int], object]
    create_verb: str = "create_resource_claim"


@dataclass
class Churn:
    """churnOp (scheduler_perf.go:819): once reached, inject one object
    per template every ``interval_ms`` while subsequent ops drain.
    mode=create keeps creating; mode=recreate deletes the previous copy of
    each template first, keeping ``number`` alive (the MixedChurn shape).
    Templates may build Pods or Nodes."""

    templates: list[Callable[[int], object]]
    interval_ms: int = 200
    mode: str = "create"


@dataclass
class Barrier:
    timeout_s: float = 600.0


@dataclass
class Workload:
    name: str
    ops: list
    threshold: float = 0.0      # reference CI floor, pods/s
    node_capacity: int = 8192   # mirror bucket hints (pow2; fixed up front
    pod_capacity: int = 16384   # so warmup compiles the full-size programs)
    batch_size: int = 2048
    # hostname-keyed topology workloads: the domain bucket (a STATIC jit
    # arg) tracks the number of distinct domains = nodes, so a scaled-down
    # warmup would compile the wrong program; keep CreateNodes unscaled
    warm_full_nodes: bool = False
    # featureGates overrides for this workload (the reference per-workload
    # featureGates block), merged onto the scheduler config's gates
    feature_gates: dict = field(default_factory=dict)
    # run a ResourceClaimController against the hub (the reference's
    # resourceclaim controller runs in kube-controller-manager): needed by
    # claim-TEMPLATE workloads, whose claims the controller materializes
    dra_claim_controller: bool = False
    # multi-tenant job queues: tenant name -> {"weight", "quota"} merged
    # onto SchedulerConfiguration.tenants for this workload
    tenants: dict = field(default_factory=dict)
    # gang workloads: op counts must stay GANG-ALIGNED, so the uniform
    # per-op scaling would strand partial gangs behind min_member — the
    # factory rebuilds the whole workload at the requested scale instead
    # (capacities/batch stay identical, so jit shapes are preserved)
    rescale: Optional[Callable[[float], "Workload"]] = None
    # post-run assertion hook: validate(hub, result) inspects the final
    # cluster state, may attach extra result fields, and RAISES on a
    # violated workload invariant (e.g. GangTopologyPacking's
    # members-land-topology-close criterion)
    validate: Optional[Callable] = None


class _ChurnState:
    def __init__(self, op: Churn, now: Callable[[], float]) -> None:
        self.op = op
        self.t0 = now()
        self.created = 0
        # mode=recreate: previous live copy per template index
        self._live: dict[int, object] = {}

    def due(self, t: float) -> int:
        # first injection fires immediately: a warm/compile pass whose
        # drain completes inside one interval must still exercise the
        # churn path (and compile its programs — e.g. the preemption
        # sweep) or the full-scale run pays the XLA compile mid-phase
        return 1 + int((t - self.t0) * 1000.0 / self.op.interval_ms)

    def _create(self, hub: Hub, obj, i: int) -> None:
        from kubernetes_tpu.api.objects import Node
        from kubernetes_tpu.scenario.lifecycle import NodeLifecycle

        obj.metadata.name = f"churn-{obj.metadata.name}-{i}"
        if isinstance(obj, Node):
            NodeLifecycle(hub).add(obj)
        else:
            hub.create_pod(obj)

    def _delete(self, hub: Hub, obj) -> None:
        from kubernetes_tpu.api.objects import Node
        from kubernetes_tpu.scenario.lifecycle import NodeLifecycle

        try:
            if isinstance(obj, Node):
                NodeLifecycle(hub).remove(obj.metadata.name)
            else:
                hub.delete_pod(obj.metadata.uid)
        except Exception:  # noqa: BLE001 — already gone is fine
            pass

    def inject(self, hub: Hub, t: float) -> None:
        want = self.due(t)
        while self.created < want:
            i = self.created
            ti = i % len(self.op.templates)
            obj = self.op.templates[ti](i)
            if self.op.mode == "recreate":
                prev = self._live.pop(ti, None)
                if prev is not None:
                    self._delete(hub, prev)
                self._live[ti] = obj
            self._create(hub, obj, i)
            self.created += 1


# ---------------------------------------------------------------- runner


class WorkloadStuck(Exception):
    """A phase did not finish within its timeout (pods stayed pending)."""


class DeviceFallback(RuntimeError):
    """The run left the device path: the scheduler's containment ladder
    swallowed a device fault and carried the batch down the serial host
    path. Right for a daemon; on a measurement path it is a silent CPU
    fallback, so the run is refused."""


def assert_device_path(sched: Scheduler) -> None:
    """Raise DeviceFallback (naming the contained exception) when any
    batch or gang degraded off the device or a pod was quarantined."""
    counts = {
        "device_fallbacks": sched.stats["device_fallbacks"],
        "gang_fallbacks{reason=device_fault}": int(
            sched.metrics.gang_fallbacks.value(reason="device_fault")),
        "quarantined": sched.stats["quarantined"],
    }
    if any(counts.values()):
        raise DeviceFallback(
            f"run left the device path: {counts}; contained exception: "
            f"{sched.last_device_fault}")


def run_workload(w: Workload, now: Callable[[], float] = time.time,
                 sleep: Callable[[float], None] = time.sleep,
                 scale: float = 1.0,
                 config=None) -> dict:
    """Execute one workload; returns the result dict (throughput summary,
    scheduler stats, the flight recorder's per-phase/per-plugin
    percentiles and host-tail share).

    ``scale`` shrinks every op count (for warmup/compile passes and unit
    tests) while keeping capacities — and therefore every jitted program
    shape — identical to the full-size run, so a scale=0.01 pass populates
    the XLA compile cache for the real one.
    """
    if scale != 1.0 and w.rescale is not None:
        w = w.rescale(scale)
        scale = 1.0
    hub = Hub()
    if w.dra_claim_controller:
        from kubernetes_tpu.plugins.dra import ResourceClaimController

        ResourceClaimController(hub)
    cfg = copy.deepcopy(config) if config is not None else default_config()
    cfg.batch_size = w.batch_size
    # quality rows gate on time-to-bind percentiles over PodTimelines —
    # the LRU must hold every pod of the run or the oldest (slowest-era)
    # pods fall out of the percentile pass
    cfg.timelines_capacity = max(
        getattr(cfg, "timelines_capacity", 4096), 2 * w.pod_capacity)
    if w.tenants:
        cfg.tenants = {**cfg.tenants, **w.tenants}
    cfg.feature_gates.update(w.feature_gates)
    sched = Scheduler(hub, cfg, caps=Capacities(
        nodes=w.node_capacity, pods=w.pod_capacity), now=now)
    churns: list[_ChurnState] = []
    summary = None
    phases: list[dict] = []

    def scaled(n: int) -> int:
        return max(1, int(n * scale)) if scale != 1.0 else n

    def pump() -> None:
        for ch in churns:
            ch.inject(hub, now())

    def drain(done_fn: Callable[[], bool], timeout_s: float) -> None:
        """Run the production loop until done_fn(); churn pods are injected
        between batches; idle waits advance backoff."""
        deadline = now() + timeout_s

        def step() -> bool:
            pump()
            return done_fn()

        while not done_fn():
            pump()
            sched.run_until_idle(on_step=step)
            if done_fn():
                return
            if now() > deadline:
                raise WorkloadStuck(
                    f"{w.name}: phase timed out after {timeout_s}s "
                    f"(pending={sched.queue.pending_counts()})")
            # queue idle but phase incomplete: pods are parked in backoff /
            # unschedulable (e.g. waiting on preemption victims) or the
            # next churn pod isn't due yet — let time pass, flush, retry
            sleep(0.05)
            sched.queue.flush_backoff_completed()

    try:
        for op in w.ops:
            if isinstance(op, CreateNodes):
                n_nodes = op.count if w.warm_full_nodes else scaled(op.count)
                for i in range(n_nodes):
                    hub.create_node(op.make_node(i))
            elif isinstance(op, CreateObjects):
                make = getattr(hub, op.create_verb)
                for i in range(scaled(op.count)):
                    make(op.make(i))
            elif isinstance(op, CreateNamespaces):
                for i in range(op.count):
                    hub.create_namespace(Namespace(metadata=ObjectMeta(
                        name=f"{op.prefix}-{i}",
                        labels=op.labels(i) if op.labels else {})))
            elif isinstance(op, Churn):
                churns.append(_ChurnState(op, now))
            elif isinstance(op, Barrier):
                drain(lambda: len(sched.queue) == 0, op.timeout_s)
            elif isinstance(op, CreatePods):
                n = scaled(op.count)
                pods = [op.make_pod(i) for i in range(n)]
                uids = {p.metadata.uid for p in pods}
                collector = None
                if op.collect_metrics:
                    collector = ThroughputCollector(uids, now)
                    hub.watch_pods(EventHandlers(
                        on_add=collector.on_add,
                        on_update=collector.on_update), replay=False)
                    collector.begin()
                for p in pods:
                    hub.create_pod(p)
                if not op.wait:
                    phases.append({"op": "createPods", "count": n,
                                   "measured": False, "waited": False})
                    continue
                if collector is not None:
                    drain(collector.done, op.timeout_s)
                    summary = collector.summarize()
                    phases.append({"op": "createPods", "count": n,
                                   "measured": True})
                else:
                    def all_bound() -> bool:
                        for u in uids:
                            p = hub.get_pod(u)
                            if p is not None and not p.spec.node_name:
                                return False
                        return True

                    drain(all_bound, op.timeout_s)
                    phases.append({"op": "createPods", "count": n,
                                   "measured": False})
            else:
                raise TypeError(f"unknown op {op!r}")

    finally:
        sched.close()  # binder threads released even on failure
    assert_device_path(sched)
    m = sched.metrics
    # scheduling-quality outcomes: preemption count, end-state per-node
    # bound-pod spread, and time-to-bind tail
    # seed EVERY node at 0 first: a scorer that hotspots all pods onto
    # one node must read as maximal imbalance, not perfect spread
    per_node: dict[str, int] = {n.metadata.name: 0
                                for n in hub.list_nodes()}
    for p in hub.list_pods():
        if p.spec.node_name:
            per_node[p.spec.node_name] = per_node.get(p.spec.node_name,
                                                      0) + 1
    counts = list(per_node.values())
    if counts:
        mean = sum(counts) / len(counts)
        spread_std = (sum((c - mean) ** 2 for c in counts)
                      / len(counts)) ** 0.5
        spread_maxmin = max(counts) - min(counts)
    else:
        spread_std = spread_maxmin = 0.0
    result = {
        "name": w.name,
        "stats": dict(sched.stats),
        # the metric slices the reference harness scrapes
        # (scheduler_perf.go:140-166): attempt latency percentiles + counts
        "metrics": {
            "attempt_p50_ms": round(
                m.attempt_duration.percentile(50) * 1e3, 2),
            "attempt_p99_ms": round(
                m.attempt_duration.percentile(99) * 1e3, 2),
            "cycle_p99_ms": round(
                m.batch_duration.percentile(99) * 1e3, 2),
            "attempts": int(sum(
                m.schedule_attempts._values.values())),
        },
        "quality": {
            "preemptions": int(sched.stats.get("preemptions", 0)),
            "spread_stddev": round(spread_std, 3),
            "spread_max_min": int(spread_maxmin),
            # p50/p99/max from ONE PodTimelines pass — the same helper
            # the scenario replay driver's SLO gate uses (ISSUE 17),
            # so bench rows and trace gates cannot drift apart
            **{k: v for k, v in time_to_bind_stats(
                sched.timelines).items() if k != "count"},
        },
    }
    if sched.jobqueue.active:
        # per-tenant admission/fairness accounting for the gang-storm
        # artifact rows (weights should show up as contended ratios)
        result["tenants"] = sched.jobqueue.tenant_stats()
        result["gangs"] = sched._gang.debug_state()["stats"]
    fl = sched.flight
    result["flight"] = {
        "enabled": fl.enabled,
        "cycles_recorded": len(fl.ring),
        "phases": fl.phase_percentiles(),
        "plugins": fl.plugin_percentiles(),
        "host_tail_share": round(fl.host_tail_share(), 4),
        # the device-launch profiler column: compiles by attributed
        # cause, per-shape walltime, resident buffer bytes
        "device": (sched.profiler.snapshot()
                   if sched.profiler is not None else None),
    }
    if w.validate is not None:
        w.validate(hub, result)
    if summary is not None:
        result.update(summary.to_dict())
    return result
