"""The trace replay driver: feed a Trace into a fresh Hub + production
Scheduler at recorded (or K×-compressed) rates, then gate.

Replay semantics:

- Event times are TRACE time; ``speed`` compresses them onto the wall
  clock (speed=10 plays a 12-trace-second trace in 1.2 wall seconds).
  There are no raw arrival sleeps — injection happens from the
  scheduler's own ``on_step`` callback plus short idle waits, and the
  driver records how far injection fell behind the recorded schedule
  (``pacing.max_lag_s``). When the box can't hold the schedule the
  report says ``hardware_limited`` honestly instead of letting the lag
  silently poison the verdict.

- SLOs are evaluated in TRACE time: measured wall time-to-bind × speed.
  Waits engineered by the trace (an outage window, a quota turn) are
  trace-time invariant across speeds; pure scheduler compute is NOT
  (it doesn't compress), which is why filed regression traces record
  the speed they were judged at and the pytest gate replays at the
  same speed.

- A warmup pass (2 throwaway nodes + a few pods, deleted afterwards)
  compiles the device programs before the clock starts; warmup pods
  never enter the SLO stats because stats are filtered to the trace's
  own pod uids.

- The gate: ``trace.slo`` (regime intent target) and ``trace.gate``
  (the ratchet bound stamped on filed regression traces) are both
  evaluated; journal-audit exactly-once over the hub's full journal is
  always part of the verdict.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Optional

from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scenario.lifecycle import NodeLifecycle
from kubernetes_tpu.scenario.trace import Trace
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.telemetry.slo import evaluate_slo, time_to_bind_stats
from kubernetes_tpu.testing.audit import audit_bind_journal
from kubernetes_tpu.utils.wire import from_wire


class ReplayStuck(Exception):
    """The trace could not drain within the wall timeout."""


def _peak_nodes(events) -> int:
    """Peak CONCURRENT node count over the trace: the warmup node set
    must reach it, because the topology domain bucket (``d_cap``, a
    static jit arg with a sticky high-water) grows with distinct
    domains — a trace whose node wave first crosses a pow2 domain
    boundary MID-replay pays that compile inside the paced window."""
    cur = peak = 0
    for e in events:
        if e.kind == "node_up":
            cur += 1
            peak = max(peak, cur)
        elif e.kind == "node_down":
            cur -= 1
    return max(peak, 2)


def _trace_zones(events) -> list[str]:
    """Distinct zone labels across the trace's nodes (build order):
    warmup nodes cycle the same zones so the domain count — hostnames
    plus zones — lands in the same pow2 bucket the replay will use."""
    from kubernetes_tpu.api.objects import LABEL_ZONE

    zones: list[str] = []
    for e in events:
        if e.kind != "node_up":
            continue
        n = from_wire(e.data["node"])
        z = n.metadata.labels.get(LABEL_ZONE)
        if z and z not in zones:
            zones.append(z)
    return zones


def _warmup(hub: Hub, sched: Scheduler, now, sleep,
            kinds: set | None = None, peak_nodes: int = 2,
            zones: list[str] | None = None, batch: int = 0) -> None:
    """Compile the device programs before the paced clock starts: bind
    throwaway pods on throwaway nodes, then remove every trace.

    Coverage matters more than count — a program that first compiles
    MID-replay stalls injection for ~a second, and that lag directly
    distorts trace-time waits (a pod injected late against an on-time
    recovery measures a shorter wait than the trace engineered). So the
    warmup covers the trace's SHAPE FAMILIES, not just the plain-fit
    path: the node set is sized to the trace's peak concurrent node
    count and cycles its zones (the topology domain bucket, sticky via
    hysteresis, reaches replay size here), a full-batch wave of plain
    pods drives one launch at the production batch shape, and the
    zone-affinity / priority / DRA-claim / gang pods compile their
    field-subset programs when the trace uses those kinds."""
    from kubernetes_tpu.api.objects import (
        LABEL_HOSTNAME,
        LABEL_POD_GROUP,
        LABEL_ZONE,
        ObjectMeta,
        PodGroup,
    )
    from kubernetes_tpu.perf.workloads import (
        _dra_claim,
        _dra_slice,
        _node,
        _pod,
    )
    from kubernetes_tpu.scenario.generators import _zone_affinity

    zones = zones or ["warmup-zone"]
    life = NodeLifecycle(hub)
    nodes = []
    for i in range(max(peak_nodes, 2)):
        n = _node(i, zones=zones)
        n.metadata.name = f"warmup-node-{i}"
        n.metadata.labels[LABEL_HOSTNAME] = n.metadata.name
        n.metadata.labels[LABEL_ZONE] = zones[i % len(zones)]
        nodes.append(life.add(n))
    # the full-batch wave: enough plain pods that one pop fills the
    # production batch (padding is to batch_size, so this compiles the
    # same [B]-shaped programs the replay's own waves will launch)
    pods = [_pod(f"warmup-pod-{i}") for i in range(max(batch, 3))]
    pods.append(_pod("warmup-aff",
                     affinity=_zone_affinity(zones[0])))
    pods.append(_pod("warmup-prio", priority=100))
    kinds = kinds or set()
    if "obj" in kinds:   # trace creates slices/claims: warm DRA
        sl = _dra_slice(0)
        sl.metadata.name = "warmup-slice"
        sl.node_name = sl.pool = "warmup-node-0"
        hub.create_resource_slice(sl)
        claim = _dra_claim(0)
        claim.metadata.name = "warmup-claim"
        hub.create_resource_claim(claim)
        dra_pod = _pod("warmup-dra")
        from kubernetes_tpu.api.objects import PodResourceClaim

        dra_pod.spec.resource_claims = [PodResourceClaim(
            name="accel", resource_claim_name="warmup-claim")]
        pods.append(dra_pod)
    if "group" in kinds:   # gang regimes: warm the device packer —
        # gated on use because a PodGroup activates the jobqueue layer,
        # and non-gang regimes must not replay through it
        hub.create_pod_group(PodGroup(
            metadata=ObjectMeta(name="warmup-gang"), min_member=2,
            queue="default", schedule_timeout_seconds=60.0))
        for m in range(2):
            gp = _pod(f"warmup-gang-m{m}")
            gp.metadata.labels[LABEL_POD_GROUP] = "warmup-gang"
            pods.append(gp)
    for p in pods:
        hub.create_pod(p)

    def bound() -> bool:
        for p in pods:
            cur = hub.get_pod(p.metadata.uid)
            if cur is None or not cur.spec.node_name:
                return False
        return True

    deadline = now() + 60.0
    while not bound():
        sched.run_until_idle(on_step=bound)
        if bound():
            break
        if now() > deadline:
            raise ReplayStuck("warmup pods did not bind in 60s")
        sleep(0.02)
        sched.queue.flush_backoff_completed()
    for p in pods:
        try:
            hub.delete_pod(p.metadata.uid)
        except Exception:  # noqa: BLE001
            pass
    for n in nodes:
        life.remove(n.metadata.name)


def replay_trace(trace: Trace, speed: float = 10.0, warmup: bool = True,
                 timeout_s: float = 180.0,
                 now: Callable[[], float] = time.time,
                 sleep: Callable[[float], None] = time.sleep) -> dict:
    """Replay one trace; return the full report (stats + verdicts)."""
    speed = max(float(speed), 1e-6)
    tcfg = trace.config or {}
    cfg = default_config()
    cfg.batch_size = int(tcfg.get("batch_size", 32))
    # replaying a K×-compressed world compresses the scheduler's time
    # constants too: un-scaled backoff would make a retry cost K trace-
    # seconds, turning every unschedulable wait speed-DEPENDENT and the
    # filed-trace verdict nondeterministic across boxes
    cfg.pod_initial_backoff_seconds = max(
        cfg.pod_initial_backoff_seconds / speed, 1e-3)
    cfg.pod_max_backoff_seconds = max(
        cfg.pod_max_backoff_seconds / speed, 1e-2)
    if tcfg.get("tenants"):
        cfg.tenants = {**cfg.tenants, **tcfg["tenants"]}
    pod_cap = int(tcfg.get("pod_capacity", 2048))
    node_cap = int(tcfg.get("node_capacity", 64))
    cfg.timelines_capacity = max(
        getattr(cfg, "timelines_capacity", 4096), 2 * pod_cap)
    hub = Hub()
    sched = Scheduler(hub, cfg,
                      caps=Capacities(nodes=node_cap, pods=pod_cap),
                      now=now)
    life = NodeLifecycle(hub)
    events = sorted(trace.events, key=lambda e: e.t)
    trace_pod_uids: set[str] = set()
    injected = {"n": 0}
    max_lag = [0.0]

    def apply(e) -> None:
        data = e.data
        if e.kind == "pod":
            p = from_wire(data["pod"])
            p.metadata.creation_timestamp = now()
            trace_pod_uids.add(p.metadata.uid)
            hub.create_pod(p)
        elif e.kind == "node_up":
            n = from_wire(data["node"])
            n.metadata.creation_timestamp = now()
            life.add(n)
        elif e.kind == "node_down":
            life.remove(data["name"])
        elif e.kind == "node_cordon":
            life.cordon(data["name"])
        elif e.kind == "node_uncordon":
            life.uncordon(data["name"])
        elif e.kind == "group":
            g = from_wire(data["group"])
            g.metadata.creation_timestamp = now()
            hub.create_pod_group(g)
        elif e.kind == "obj":
            o = from_wire(data["obj"])
            if getattr(o, "metadata", None) is not None:
                o.metadata.creation_timestamp = now()
            getattr(hub, data["verb"])(o)
        else:
            raise ValueError(f"unknown trace event kind {e.kind!r}")
        sched.metrics.scenario_events.inc(kind=e.kind)

    try:
        if warmup:
            _warmup(hub, sched, now, sleep,
                    kinds={e.kind for e in events},
                    peak_nodes=_peak_nodes(events),
                    zones=_trace_zones(events),
                    batch=cfg.batch_size)
        prof = sched.profiler
        warm_compiles = prof.compiles if prof is not None else 0
        wall_start = now()
        idx = [0]

        def inject_due() -> None:
            t_rel = now() - wall_start
            while idx[0] < len(events) \
                    and events[idx[0]].t / speed <= t_rel:
                e = events[idx[0]]
                idx[0] += 1
                injected["n"] += 1
                max_lag[0] = max(max_lag[0],
                                 (now() - wall_start) - e.t / speed)
                apply(e)

        def done() -> bool:
            if idx[0] < len(events) or len(sched.queue):
                return False
            for p in hub.list_pods():
                if not p.spec.node_name:
                    return False
            return True

        def step() -> bool:
            inject_due()
            return done()

        deadline = wall_start + timeout_s
        completed = True
        while not done():
            inject_due()
            sched.run_until_idle(on_step=step)
            if done():
                break
            if now() > deadline:
                completed = False
                break
            # idle but incomplete: wait for the next due event or a
            # backoff flush, whichever is sooner
            wait = 0.05
            if idx[0] < len(events):
                due = wall_start + events[idx[0]].t / speed
                wait = min(wait, max(due - now(), 0.0) + 1e-3)
            sleep(wait)
            sched.queue.flush_backoff_completed()
        wall_s = now() - wall_start
    finally:
        sched.close()

    # stats in wall AND trace time; the gates read trace time. A trace
    # may scope its SLO to a uid prefix (overload regimes: best-effort
    # pods are SUPPOSED to wait — gating their p99 would punish correct
    # shedding; the priority pods are the protected class the SLO is
    # about). The audit and survivor counts still cover every pod.
    slo_uids = trace_pod_uids
    slo_prefix = tcfg.get("slo_uid_prefix")
    if slo_prefix:
        scoped = {u for u in trace_pod_uids if u.startswith(slo_prefix)}
        if scoped:
            slo_uids = scoped
    stats_wall = time_to_bind_stats(sched.timelines, uids=slo_uids)
    stats = time_to_bind_stats(sched.timelines, uids=slo_uids,
                               scale=speed)
    slo_verdict = evaluate_slo(stats, trace.slo)
    gate_verdict = evaluate_slo(stats, trace.gate)
    for v, tag in ((slo_verdict, "slo"), (gate_verdict, "gate")):
        for b in v["breaches"]:
            sched.metrics.scenario_slo_breaches.inc(
                metric=f"{tag}:{b['metric']}")
    sched.metrics.scenario_time_to_bind_p99.set(
        stats["time_to_bind_p99_ms"] / 1e3)
    if (not slo_verdict["ok"] or not gate_verdict["ok"]) \
            and getattr(sched, "autopsy", None) is not None:
        # breach → auto-autopsy: the bundle names the filed trace
        # (name/generator/seed/speed) so the incident points straight
        # at the replayable reproducer. Post-close is safe — the
        # flight ring, timelines, and stats are plain host state.
        sched.watchdog.incident(
            "scenario_slo_breach",
            reason=f"replay of trace {trace.name!r} breached its "
                   f"{'SLO' if not slo_verdict['ok'] else 'gate'}",
            details={"trace": trace.name, "generator": trace.generator,
                     "seed": trace.seed, "speed": speed,
                     "stats": stats,
                     "slo_breaches": slo_verdict["breaches"],
                     "gate_breaches": gate_verdict["breaches"]})

    live = hub.list_pods()
    audit = audit_bind_journal(
        hub=hub,
        expected_uids={p.metadata.uid for p in live
                       if p.metadata.uid in trace_pod_uids})
    audit_ok = bool(audit["ok"])

    report = {
        "name": trace.name,
        "generator": trace.generator,
        "seed": trace.seed,
        "speed": speed,
        "events": len(events),
        "injected": injected["n"],
        "completed": completed,
        "wall_s": round(wall_s, 3),
        "trace_s": round(trace.duration(), 3),
        "pods": len(trace_pod_uids),
        "slo_pods": len(slo_uids),
        "survivors": sum(1 for p in live
                         if p.metadata.uid in trace_pod_uids),
        # the shape-family warmup's contract: every compile happened
        # BEFORE the paced clock started (a mid-replay compile stalls
        # injection and silently distorts trace-time waits)
        "device": {
            "warmup_compiles": warm_compiles,
            "mid_replay_compiles": (
                (prof.compiles - warm_compiles)
                if prof is not None else None),
            "launches": prof.launches if prof is not None else None,
        },
        "stats": stats,             # trace-time ms (gated)
        "stats_wall": stats_wall,   # wall ms (informational)
        "slo": {**slo_verdict, "target": dict(trace.slo)},
        "gate": {**gate_verdict, "target": dict(trace.gate)},
        "audit": {k: audit[k] for k in
                  ("ok", "binds", "double_binds", "lost", "too_old")},
        "pacing": {
            "max_lag_s": round(max_lag[0], 3),
            "held": max_lag[0] <= 1.0,
            # 1-core boxes cannot pace injection against a busy drain
            # loop
            "hardware_limited": (os.cpu_count() or 1) < 2
            or max_lag[0] > 1.0,
        },
        "ok": completed and audit_ok and slo_verdict["ok"]
        and gate_verdict["ok"],
    }
    return report
