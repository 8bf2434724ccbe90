"""Run() daemon, maintenance timers, Permit WAIT, and the async binding
cycle (reference: scheduler.go Run, scheduling_queue.go:378-386 flush
goroutines, runtime/waiting_pods_map.go, schedule_one.go:124/270 binding
goroutine + :337 bind-failure requeue)."""

import copy
import functools
import threading
import time

import pytest

from kubernetes_tpu.api.objects import (
    Container,
    LABEL_HOSTNAME,
    Node,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    ResourceRequirements,
)
from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.framework.interface import Code, PermitPlugin, Status
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.plugins.registry import PluginDescriptor, in_tree_registry
from kubernetes_tpu.scheduler import Scheduler


class Clock:
    def __init__(self):
        self.t = 1000.0

    def now(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def mknode(i, cpu="16"):
    name = f"node-{i}"
    return Node(metadata=ObjectMeta(name=name, labels={LABEL_HOSTNAME: name}),
                status=NodeStatus(allocatable={"cpu": cpu, "memory": "32Gi",
                                               "pods": "110"}))


def mkpod(name, cpu="100m"):
    return Pod(metadata=ObjectMeta(name=name),
               spec=PodSpec(containers=[Container(
                   name="c", resources=ResourceRequirements(
                       requests={"cpu": cpu, "memory": "64Mi"}))]))


def mksched(hub, clock=None, registry=None, batch=16):
    cfg = default_config()
    cfg.batch_size = batch
    clock = clock or Clock()
    return Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64),
                     now=clock.now, registry=registry), clock


def bound_node(hub, pod):
    p = hub.get_pod(pod.metadata.uid)
    return p.spec.node_name if p else None


class GatePermit(PermitPlugin):
    """Test permit plugin: WAITs every pod until allowed externally."""

    NAME = "GatePermit"

    def __init__(self, timeout=60.0):
        self.timeout = timeout
        self.seen = []

    def permit(self, state, pod, node_name):
        self.seen.append(pod.metadata.name)
        return Status(code=Code.WAIT, plugin=self.NAME), self.timeout


def registry_with_permit(plugin):
    reg = in_tree_registry()
    reg["GatePermit"] = PluginDescriptor(
        name="GatePermit", points=("permit",),
        factory=lambda args: plugin)
    return reg


def enable_plugin(cfg, name):
    from kubernetes_tpu.config.types import Plugin

    cfg.profiles[0].plugins.multi_point.enabled.append(Plugin(name, 0))


def test_permit_wait_then_allow_binds():
    hub = Hub()
    permit = GatePermit()
    cfg = default_config()
    cfg.batch_size = 16
    enable_plugin(cfg, "GatePermit")
    clock = Clock()
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64),
                      now=clock.now,
                      registry=registry_with_permit(permit))
    hub.create_node(mknode(0))
    p = mkpod("p")
    hub.create_pod(p)
    sched.run_until_idle()
    # parked at permit: reservation held (assumed), not bound, not failed
    assert bound_node(hub, p) == ""
    assert len(sched.framework.waiting_pods) == 1
    assert sched.cache.assumed_pod_count() == 1
    assert sched.stats["scheduled"] == 0
    # an approver allows it: next cycle binds
    wp = sched.framework.waiting_pods.get(p.metadata.uid)
    wp.allow("GatePermit")
    sched.run_until_idle()
    assert bound_node(hub, p) == "node-0"
    assert sched.stats["scheduled"] == 1
    assert sched.cache.assumed_pod_count() == 0


def test_permit_wait_timeout_requeues():
    hub = Hub()
    permit = GatePermit(timeout=30.0)
    cfg = default_config()
    cfg.batch_size = 16
    enable_plugin(cfg, "GatePermit")
    clock = Clock()
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64),
                      now=clock.now,
                      registry=registry_with_permit(permit))
    hub.create_node(mknode(0))
    p = mkpod("p")
    hub.create_pod(p)
    sched.run_until_idle()
    assert len(sched.framework.waiting_pods) == 1
    # the timeout passes with no allow: unreserve + UNSCHEDULABLE requeue
    # attributed to the timing-out plugin (schedule_one.go:270)
    clock.tick(31.0)
    sched.run_maintenance()
    assert len(sched.framework.waiting_pods) == 0
    assert sched.cache.assumed_pod_count() == 0
    assert sched.stats["unschedulable"] == 1
    assert sched.stats["errors"] == 0
    cond = hub.get_pod(p.metadata.uid).status.conditions[0]
    assert cond.reason == "Unschedulable"


def test_permit_reject_while_waiting():
    hub = Hub()
    permit = GatePermit()
    cfg = default_config()
    cfg.batch_size = 16
    enable_plugin(cfg, "GatePermit")
    clock = Clock()
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64),
                      now=clock.now,
                      registry=registry_with_permit(permit))
    hub.create_node(mknode(0))
    p = mkpod("p")
    hub.create_pod(p)
    sched.run_until_idle()
    wp = sched.framework.waiting_pods.get(p.metadata.uid)
    wp.reject("GatePermit", "not today")
    sched.run_until_idle()
    assert bound_node(hub, p) == ""
    assert sched.cache.assumed_pod_count() == 0


def test_bind_failure_unreserves_and_requeues():
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0))
    fails = {"n": 0}
    orig_bind = hub.bind

    def flaky_bind(pod, node_name):
        if fails["n"] == 0:
            fails["n"] += 1
            raise RuntimeError("apiserver hiccup")
        orig_bind(pod, node_name)

    sched.framework.instance("DefaultBinder")._binder = flaky_bind
    p = mkpod("p")
    hub.create_pod(p)
    sched.run_until_idle()
    clock.tick(2.0)
    sched.queue.flush_backoff_completed()
    sched.run_until_idle()
    # first attempt failed at bind (Forget + error-class requeue recorded);
    # the retry then bound cleanly
    assert sched.stats["errors"] == 1
    assert fails["n"] == 1
    assert bound_node(hub, p) == "node-0"
    assert sched.stats["scheduled"] == 1
    assert sched.cache.assumed_pod_count() == 0
    cond_reasons = [c.reason for c in
                    hub.get_pod(p.metadata.uid).status.conditions]
    assert "SchedulerError" in cond_reasons


def test_unschedulable_timeout_flush_without_events():
    """A pod whose rejecting plugin never sees a matching event escapes via
    the 5min cap (scheduling_queue.go:378's flushUnschedulablePodsLeftover),
    driven by run_maintenance's 30s tick."""
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="1"))
    big = mkpod("big", cpu="8")
    hub.create_pod(big)
    sched.run_until_idle()
    assert sched.stats["unschedulable"] == 1
    # grow the node quietly (no hub event => no requeue signal)
    sched.queue._unschedulable[big.metadata.uid].unschedulable_plugins = set()
    clock.tick(301.0)
    sched.run_maintenance()
    counts = sched.queue.pending_counts()
    assert counts["unschedulable"] == 0, "flushed by the 5min cap"
    assert counts["active"] + counts["backoff"] == 1


def wait_for(cond, seconds):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return bool(cond())


def test_daemon_thread_schedules_and_stops():
    """start()/stop(): pods created from a foreign thread while the daemon
    runs are scheduled without explicit drains."""
    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    hub.create_node(mknode(0))
    sched.start()
    try:
        pods = [mkpod(f"p{i}") for i in range(10)]
        for p in pods:
            hub.create_pod(p)
        assert wait_for(lambda: all(bound_node(hub, p) for p in pods), 30)
    finally:
        sched.stop()
    assert sched._daemon is None


def idle_daemon(monkeypatch, idle_sleep):
    """A warmed scheduler over one node whose start() runs the daemon with
    ``idle_sleep``. Returns (hub, sched, idle, hooks): ``idle`` is set
    after each drain that found no pod, and each callable put in ``hooks``
    runs once on the loop thread right there, between that drain and the
    idle wait."""
    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    hub.create_node(mknode(0))
    warm = mkpod("warm")
    hub.create_pod(warm)
    sched.run_until_idle()                  # the compile, before the daemon
    assert bound_node(hub, warm) == "node-0"
    idle = threading.Event()
    hooks = []
    drain = sched.run_until_idle

    def run_until_idle(**kw):
        n = drain(**kw)
        if n == 0:
            while hooks:
                hooks.pop(0)()
            idle.set()
        return n

    monkeypatch.setattr(sched, "run_until_idle", run_until_idle)
    monkeypatch.setattr(sched, "run", functools.partial(
        Scheduler.run, sched, idle_sleep=idle_sleep))
    return hub, sched, idle, hooks


def idle_wait_ends(sched):
    waits = sched.metrics.loop_idle_waits
    return {end: waits.value(end=end) for end in ("event", "timeout")}


def test_an_idle_daemon_binds_a_pod_created_from_another_thread_at_once(
        monkeypatch):
    """A pod created while the loop sleeps ends the sleep: it binds well
    inside an idle_sleep of 5 s, where a polling loop would wait it out."""
    hub, sched, idle, _ = idle_daemon(monkeypatch, idle_sleep=5.0)
    sched.start()
    try:
        assert idle.wait(30)
        p = mkpod("p")
        t0 = time.monotonic()
        hub.create_pod(p)
        assert wait_for(lambda: bound_node(hub, p), 30)
        assert time.monotonic() - t0 < 2.5
        assert idle_wait_ends(sched)["event"] >= 1
    finally:
        sched.stop()


def test_an_event_between_the_empty_drain_and_the_wait_is_not_lost(
        monkeypatch):
    """The wake event is cleared before the drain, not after it: a pod
    created by another thread after the drain found nothing and before
    the loop waits ends that wait at once."""
    hub, sched, _idle, hooks = idle_daemon(monkeypatch, idle_sleep=5.0)
    p = mkpod("p")
    created = []

    def create_from_another_thread():
        t = threading.Thread(target=hub.create_pod, args=(p,))
        t.start()
        t.join()
        created.append(time.monotonic())

    hooks.append(create_from_another_thread)
    sched.start()
    try:
        assert wait_for(lambda: bound_node(hub, p), 30)
        assert time.monotonic() - created[0] < 2.5
        assert idle_wait_ends(sched) == {"event": 1, "timeout": 0}
    finally:
        sched.stop()


def test_a_binder_workers_event_does_not_end_the_wait(monkeypatch):
    """An event raised on a binder worker is deferred, not applied: no
    pod enters the activeQ until the loop replays it, so the wait runs
    out and the next turn schedules the pod."""
    hub, sched, _idle, hooks = idle_daemon(monkeypatch, idle_sleep=1.0)
    p = mkpod("p")
    hooks.append(lambda: sched._binder.submit(hub.create_pod, p).result())
    sched.start()
    try:
        assert wait_for(lambda: bound_node(hub, p), 30)
        ends = idle_wait_ends(sched)
        assert ends["event"] == 0
        assert ends["timeout"] >= 1
    finally:
        sched.stop()


def test_node_updates_from_another_thread_do_not_end_the_wait(monkeypatch):
    """A stream of node heartbeats puts no pod in the activeQ: the idle
    loop keeps turning once an idle_sleep, not once an event."""
    hub, sched, idle, _ = idle_daemon(monkeypatch, idle_sleep=0.5)
    sched.start()
    try:
        assert idle.wait(30)
        turn0, t0 = sched.flight.turn, time.monotonic()

        def heartbeats():
            for i in range(150):
                node = copy.deepcopy(hub.get_node("node-0"))
                node.metadata.labels["heartbeat"] = str(i)
                hub.update_node(node)
                time.sleep(0.01)

        t = threading.Thread(target=heartbeats)
        t.start()
        t.join()
        turns = sched.flight.turn - turn0
        seconds = time.monotonic() - t0
    finally:
        sched.stop()
    assert seconds >= 1.5
    assert idle_wait_ends(sched)["event"] == 1          # stop() alone
    assert turns <= seconds / 0.5 + 2, (turns, seconds)


def test_stop_ends_a_long_idle_wait_promptly(monkeypatch):
    _hub, sched, idle, _ = idle_daemon(monkeypatch, idle_sleep=60.0)
    sched.start()
    assert idle.wait(30)
    t0 = time.monotonic()
    sched.stop()
    assert time.monotonic() - t0 < 5.0
    assert sched._daemon is None
    assert idle_wait_ends(sched)["event"] >= 1


def test_the_idle_wait_counter_counts_how_each_wait_ended(monkeypatch):
    """scheduler_loop_idle_waits_total{end} equals the waits as they
    ended: one ran out, a pod event ended one, stop() ended the last."""
    from kubernetes_tpu.telemetry.fleet import parse_exposition

    hub, sched, _idle, _hooks = idle_daemon(monkeypatch, idle_sleep=1.0)
    ends = []
    wait = sched.queue.wake.wait

    def recording_wait(timeout=None):
        woke = wait(timeout)
        ends.append("event" if woke else "timeout")
        return woke

    monkeypatch.setattr(sched.queue.wake, "wait", recording_wait)
    sched.start()
    try:
        assert wait_for(lambda: "timeout" in ends, 30)
        p = mkpod("p")
        hub.create_pod(p)
        assert wait_for(lambda: bound_node(hub, p), 30)
    finally:
        sched.stop()
    want = {"event": ends.count("event"), "timeout": ends.count("timeout")}
    assert want["event"] >= 2 and want["timeout"] >= 1
    assert idle_wait_ends(sched) == want
    exp = parse_exposition(sched.metrics.registry.render_text())
    assert {s.labels["end"]: s.value for s in exp.samples
            if s.name == "scheduler_loop_idle_waits_total"} == want
    assert exp.type["scheduler_loop_idle_waits_total"] == "counter"


# suite-tier discipline (tests/test_markers.py): area marker
pytestmark = pytest.mark.core
