"""Chaos invariant suite: the scheduler + hub client under injected
faults (kubernetes_tpu/chaos.py). Every scenario asserts the storm
invariants from the fault model (README "Fault model"):

* no double-bind (the hub's bind-once Conflict + informer reconciliation),
* no lost or wedged pod (degraded mode parks with backoff, never drops),
* cache–hub convergence after the storm (reflector relist diff),
* leader failover within the lease duration when the holder is cut off.
"""

import threading
import time

import pytest

from kubernetes_tpu.chaos import ChaosConfig, ChaosHub, ChaosProxy
from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.hub import EventHandlers, Hub, Unavailable
from kubernetes_tpu.hubclient import RemoteHub
from kubernetes_tpu.hubserver import HubServer
from kubernetes_tpu.leaderelection import LeaderElector
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing import MakeNode, MakePod
from kubernetes_tpu.utils.backoff import Backoff, RetryBudget, retry_call

pytestmark = pytest.mark.chaos


# ------------------------------------------------------------------ utils


def test_backoff_decorrelated_jitter_bounds():
    import random

    bo = Backoff(base=0.05, cap=1.0, rng=random.Random(1))
    prev = 0.05
    for _ in range(50):
        s = bo.next()
        assert 0.05 <= s <= min(1.0, max(prev * 3, 0.05) + 1e-9)
        prev = s
    bo.reset()
    assert bo.next() <= 0.15 + 1e-9   # back to base * 3 ceiling


def test_retry_budget_exhausts_and_refills():
    clock = [0.0]
    budget = RetryBudget(budget=3.0, refill_per_sec=1.0,
                         now=lambda: clock[0])
    assert all(budget.try_spend() for _ in range(3))
    assert not budget.try_spend()          # dry: fail fast
    clock[0] += 2.0
    assert budget.try_spend()              # refilled 2 tokens
    assert budget.try_spend()
    assert not budget.try_spend()


def test_retry_call_deadline_and_success():
    clock = [0.0]
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("boom")
        return "ok"

    assert retry_call(flaky, retry_on=(OSError,), deadline=10.0,
                      sleep=lambda s: clock.__setitem__(0, clock[0] + s),
                      now=lambda: clock[0]) == "ok"
    calls.clear()
    with pytest.raises(OSError):
        retry_call(lambda: (_ for _ in ()).throw(OSError("x")),
                   retry_on=(OSError,), deadline=0.0,
                   sleep=lambda s: None, now=lambda: clock[0])


# -------------------------------------------------------------- ChaosHub


def test_chaoshub_injects_and_heals():
    hub = Hub()
    chub = ChaosHub(hub, ChaosConfig(seed=3, call_error_rate=1.0))
    with pytest.raises(Unavailable):
        chub.create_node(MakeNode().name("n").obj())
    chub.set_fault(call_error_rate=0.0)
    chub.create_node(MakeNode().name("n").obj())
    assert hub.get_node("n") is not None
    chub.partition_for(30.0)
    with pytest.raises(Unavailable):
        chub.list_pods()
    with pytest.raises(Unavailable):
        chub.leases.get("x")               # leases are RPCs too
    chub.heal()
    assert chub.list_pods() == []
    stats = chub.chaos_stats()
    assert stats["injected_errors"] == 3
    assert stats["calls_seen"] >= 5


def test_chaoshub_deterministic_by_seed():
    def draw_sequence(seed):
        hub = Hub()
        chub = ChaosHub(hub, ChaosConfig(seed=seed, call_error_rate=0.5))
        out = []
        for _ in range(40):
            try:
                chub.list_pods()
                out.append(0)
            except Unavailable:
                out.append(1)
        return out

    assert draw_sequence(11) == draw_sequence(11)
    assert draw_sequence(11) != draw_sequence(12)


# ------------------------------------------------------------ ChaosProxy


@pytest.fixture()
def proxied_hub():
    hub = Hub()
    server = HubServer(hub).start()
    proxy = ChaosProxy(server.address, config=ChaosConfig(seed=5)).start()
    client = RemoteHub(proxy.address, timeout=10.0, retry_deadline=5.0,
                       retry_base=0.01, retry_cap=0.1)
    yield hub, proxy, client
    client.close()
    proxy.stop()
    server.stop()


def test_idempotent_calls_retry_through_flaky_proxy(proxied_hub):
    hub, proxy, client = proxied_hub
    hub.create_node(MakeNode().name("n1").obj())
    proxy.set_fault(call_error_rate=0.5)
    for _ in range(10):                    # each likely hits ≥1 injected 503
        assert client.get_node("n1") is not None
    assert client.resilience_stats()["retries"] > 0
    assert proxy.stats["injected_errors"] > 0


def test_nonidempotent_calls_fail_fast_as_unavailable(proxied_hub):
    hub, proxy, client = proxied_hub
    proxy.set_fault(call_error_rate=1.0)
    before = client.resilience_stats()["retries"]
    with pytest.raises(Unavailable):
        client.create_pod(MakePod().name("p").obj())
    assert client.resilience_stats()["retries"] == before  # no blind replay
    assert not client.connected
    proxy.set_fault(call_error_rate=0.0)
    p = MakePod().name("p").obj()
    client.create_pod(p)
    assert client.connected
    assert hub.get_pod(p.metadata.uid) is not None


def test_watch_cuts_reconnect_without_loss_or_dupes(proxied_hub):
    hub, proxy, client = proxied_hub
    proxy.set_fault(watch_cut_every=3)     # die every third event
    added = []
    client.watch_nodes(EventHandlers(
        on_add=lambda o: added.append(o.metadata.name)))
    names = [f"n-{i}" for i in range(12)]
    for name in names:
        hub.create_node(MakeNode().name(name).obj())
        time.sleep(0.02)
    deadline = time.time() + 20
    while time.time() < deadline and len(set(added)) < len(names):
        time.sleep(0.05)
    assert sorted(set(added)) == sorted(names), \
        "every add must survive the cuts"
    assert len(added) == len(names), "relist must not duplicate adds"
    assert client.resilience_stats()["watch_reconnects"] > 0
    assert proxy.stats["injected_cuts"] > 0


def test_initial_watch_survives_hub_binding_late():
    """The first connect() is guarded: a client whose hub isn't listening
    yet must come up once the hub does (scheduler startup vs hub race)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    client = RemoteHub(f"http://127.0.0.1:{port}", timeout=10.0,
                       retry_deadline=8.0, retry_base=0.02, retry_cap=0.2)
    hub = Hub()
    hub.create_node(MakeNode().name("late").obj())
    seen = []
    err = []

    def start_watch():
        try:
            client.watch_nodes(EventHandlers(
                on_add=lambda o: seen.append(o.metadata.name)))
        except Exception as e:  # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=start_watch, daemon=True)
    t.start()
    time.sleep(0.5)                        # client is retrying against ECONNREFUSED
    server = HubServer(hub, port=port).start()
    try:
        t.join(timeout=10)
        assert not err, f"guarded connect must not raise: {err}"
        assert seen == ["late"]
    finally:
        client.close()
        server.stop()


def test_watcher_handles_pruned_on_reconnect(proxied_hub):
    hub, proxy, client = proxied_hub
    proxy.set_fault(watch_cut_every=1)     # cut at the 2nd live event
    client.watch_nodes(EventHandlers(on_add=lambda o: None))
    deadline = time.time() + 15
    i = 0
    while time.time() < deadline \
            and client.resilience_stats()["watch_reconnects"] < 3:
        hub.create_node(MakeNode().name(f"n-{i}").obj())
        i += 1
        time.sleep(0.1)
    assert client.resilience_stats()["watch_reconnects"] >= 3
    # one reflector = at most one live handle tracked, not one per reconnect
    assert len(client._watchers) <= 1


# ---------------------------------------------------- scheduler scenarios


def _wait(pred, timeout_s: float, interval: float = 0.05) -> bool:
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def test_scheduler_survives_partition_during_binding():
    """Partition the wire while bindings are in flight: afterwards every
    pod is bound exactly once, nothing is lost, and the cache converges
    against the hub (the ISSUE's headline invariant)."""
    hub = Hub()
    server = HubServer(hub).start()
    proxy = ChaosProxy(server.address, config=ChaosConfig(seed=9)).start()
    client = RemoteHub(proxy.address, timeout=10.0, retry_deadline=2.0,
                       retry_base=0.01, retry_cap=0.1)
    for i in range(6):
        hub.create_node(MakeNode().name(f"n-{i}").capacity(cpu="64").obj())
    cfg = default_config()
    cfg.batch_size = 8
    sched = Scheduler(client, cfg, caps=Capacities(nodes=16, pods=256))
    try:
        sched.start()
        pods = [MakePod().name(f"p-{i}").req(cpu="100m").obj()
                for i in range(48)]
        for p in pods:
            hub.create_pod(p)

        def bound_count():
            return sum(1 for p in hub.list_pods() if p.spec.node_name)

        assert _wait(lambda: bound_count() >= 4, 30), "no binding started"
        proxy.partition_for(1.5)           # mid-storm partition
        assert _wait(lambda: bound_count() == len(pods), 60), \
            f"lost pods: {len(pods) - bound_count()} unbound"
        # exactly-once: every pod bound to exactly one node, and the
        # hub's bind-once Conflict means no uid can be double-bound
        for p in hub.list_pods():
            assert p.spec.node_name, f"{p.metadata.name} unbound"
        # convergence: reflector relist + assume/confirm settle
        assert _wait(lambda: not sched.cache.compare_with_hub(hub), 20), \
            sched.cache.compare_with_hub(hub)
    finally:
        sched.close()
        client.close()
        proxy.stop()
        server.stop()


def test_scheduler_parks_not_errors_when_hub_unreachable():
    """Full outage (in-process ChaosHub partition): the drain loop parks
    pods with backoff instead of erroring them, preserves assumed state,
    and schedules everything once the hub heals."""
    hub = Hub()
    chub = ChaosHub(hub)
    for i in range(4):
        chub.create_node(MakeNode().name(f"n-{i}").capacity(cpu="32").obj())
    cfg = default_config()
    cfg.batch_size = 8
    sched = Scheduler(chub, cfg, caps=Capacities(nodes=8, pods=64))
    try:
        for i in range(10):
            chub.create_pod(MakePod().name(f"p-{i}").req(cpu="100m").obj())
        chub.partition_for(600.0)
        attempted = sched.run_until_idle()      # must NOT raise
        assert attempted > 0
        assert sched.stats["errors"] == 0, "outage must not count as errors"
        assert sched.stats["parked_unreachable"] > 0
        assert sched.hub_degraded()
        assert sum(1 for p in hub.list_pods() if p.spec.node_name) == 0
        chub.heal()
        sched.run_maintenance()                 # probe clears degraded
        assert not sched.hub_degraded()
        deadline = time.time() + 15
        while time.time() < deadline:
            time.sleep(0.3)                     # let the park backoff lapse
            sched.run_maintenance()
            if sched.run_until_idle() == 0 and all(
                    p.spec.node_name for p in hub.list_pods()):
                break
        assert all(p.spec.node_name for p in hub.list_pods()), \
            "parked pods must schedule after heal"
        assert sched.cache.compare_with_hub(hub) == []
    finally:
        sched.close()


def test_assumed_pods_preserved_while_degraded():
    """cleanup_assumed_pods must not expire optimistic placements while
    their confirm events cannot arrive (double-scheduling guard)."""
    clock = [1000.0]
    hub = Hub()
    chub = ChaosHub(hub)
    chub.create_node(MakeNode().name("n-0").capacity(cpu="32").obj())
    cfg = default_config()
    cfg.async_binding = False
    sched = Scheduler(chub, cfg, caps=Capacities(nodes=8, pods=64),
                      now=lambda: clock[0])
    try:
        pod = MakePod().name("p").req(cpu="100m").obj()
        chub.create_pod(pod)
        sched.run_until_idle()
        assert hub.get_pod(pod.metadata.uid).spec.node_name
        # simulate: confirm event never arrived (drop it from the cache's
        # view by assuming a fresh pod directly)
        ghost = MakePod().name("ghost").req(cpu="100m").obj()
        ghost.spec.node_name = "n-0"
        sched.cache._ttl = 30.0             # default 0 = never expire
        sched.cache.assume_pod(ghost)
        sched.cache.finish_binding(ghost)   # start the expiry clock
        chub.partition_for(3600.0)
        sched._hub_down = True
        clock[0] += 600.0                       # way past assume TTL + flush
        sched.run_maintenance()                 # degraded: no expiry
        assert sched.cache.assumed_pod_count() >= 1
        chub.heal()
        sched._hub_down = False
        clock[0] += 31.0                        # reopen the 30s flush gate
        sched.run_maintenance()                 # healthy: expiry resumes
        assert sched.cache.assumed_pod_count() == 0
    finally:
        sched.close()


# ------------------------------------------------------- leader election


def test_leader_failover_within_lease_duration():
    """Cut the leader off from the lease store: it steps down by the
    renew deadline and a healthy peer takes over within lease_duration."""
    hub = Hub()
    server = HubServer(hub).start()
    proxy = ChaosProxy(server.address).start()
    cut_client = RemoteHub(proxy.address, timeout=5.0, retry_deadline=0.2,
                           retry_base=0.01, retry_cap=0.05)
    clock = time.monotonic
    lease_duration, renew_deadline = 2.0, 1.0
    a = LeaderElector(cut_client.leases, "a",
                      lease_duration=lease_duration,
                      renew_deadline=renew_deadline, retry_period=0.1,
                      now=clock)
    b = LeaderElector(hub.leases, "b", lease_duration=lease_duration,
                      renew_deadline=renew_deadline, retry_period=0.1,
                      now=clock)
    try:
        assert a.tick() and a.is_leader()
        assert not b.tick()                    # lease held by a
        t0 = clock()
        proxy.partition_for(3600.0)            # a is cut off
        stepped_down = failover = None
        deadline = clock() + 3 * lease_duration
        while clock() < deadline and failover is None:
            a.tick()                           # must not raise
            if stepped_down is None and not a.is_leader():
                stepped_down = clock() - t0
            if b.tick():
                failover = clock() - t0
            time.sleep(0.05)
        assert stepped_down is not None, "cut-off leader never stepped down"
        assert stepped_down <= renew_deadline + 1.0
        assert failover is not None, "peer never took over"
        assert failover <= lease_duration + 1.0, \
            f"failover took {failover:.1f}s > lease_duration"
        assert a.transport_errors > 0
        assert not a.is_leader() and b.is_leader()
    finally:
        cut_client.close()
        proxy.stop()
        server.stop()


def test_elector_release_survives_dead_store():
    class DeadStore:
        def get(self, name):
            raise OSError("store down")

        def update(self, lease, expect_holder):
            raise OSError("store down")

    el = LeaderElector(DeadStore(), "x", retry_period=0.0)
    assert el.tick() is False                  # no crash
    el._leading = True                         # pretend we were leading
    el._last_renew = el.now()
    el.release()                               # best-effort, no crash
    assert not el.is_leader()
    assert el.transport_errors >= 2


# ------------------------------------------------------------ serving


def test_readyz_reflects_degraded_state():
    import urllib.error
    import urllib.request

    from kubernetes_tpu.serving import ServingEndpoints

    hub = Hub()
    chub = ChaosHub(hub)
    sched = Scheduler(chub, default_config(),
                      caps=Capacities(nodes=8, pods=64))
    serving = ServingEndpoints(sched)
    serving.start()
    try:
        url = f"http://127.0.0.1:{serving.port}/readyz"
        assert urllib.request.urlopen(url).status == 200
        sched._hub_down = True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url)
        assert ei.value.code == 503
        # /metrics exposes the resilience surface
        sched._export_resilience_metrics()
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{serving.port}/metrics").read().decode()
        assert "scheduler_hub_degraded 1.0" in text
        assert "chaos_injected_faults" in text
        sched._hub_down = False
    finally:
        serving.stop()
        sched.close()


# --------------------------------------- watch-resume at Daemonset scale


def test_midwatch_cut_at_15k_nodes_resumes_with_zero_relists():
    """The Daemonset-15k reconnect storm the journal exists to kill: a
    reflector synced over 15 000 nodes loses its stream mid-watch; the
    reconnect must RESUME from since_rv (replaying only the gap events)
    — zero relists, zero duplicate adds — because a full 15k-object
    relist per reconnect is exactly the L0 cost etcd's revision-resumed
    watches avoid."""
    hub = Hub()                      # default ring >> the gap size
    server = HubServer(hub).start()
    proxy = ChaosProxy(server.address, config=ChaosConfig(seed=3)).start()
    client = RemoteHub(proxy.address, timeout=30.0, retry_base=0.01,
                       retry_cap=0.1)
    n_nodes = 15_000
    for i in range(n_nodes):
        hub.create_node(MakeNode().name(f"n{i}").obj())
    adds, updates = [], []
    try:
        client.watch_nodes(EventHandlers(
            on_add=lambda o: adds.append(o.metadata.name),
            on_update=lambda old, new: updates.append(new.metadata.name)))
        assert len(adds) == n_nodes, "initial LIST replay synced"
        # cut the stream on the next live event (that event is dropped
        # from the wire — only the journal can deliver it now)
        proxy.set_fault(watch_cut_rate=1.0)
        upd = hub.get_node("n0").clone()
        upd.metadata.labels["touched"] = "1"
        hub.update_node(upd)
        # while the stream is down, more of the gap accumulates
        deadline = time.time() + 10
        while proxy.stats["injected_cuts"] < 1 and time.time() < deadline:
            time.sleep(0.02)
        proxy.set_fault(watch_cut_rate=0.0)
        for i in range(1, 6):
            u = hub.get_node(f"n{i}").clone()
            u.metadata.labels["touched"] = "1"
            hub.update_node(u)
        deadline = time.time() + 30
        while time.time() < deadline and len(updates) < 6:
            time.sleep(0.05)
        assert sorted(set(updates)) == [f"n{i}" for i in range(6)], \
            "every gap event must arrive through the journal resume"
        stats = client.resilience_stats()
        assert stats["watch_resumes"] >= 1, stats
        assert stats["watch_relists"] == 0, \
            f"a 15k-node relist storm happened: {stats}"
        assert len(adds) == n_nodes, "no duplicate adds (no relist ran)"
        assert proxy.stats["injected_cuts"] >= 1
    finally:
        client.close()
        proxy.stop()
        server.stop()


# ------------------------- the self-healing core (fencing / ladder / etc)


def test_inflight_async_bind_rejected_fenced_after_failover():
    """Satellite: leader failover with in-flight async binds. The old
    leader's late Hub.bind must be rejected Fenced (no double-place);
    the new leader then schedules the pod exactly once."""
    from kubernetes_tpu.leaderelection import LeaderElector

    hub = Hub()
    hub.create_node(MakeNode().name("n").capacity(cpu="8").obj())
    elector_a = LeaderElector(hub.leases, "a", lease_duration=0.5,
                              renew_deadline=0.3, retry_period=0.05,
                              now=time.monotonic)
    elector_b = LeaderElector(hub.leases, "b", lease_duration=0.5,
                              renew_deadline=0.3, retry_period=0.05,
                              now=time.monotonic)

    class StallHub:
        """Delegating hub whose bind stalls long enough for the caller
        to be deposed mid-flight (the async binder pool race)."""

        def __init__(self, inner):
            self._inner = inner
            self.stall = None       # callable run before the first bind

        def bind(self, *args):
            if self.stall is not None:
                stall, self.stall = self.stall, None
                stall()
            return self._inner.bind(*args)

        def __getattr__(self, name):
            return getattr(self._inner, name)

    shub = StallHub(hub)
    cfg = default_config()
    sched_a = Scheduler(shub, cfg, caps=Capacities(nodes=8, pods=64))
    sched_a._elector = elector_a
    assert elector_a.tick() and elector_a.epoch == 1
    binds = []
    hub.watch_pods(EventHandlers(
        on_update=lambda old, new: binds.append(new.metadata.uid)
        if not old.spec.node_name and new.spec.node_name else None),
        replay=False)

    def depose_a():
        # runs on the binder thread, after a's launch chose a node but
        # before its bind lands: a's lease expires, b acquires
        time.sleep(0.6)
        assert elector_b.try_acquire_or_renew()
        assert elector_b.epoch == 2

    shub.stall = depose_a
    pod = MakePod().name("p").req(cpu="100m").obj()
    hub.create_pod(pod)
    try:
        sched_a.run_until_idle()           # must not raise
        assert hub.get_pod(pod.metadata.uid).spec.node_name == "", \
            "deposed leader's in-flight bind must be rejected"
        assert sched_a.stats["fenced"] == 1
        assert sched_a.metrics.fenced_writes.value(verb="bind") == 1, \
            "the BIND must be what was fenced (not a follow-on patch)"
        assert sched_a.stats["errors"] == 0, \
            "a fenced bind is not a scheduler error"
        assert sched_a.cache.assumed_pod_count() == 0, \
            "fenced bind must release its optimistic claim"
        # the new leader schedules it exactly once
        sched_b = Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=64))
        sched_b._elector = elector_b
        try:
            sched_b.run_until_idle()
            assert hub.get_pod(pod.metadata.uid).spec.node_name == "n"
            assert binds == [pod.metadata.uid], \
                f"pod must bind exactly once, saw {binds}"
        finally:
            sched_b.close()
    finally:
        sched_a.close()


@pytest.mark.quarantine
def test_rebucket_nonconvergence_parks_batch_daemon_survives(monkeypatch):
    """Satellite regression: the re-bucketing RuntimeError used to
    escape the scheduling loop and kill the daemon; now the batch
    degrades to the host path and everything still schedules."""
    from kubernetes_tpu.backend.mirror import CapacityError, Mirror

    hub = Hub()
    for i in range(2):
        hub.create_node(MakeNode().name(f"n-{i}").capacity(cpu="8").obj())
    cfg = default_config()
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=8, pods=64))

    def always_overflow(self, pods, batch_size):
        raise CapacityError("nodes", 64)

    monkeypatch.setattr(Mirror, "prepare_launch", always_overflow)
    try:
        for i in range(6):
            hub.create_pod(MakePod().name(f"p-{i}").req(cpu="100m").obj())
        sched.run_until_idle()             # must not raise
        assert sched.stats["device_fallbacks"] >= 1
        assert all(p.spec.node_name for p in hub.list_pods()), \
            "host fallback must still place the batch"
    finally:
        sched.close()


def test_keepalive_backs_off_on_persistent_error(monkeypatch):
    """Satellite: a persistent scheduling-loop error must not busy-spin
    the keep-alive — decorrelated backoff paces retries and
    scheduler_cycle_crashes_total counts them."""
    hub = Hub()
    sched = Scheduler(hub, default_config(),
                      caps=Capacities(nodes=8, pods=64))
    monkeypatch.setattr(
        sched, "run_maintenance",
        lambda: (_ for _ in ()).throw(RuntimeError("persistent")))
    stop = threading.Event()
    t = threading.Thread(target=sched.run, args=(stop,), daemon=True)
    t.start()
    time.sleep(1.2)
    stop.set()
    t.join(timeout=5)
    try:
        crashes = sched.metrics.cycle_crashes.value()
        assert crashes >= 1, "keep-alive must record the crash"
        assert crashes <= 5, \
            f"{crashes} crashes in 1.2s: the keep-alive is busy-spinning"
        assert isinstance(sched.daemon_error, RuntimeError)
    finally:
        sched.close()


def test_condition_patch_drops_are_counted():
    """Satellite: degraded-mode (and fenced) condition-patch drops are
    counted so operators can see lost status."""
    from kubernetes_tpu.api.objects import PodCondition
    from kubernetes_tpu.leaderelection import Lease

    hub = Hub()
    chub = ChaosHub(hub)
    sched = Scheduler(chub, default_config(),
                      caps=Capacities(nodes=8, pods=64))
    try:
        pod = MakePod().name("p").req(cpu="100m").obj()
        hub.create_pod(pod)
        cond = PodCondition(type="PodScheduled", status="False",
                            reason="Unschedulable")
        chub.partition_for(60.0)
        sched._patch_condition_best_effort(pod, cond)
        m = sched.metrics.condition_patches_dropped
        assert m.value(reason="unavailable") == 1
        chub.heal()
        # fenced drop: our epoch predates an acquisition we never made
        hub.leases.update(Lease(name="kube-scheduler",
                                holder_identity="other"), None)

        class Tok:
            epoch = 0
            lease_name = "kube-scheduler"

        sched._elector = Tok()
        sched._patch_condition_best_effort(pod, cond)
        assert m.value(reason="fenced") == 1
        assert sched.metrics.fenced_writes.value(
            verb="patch_pod_condition") == 1
    finally:
        sched._elector = None
        sched.close()


def test_fenced_error_roundtrips_the_wire(proxied_hub):
    """Fenced must survive the HTTP hop typed (the RPC layer's analog
    of the apiserver's 403), not decay into RemoteError."""
    from kubernetes_tpu.hub import Fenced
    from kubernetes_tpu.leaderelection import Lease

    hub, proxy, client = proxied_hub
    pod = MakePod().name("p").req(cpu="100m").obj()
    hub.create_pod(pod)
    hub.create_node(MakeNode().name("n").obj())
    hub.leases.update(Lease(name="kube-scheduler",
                            holder_identity="leader"), None)
    with pytest.raises(Fenced):
        client.bind(pod, "n", 0, "kube-scheduler")
    assert hub.get_pod(pod.metadata.uid).spec.node_name == ""
    client.bind(pod, "n", hub.leases.epoch_of("kube-scheduler"),
                "kube-scheduler")
    assert hub.get_pod(pod.metadata.uid).spec.node_name == "n"


def test_deposed_leader_evictions_and_clears_are_fenced():
    """Regression (ROADMAP carried-over gap): a deposed leader's QUEUED
    preemption evictions and nomination clears must be rejected Fenced at
    the hub — the new leader may have re-planned around those victims —
    and the whole backlog dropped, not replayed under a newer epoch."""
    from kubernetes_tpu.framework.preemption import Candidate
    from kubernetes_tpu.leaderelection import Lease

    hub = Hub()
    hub.create_node(MakeNode().name("n").capacity(cpu="8").obj())
    victim = MakePod().name("victim").req(cpu="100m").obj()
    victim.spec.node_name = "n"
    hub.create_pod(victim)
    nominee = MakePod().name("nominee").req(cpu="100m").obj()
    nominee.status.nominated_node_name = "n"
    hub.create_pod(nominee)
    sched = Scheduler(hub, default_config(),
                      caps=Capacities(nodes=8, pods=64))
    try:
        # another scheduler took the lease: our (fake) elector's epoch 0
        # predates its acquisition — every fenced write must bounce
        hub.leases.update(Lease(name="kube-scheduler",
                                holder_identity="other"), None)

        class Tok:
            epoch = 0
            lease_name = "kube-scheduler"

        sched._elector = Tok()
        preemptor = MakePod().name("preemptor").req(cpu="100m").obj()
        sched.preemption.prepare_candidate(
            Candidate(node_name="n", row=0, victims=[victim],
                      pdb_violations=0), preemptor)
        sched.preemption.flush_evictions()
        assert hub.get_pod(victim.metadata.uid) is not None, \
            "a deposed leader's queued eviction must NOT land"
        assert sched.metrics.fenced_writes.value(verb="delete_pod") == 1
        assert not sched.preemption._pending, \
            "the eviction backlog must be dropped, not replayed"
        assert preemptor.metadata.uid not in sched.preemption.preempting, \
            "stranded preemptors must be ungated for the retry path"
        # deferred nomination-clear replays are fenced the same way
        sched.preemption._pending_clears.append(nominee.metadata.uid)
        sched.preemption.flush_evictions()
        assert hub.get_pod(
            nominee.metadata.uid).status.nominated_node_name == "n", \
            "a deposed leader's queued nomination clear must NOT land"
        assert sched.metrics.fenced_writes.value(
            verb="clear_nominated_node") == 1
        assert not sched.preemption._pending_clears
        # re-elected with the CURRENT epoch, the same flush goes through
        class Tok2:
            epoch = hub.leases.epoch_of("kube-scheduler")
            lease_name = "kube-scheduler"

        sched._elector = Tok2()
        sched.preemption._pending_clears.append(nominee.metadata.uid)
        sched.preemption.flush_evictions()
        assert hub.get_pod(
            nominee.metadata.uid).status.nominated_node_name == ""
    finally:
        sched._elector = None
        sched.close()


@pytest.mark.quarantine
def test_device_fault_storm_ladder_and_quarantine():
    """The device-fault storm gate, small: injected launch errors +
    NaN-poisoned results + a genuine poison pod; every healthy pod
    binds, the poison pod is quarantined with a hub Event, zero daemon
    deaths (``chaos --storm all`` runs the full battery)."""
    from kubernetes_tpu.chaos import run_device_storm

    report = run_device_storm(pods=24, nodes=4, seed=11)
    assert report["ok"], report


@pytest.mark.quarantine
def test_quarantine_releases_with_escalating_backoff():
    """A quarantined pod re-enters the queue after its backoff and, on
    re-offense, re-quarantines with a doubled window."""
    from kubernetes_tpu.chaos import make_poison_pod

    clock = [1000.0]
    hub = Hub()
    hub.create_node(MakeNode().name("n").capacity(cpu="8").obj())
    sched = Scheduler(hub, default_config(),
                      caps=Capacities(nodes=8, pods=64),
                      now=lambda: clock[0])
    try:
        poison = make_poison_pod("bad")
        hub.create_pod(poison)
        sched.run_until_idle()
        uid = poison.metadata.uid
        assert uid in sched.quarantined_uids()
        until1 = sched._quarantine[uid]["until"]
        assert until1 - clock[0] == pytest.approx(5.0)
        clock[0] = until1 + 0.1
        sched.run_maintenance()                # released back to queue
        assert uid not in sched.quarantined_uids()
        sched.run_until_idle()                 # re-offends immediately
        assert uid in sched.quarantined_uids()
        until2 = sched._quarantine[uid]["until"]
        assert until2 - clock[0] == pytest.approx(10.0), \
            "re-offense must double the quarantine window"
        events = [e for e in hub.list_events(ref_kind="Pod")
                  if e.reason == "Quarantined"]
        assert events and events[0].count >= 1
    finally:
        sched.close()


@pytest.mark.quarantine
def test_quarantine_holds_through_informer_updates():
    """A controller status patch (or relist replay) for a quarantined
    pod must not re-queue it — that would reset the escalating backoff;
    the freshened spec rides along for the eventual release."""
    from kubernetes_tpu.chaos import make_poison_pod

    clock = [1000.0]
    hub = Hub()
    hub.create_node(MakeNode().name("n").capacity(cpu="8").obj())
    sched = Scheduler(hub, default_config(),
                      caps=Capacities(nodes=8, pods=64),
                      now=lambda: clock[0])
    try:
        poison = make_poison_pod("bad")
        hub.create_pod(poison)
        sched.run_until_idle()
        uid = poison.metadata.uid
        assert uid in sched.quarantined_uids()
        # a controller annotates the pod mid-quarantine
        upd = hub.get_pod(uid).clone()
        upd.metadata.labels["touched"] = "1"
        hub.update_pod(upd)
        assert uid in sched.quarantined_uids()
        assert sched.queue.pending_counts() == {
            k: 0 for k in sched.queue.pending_counts()}, \
            "the update must not re-queue the quarantined pod"
        assert sched.run_until_idle() == 0
        # release re-fetches hub truth, so the newest spec comes back
        clock[0] = sched._quarantine[uid]["until"] + 0.1
        sched.run_maintenance()
        assert uid not in sched.quarantined_uids()
        assert sched.queue.pending_counts()["active"] == 1
    finally:
        sched.close()


# ------------------------------------------------- the full storm (slow)


@pytest.mark.slow
def test_chaos_smoke_storm():
    """scheduler + kubemark hollow nodes through the proxy under call
    faults, watch cuts, and a partition (``chaos --storm smoke``)."""
    from kubernetes_tpu.chaos import run_smoke

    report = run_smoke(pods=30, nodes=6, seed=7)
    assert report["ok"], report


@pytest.mark.slow
@pytest.mark.quarantine
def test_chaos_crash_storm():
    """The acceptance storm, scaled down for the suite: device faults +
    watch cuts + leader kill + kill-and-restart; every pod bound exactly
    once, poison quarantined, zero daemon deaths (``chaos --storm crash``
    runs it at >=1k pods)."""
    from kubernetes_tpu.chaos import run_crash_storm

    report = run_crash_storm(pods=150, nodes=8, seed=13, timeout_s=120.0)
    assert report["ok"], report


@pytest.mark.slow
@pytest.mark.gang
def test_chaos_gang_storm():
    """Gang atomicity under leader kill mid-commit, scaled down for the
    suite: every gang lands fully or not at all (zero partial gangs on
    the bind ledger), no duplicate binds, no leaked assumed pods
    (``chaos --storm gang`` runs it at full size)."""
    from kubernetes_tpu.chaos import run_gang_storm

    report = run_gang_storm(gangs=6, nodes=10, seed=17, timeout_s=150.0)
    assert report["ok"], report
