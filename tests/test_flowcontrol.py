"""Flow control & overload protection (fabric/flowcontrol + the 429
wire contract + scheduler brownout): the stack's analog of the
reference's API Priority and Fairness
(staging/src/k8s.io/apiserver/pkg/util/flowcontrol) — priority levels
with bounded concurrency shares, shuffle-sharded fair queues,
queue-wait deadlines, and honest typed rejections (HTTP 429 +
Retry-After) that clients retry WITHIN their existing budget, never
blindly for non-idempotent verbs."""

import threading
import time

import pytest

from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.fabric.flowcontrol import (
    DEFAULT_LEVELS,
    FlowController,
    LevelConfig,
    classify_call,
)
from kubernetes_tpu.hub import Hub, TooManyRequests
from kubernetes_tpu.hubclient import RemoteHub
from kubernetes_tpu.hubserver import HubServer
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing import MakeNode, MakePod

pytestmark = pytest.mark.flowcontrol


# ------------------------------------------------------------------
# classification: identity ≻ verb ≻ anonymity
# ------------------------------------------------------------------


def test_classify_identity_outranks_verb():
    # a scheduler's LIST is scheduler traffic, not best-effort
    assert classify_call("list_pods", [], "scheduler-3") == \
        ("scheduler", "scheduler-3")
    assert classify_call("list_pods", [], "relay-east") == \
        ("system", "relay-east")
    # verb outranks anonymity: an unidentified bind still rides the
    # binding level (progress over protocol)
    level, _ = classify_call("bind", [], None)
    assert level == "scheduler"


def test_classify_tenant_and_anonymous():
    pod = MakePod().name("w").namespace("team-a").obj()
    assert classify_call("create_pod", [pod], None) == \
        ("tenant", "team-a")
    # ns/name key strings attribute the same way
    assert classify_call("get_pod_group", ["team-b/pg"], None) == \
        ("tenant", "team-b")
    # attributed-but-namespace-less callers are tenants of their own
    # identity; fully anonymous namespace-less reads are best-effort
    assert classify_call("list_nodes", [], "ci-bot") == \
        ("tenant", "ci-bot")
    assert classify_call("list_nodes", [], None) == \
        ("best-effort", "anon")


# ------------------------------------------------------------------
# admission: seats, bounded queues, deadlines, seat handoff
# ------------------------------------------------------------------


def test_seats_then_bounded_queue_then_429():
    fc = FlowController(total_concurrency=10, levels={
        "best-effort": LevelConfig(share=0.1, queues=1, queue_depth=2,
                                   queue_wait_s=0.2)})
    # share 0.1 of 10 -> exactly 1 seat
    fc.admit("best-effort", "anon")
    started, admitted = [], []

    def waiter():
        started.append(1)
        fc.admit("best-effort", "anon")
        admitted.append(1)
        fc.release("best-effort")

    threads = [threading.Thread(target=waiter) for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 2.0
    while fc.stats()["levels"]["best-effort"]["queue_depth"] < 2 \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    # seat taken + queue at its bound: the next request is rejected
    # IMMEDIATELY (full queue), with an honest Retry-After hint
    with pytest.raises(TooManyRequests) as ei:
        fc.admit("best-effort", "anon")
    assert ei.value.retry_after > 0
    # releasing the seat hands it to a queued waiter (no 429 for them)
    fc.release("best-effort")
    for t in threads:
        t.join(timeout=2.0)
    assert len(admitted) == 2
    s = fc.stats()["levels"]["best-effort"]
    assert s["rejected_full"] == 1
    assert s["rejected_timeout"] == 0
    assert s["depth_peak"] <= s["queue_depth_bound"]


def test_queue_wait_deadline_answers_429():
    fc = FlowController(total_concurrency=10, levels={
        "best-effort": LevelConfig(share=0.1, queues=1, queue_depth=4,
                                   queue_wait_s=0.05)})
    fc.admit("best-effort", "anon")      # hold the only seat
    t0 = time.monotonic()
    with pytest.raises(TooManyRequests):
        fc.admit("best-effort", "anon")  # queues, then deadline fires
    assert time.monotonic() - t0 >= 0.05
    s = fc.stats()["levels"]["best-effort"]
    assert s["rejected_timeout"] == 1
    fc.release("best-effort")
    assert fc.stats()["levels"]["best-effort"]["in_flight"] == 0


def test_levels_are_isolated():
    """One level at its share does not consume another level's seats —
    the priority-isolation property the overload storm gates on."""
    fc = FlowController(total_concurrency=10)
    # saturate best-effort completely (seats + queue)
    fc.admit("best-effort", "anon")
    # system and scheduler admission is untouched
    for lv in ("system", "scheduler", "tenant"):
        fc.admit(lv, "x")
        fc.release(lv)
    s = fc.stats()["levels"]
    assert s["system"]["rejected_full"] == 0
    assert s["scheduler"]["rejected_full"] == 0
    fc.release("best-effort")


def test_default_levels_shares_cover_the_budget():
    total = sum(cfg.share for cfg in DEFAULT_LEVELS.values())
    assert total == pytest.approx(1.0)
    fc = FlowController(total_concurrency=64)
    seats = {n: lv["seats"] for n, lv in fc.stats()["levels"].items()}
    assert seats["system"] >= seats["tenant"] >= seats["best-effort"]


# ------------------------------------------------------------------
# the 429 wire contract: typed rejections, retry budget, idempotency
# ------------------------------------------------------------------


@pytest.fixture()
def throttled_hub():
    """A served hub whose best-effort level is a single seat with no
    queue to speak of — held by the fixture, so every anonymous call
    is shed with a 429 until the seat is released."""
    hub = Hub()
    flow = FlowController(total_concurrency=10, levels={
        "best-effort": LevelConfig(share=0.1, queues=1, queue_depth=1,
                                   queue_wait_s=0.05)})
    server = HubServer(hub, flow=flow).start()
    yield hub, flow, server
    server.stop()


def test_429_roundtrip_typed_with_hint(throttled_hub):
    hub, flow, server = throttled_hub
    flow.admit("best-effort", "anon")
    client = RemoteHub(server.address, timeout=5.0, retry_deadline=0.3,
                       retry_base=0.01, retry_cap=0.05)
    try:
        with pytest.raises(TooManyRequests) as ei:
            client.list_nodes()
        # the server's Retry-After hint survived the wire
        assert ei.value.retry_after > 0
        s = client.resilience_stats()
        assert s["throttled_429s"] >= 1
        # throttles are NOT transport faults: no degraded mode entered
        assert not s["degraded"]
    finally:
        flow.release("best-effort")
        client.close()


def test_429_idempotent_retry_within_budget(throttled_hub):
    """An idempotent read shed by flow control retries with the server
    hint inside the NORMAL retry budget and succeeds once the seat
    frees — the client never gives up early, never spins."""
    hub, flow, server = throttled_hub
    hub.create_node(MakeNode().name("n1").obj())
    flow.admit("best-effort", "anon")
    released = threading.Timer(0.25,
                               lambda: flow.release("best-effort"))
    client = RemoteHub(server.address, timeout=5.0, retry_deadline=3.0,
                       retry_base=0.01, retry_cap=0.05)
    try:
        t0 = time.monotonic()
        released.start()
        nodes = client.list_nodes()     # throttled, retried, lands
        elapsed = time.monotonic() - t0
        assert [n.metadata.name for n in nodes] == ["n1"]
        assert elapsed >= 0.2           # it actually waited the storm out
        s = client.resilience_stats()
        assert s["throttled_429s"] >= 1
        assert s["throttle_retries"] >= 1
        assert s["throttle_retries"] <= s["throttled_429s"]
    finally:
        released.cancel()
        client.close()


def test_429_non_idempotent_never_replayed(throttled_hub):
    """The audit the issue demands: a throttled non-idempotent verb
    surfaces the typed verdict IMMEDIATELY — no blind replay, no
    double-apply — and the request provably never ran server-side."""
    hub, flow, server = throttled_hub
    # an anonymous namespace-less create classifies best-effort
    pod = MakePod().name("shed-me").obj()
    pod.metadata.namespace = ""
    flow.admit("best-effort", "anon")
    client = RemoteHub(server.address, timeout=5.0, retry_deadline=3.0,
                       retry_base=0.01, retry_cap=0.05)
    try:
        t0 = time.monotonic()
        with pytest.raises(TooManyRequests):
            client.create_pod(pod)
        # no retry loop: the verdict came back in one round trip even
        # though the retry deadline allowed for seconds of patience
        assert time.monotonic() - t0 < 1.0
        s = client.resilience_stats()
        assert s["throttled_429s"] >= 1
        assert s["throttle_retries"] == 0
        # the flow controller rejected BEFORE dispatch: nothing ran
        assert hub.get_pod(pod.metadata.uid) is None
    finally:
        flow.release("best-effort")
        client.close()


def test_flow_metrics_ride_the_server_exposition(throttled_hub):
    hub, flow, server = throttled_hub
    flow.admit("best-effort", "anon")
    client = RemoteHub(server.address, timeout=5.0, retry_deadline=0.2,
                       retry_base=0.01, retry_cap=0.05)
    try:
        with pytest.raises(TooManyRequests):
            client.list_nodes()
    finally:
        flow.release("best-effort")
        client.close()
    import urllib.request
    text = urllib.request.urlopen(server.address + "/metrics",
                                  timeout=5.0).read().decode()
    assert "hub_flow_seats" in text
    assert 'hub_flow_rejected_total{level="best-effort"' in text


def test_flow_metrics_round_trip_strict_parser():
    """The hand-rolled hub_flow_* exposition re-parses under
    telemetry.fleet's strict parser (the lint every fabric component's
    metrics_text must pass — the fleet merge ingests this)."""
    from kubernetes_tpu.telemetry.fleet import parse_exposition

    fc = FlowController(total_concurrency=10, levels={
        "best-effort": LevelConfig(share=0.1, queues=1, queue_depth=1,
                                   queue_wait_s=0.01)})
    fc.admit("best-effort", "anon")
    with pytest.raises(TooManyRequests):
        fc.admit("best-effort", "anon")     # deadline -> rejected row
    fc.release("best-effort")
    exp = parse_exposition(fc.metrics_text())
    names = {s.name for s in exp.samples}
    assert {"hub_flow_seats", "hub_flow_in_flight",
            "hub_flow_queue_depth", "hub_flow_admitted_total",
            "hub_flow_rejected_total"} <= names
    rej = [s for s in exp.samples if s.name == "hub_flow_rejected_total"
           and s.labels.get("level") == "best-effort"
           and s.labels.get("reason") == "timeout"]
    assert rej and rej[0].value == 1.0


# ------------------------------------------------------------------
# scheduler brownout: shed-aware self-protection
# ------------------------------------------------------------------


def _brownout_scheduler(threshold: int = 5, batch: int = 64):
    hub = Hub()
    hub.create_node(MakeNode().name("n1").capacity(cpu="64").obj())
    cfg = default_config()
    cfg.batch_size = batch
    cfg.brownout_throttle_threshold = threshold
    cfg.brownout_clear_windows = 2
    cfg.tenants = {"prio": {"weight": 8.0}, "scav": {"weight": 0.1}}
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=4, pods=128))
    throttled = {"n": 0.0}
    hub.resilience_stats = lambda: {"throttled_429s": throttled["n"]}
    return sched, throttled


def _tick_brownout(sched):
    # defeat the ≤1/s evaluation gate so the test drives windows
    sched._last_brownout_eval = 0.0
    sched._evaluate_brownout()


def test_brownout_enters_shrinks_and_recovers():
    sched, throttled = _brownout_scheduler()
    sched.drift_check_interval = 10.0
    try:
        assert sched._effective_batch() == 64
        _tick_brownout(sched)               # baseline window: 0 throttles
        throttled["n"] += 20                # a sustained shed window
        _tick_brownout(sched)
        assert sched.brownout
        assert sched._effective_batch() < 64
        assert sched.drift_check_interval > 10.0
        assert "scav" in sched.jobqueue.parked     # parked best-effort
        assert "prio" not in sched.jobqueue.parked
        st = sched.brownout_state()
        assert st["active"] and st["enters"] == 1
        # still shedding: stays browned out
        throttled["n"] += 20
        _tick_brownout(sched)
        assert sched.brownout
        # two consecutive clean windows: un-brown, restore everything
        _tick_brownout(sched)
        assert sched.brownout               # one clean window is not enough
        _tick_brownout(sched)
        assert not sched.brownout
        assert sched._effective_batch() == 64
        assert sched.drift_check_interval == 10.0
        assert not sched.jobqueue.parked
        assert sched.stats["brownout_exits"] == 1
        # the transitions made it to the exposition
        text = sched.metrics.registry.render_text()
        assert 'scheduler_brownout_transitions_total{phase="enter"}' \
            in text
    finally:
        sched.close()


@pytest.mark.parametrize("batch, shrunk", [
    (4096, 1024),   # the divisor: a quarter of the configured batch
    (16, 8),        # the floor: a quarter would be 4
    (8, 8),         # never above the configured batch
])
def test_brownout_batch_is_a_quarter_but_not_under_the_floor(batch, shrunk):
    sched, throttled = _brownout_scheduler(batch=batch)
    try:
        assert sched._effective_batch() == batch
        _tick_brownout(sched)
        throttled["n"] += 20
        _tick_brownout(sched)
        assert sched.brownout
        assert sched._effective_batch() == shrunk
    finally:
        sched.close()


def test_brownout_disabled_by_zero_threshold():
    sched, throttled = _brownout_scheduler(threshold=0)
    try:
        throttled["n"] += 1000
        _tick_brownout(sched)
        assert not sched.brownout
    finally:
        sched.close()


def test_parked_tenants_release_nothing_and_bank_no_credit():
    """While parked, a best-effort tenant sits out the DRR rotation
    entirely; un-parking must not let it burst past its weight, so
    deficits are zeroed while parked, not accumulated."""
    from kubernetes_tpu.api.objects import LABEL_QUEUE
    from kubernetes_tpu.backend.jobqueue import JobQueue

    class FakePQ:
        def __init__(self):
            self.pods = []

        def add(self, pod):
            self.pods.append(pod)

    jq = JobQueue({"prio": {"weight": 8.0}, "scav": {"weight": 0.1}})
    for i in range(4):
        for tenant in ("prio", "scav"):
            p = MakePod().name(f"{tenant}-{i}").req(cpu="100m").obj()
            p.metadata.labels[LABEL_QUEUE] = tenant
            jq.add(p)
    assert jq.park_below(0.25) == ["scav"]
    pq = FakePQ()
    assert jq.release(pq, budget=64) == 4
    assert all(p.metadata.name.startswith("prio-") for p in pq.pods)
    assert jq.tenant_stats()["scav"]["parked"]
    assert not jq.tenant_stats()["prio"]["parked"]
    # parked while the rotation ran repeatedly: no credit banked
    for _ in range(5):
        jq.release(FakePQ(), budget=64)
    assert jq.unpark_all() == ["scav"]
    pq2 = FakePQ()
    assert jq.release(pq2, budget=64) == 4
    assert sorted(p.metadata.name for p in pq2.pods) == \
        [f"scav-{i}" for i in range(4)]
