"""Leader election (server.go:284-317) + HTTP extender (extender.go)."""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

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
from kubernetes_tpu.extender import ExtenderConfig
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.leaderelection import LeaderElector
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import Scheduler


class Clock:
    def __init__(self):
        self.t = 1000.0

    def now(self):
        return self.t


def test_leader_election_acquire_renew_takeover():
    hub = Hub()
    clock = Clock()
    a = LeaderElector(hub.leases, "a", now=clock.now)
    b = LeaderElector(hub.leases, "b", now=clock.now)
    assert a.try_acquire_or_renew() is True
    assert b.try_acquire_or_renew() is False
    assert a.is_leader() and not b.is_leader()
    # renewals keep the lease
    clock.t += 10
    assert a.try_acquire_or_renew() is True
    clock.t += 10
    assert b.try_acquire_or_renew() is False, "a renewed 10s ago"
    # a goes silent past the lease duration: b takes over
    clock.t += 16
    assert b.try_acquire_or_renew() is True
    assert not a.try_acquire_or_renew()
    assert not a.is_leader()
    lease = hub.leases.get("kube-scheduler")
    assert lease.holder_identity == "b"
    assert lease.lease_transitions == 1


def test_fencing_epoch_monotonic_per_acquisition():
    """The store stamps a fresh epoch on every ACQUISITION (vacant ->
    holder, steal), never on renewals; electors track their newest
    acquisition's epoch (the fencing token for hub writes)."""
    hub = Hub()
    clock = Clock()
    a = LeaderElector(hub.leases, "a", now=clock.now)
    b = LeaderElector(hub.leases, "b", now=clock.now)
    assert a.try_acquire_or_renew()
    assert a.epoch == 1
    clock.t += 5
    assert a.try_acquire_or_renew()            # renewal: same epoch
    assert a.epoch == 1
    assert hub.leases.epoch_of("kube-scheduler") == 1
    clock.t += 16                              # a expires; b steals
    assert b.try_acquire_or_renew()
    assert b.epoch == 2
    assert a.epoch == 1, "deposed holder keeps its old token"
    assert hub.leases.epoch_of("kube-scheduler") == 2
    b.release()
    assert a.try_acquire_or_renew()            # re-acquire after vacancy
    assert a.epoch == 3


def test_hub_rejects_fenced_writes():
    """Hub.bind / patch_pod_condition from a deposed epoch raise Fenced;
    the current epoch's writes land (satellite: fenced binds)."""
    import pytest as _pytest

    from kubernetes_tpu.api.objects import PodCondition
    from kubernetes_tpu.hub import Conflict, Fenced
    from kubernetes_tpu.testing import MakeNode, MakePod

    hub = Hub()
    clock = Clock()
    a = LeaderElector(hub.leases, "a", now=clock.now)
    b = LeaderElector(hub.leases, "b", now=clock.now)
    hub.create_node(MakeNode().name("n").obj())
    pod = MakePod().name("p").req(cpu="100m").obj()
    hub.create_pod(pod)
    assert a.try_acquire_or_renew()
    clock.t += 16
    assert b.try_acquire_or_renew()            # b deposes a
    with _pytest.raises(Fenced):
        hub.bind(pod, "n", a.epoch, a.lease_name)
    assert hub.get_pod(pod.metadata.uid).spec.node_name == "", \
        "a fenced bind must not land"
    with _pytest.raises(Fenced):
        hub.patch_pod_condition(pod, PodCondition(
            type="PodScheduled", status="False", reason="x"),
            None, a.epoch, a.lease_name)
    hub.bind(pod, "n", b.epoch, b.lease_name)  # the new leader binds
    assert hub.get_pod(pod.metadata.uid).spec.node_name == "n"
    with _pytest.raises(Conflict):
        hub.bind(pod, "n", b.epoch, b.lease_name)   # bind-once holds
    # unfenced callers (no elector) are untouched
    pod2 = MakePod().name("p2").req(cpu="100m").obj()
    hub.create_pod(pod2)
    hub.bind(pod2, "n")
    assert hub.get_pod(pod2.metadata.uid).spec.node_name == "n"


def test_leader_election_release():
    hub = Hub()
    clock = Clock()
    a = LeaderElector(hub.leases, "a", now=clock.now)
    b = LeaderElector(hub.leases, "b", now=clock.now)
    a.try_acquire_or_renew()
    a.release()
    assert b.try_acquire_or_renew() is True, "vacated lease acquired"


def test_only_leader_schedules():
    hub = Hub()
    hub.create_node(Node(
        metadata=ObjectMeta(name="n", labels={LABEL_HOSTNAME: "n"}),
        status=NodeStatus(allocatable={"cpu": "8", "memory": "16Gi",
                                       "pods": "110"})))
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    # another instance holds the lease
    other = LeaderElector(hub.leases, "other")
    assert other.try_acquire_or_renew()
    follower = LeaderElector(hub.leases, "me", retry_period=0.01)
    sched.start(elector=follower)
    try:
        p = Pod(metadata=ObjectMeta(name="p"),
                spec=PodSpec(containers=[Container(
                    name="c", resources=ResourceRequirements(
                        requests={"cpu": "1"}))]))
        hub.create_pod(p)
        import time

        time.sleep(0.5)
        assert hub.get_pod(p.metadata.uid).spec.node_name == "", \
            "a non-leader must not bind"
        # the holder releases: our follower acquires and schedules
        other.release()
        deadline = time.time() + 20
        while time.time() < deadline:
            if hub.get_pod(p.metadata.uid).spec.node_name:
                break
            time.sleep(0.05)
        assert hub.get_pod(p.metadata.uid).spec.node_name == "n"
    finally:
        sched.stop()
        sched.close()


# ---------------------------- extender ----------------------------


class _StubExtender(BaseHTTPRequestHandler):
    reject = set()
    scores = {}
    calls = []

    def log_message(self, *a):
        pass

    preempt_veto = set()    # candidate nodes dropped by /preempt
    bound = []              # (podName, node) seen by /bind

    def do_POST(self):  # noqa: N802
        body = json.loads(self.rfile.read(
            int(self.headers["Content-Length"])).decode())
        type(self).calls.append((self.path, body))
        if self.path.endswith("/filter"):
            names = body.get("nodenames")
            if names is None:   # non-nodeCacheCapable: full node objects
                names = [n["metadata"]["name"] for n in body["nodes"]]
            passed = [n for n in names if n not in type(self).reject]
            out = {"nodenames": passed,
                   "failedNodes": {n: "vetoed" for n in type(self).reject
                                   if n in names}}
        elif self.path.endswith("/bind"):
            type(self).bound.append((body["podName"], body["node"]))
            out = {}
        elif self.path.endswith("/preempt"):
            out = {"nodeNameToVictims": {
                node: entry
                for node, entry in body["nodeNameToVictims"].items()
                if node not in type(self).preempt_veto}}
        else:
            names = body.get("nodenames")
            if names is None:
                names = [n["metadata"]["name"] for n in body["nodes"]]
            out = [{"host": n, "score": type(self).scores.get(n, 0)}
                   for n in names]
        data = json.dumps(out).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)


def _with_stub(fn):
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _StubExtender)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        fn(f"http://127.0.0.1:{srv.server_address[1]}")
    finally:
        srv.shutdown()
        srv.server_close()


def _cluster(url, managed=None):
    hub = Hub()
    for n in ("n0", "n1", "n2"):
        hub.create_node(Node(
            metadata=ObjectMeta(name=n, labels={LABEL_HOSTNAME: n}),
            status=NodeStatus(allocatable={"cpu": "8", "memory": "16Gi",
                                           "pods": "110"})))
    cfg = default_config()
    cfg.batch_size = 16
    cfg.extenders = [ExtenderConfig(
        url_prefix=url, filter_verb="filter", prioritize_verb="prioritize",
        weight=100.0, managed_resources=managed or [])]
    return hub, Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))


def test_extender_filter_vetoes_nodes():
    _StubExtender.reject = {"n0", "n2"}
    _StubExtender.scores = {}
    _StubExtender.calls = []

    def run(url):
        hub, sched = _cluster(url)
        p = Pod(metadata=ObjectMeta(name="p"),
                spec=PodSpec(containers=[Container(
                    name="c", resources=ResourceRequirements(
                        requests={"cpu": "1"}))]))
        hub.create_pod(p)
        sched.run_until_idle()
        assert hub.get_pod(p.metadata.uid).spec.node_name == "n1"
        assert any(path.endswith("/filter")
                   for path, _ in _StubExtender.calls)
        sched.close()

    _with_stub(run)


def test_extender_prioritize_steers_choice():
    _StubExtender.reject = set()
    _StubExtender.scores = {"n2": 10}
    _StubExtender.calls = []

    def run(url):
        hub, sched = _cluster(url)
        p = Pod(metadata=ObjectMeta(name="p"),
                spec=PodSpec(containers=[Container(
                    name="c", resources=ResourceRequirements(
                        requests={"cpu": "1"}))]))
        hub.create_pod(p)
        sched.run_until_idle()
        assert hub.get_pod(p.metadata.uid).spec.node_name == "n2", \
            "weighted extender score dominates"
        sched.close()

    _with_stub(run)


def test_extender_managed_resources_gate():
    _StubExtender.reject = {"n0", "n1", "n2"}
    _StubExtender.calls = []

    def run(url):
        hub, sched = _cluster(url, managed=["example.com/fpga"])
        plain = Pod(metadata=ObjectMeta(name="plain"),
                    spec=PodSpec(containers=[Container(
                        name="c", resources=ResourceRequirements(
                            requests={"cpu": "1"}))]))
        hub.create_pod(plain)
        sched.run_until_idle()
        assert hub.get_pod(plain.metadata.uid).spec.node_name, \
            "uninterested extender never consulted"
        assert not _StubExtender.calls
        sched.close()

    _with_stub(run)


def test_extender_unreachable_nonignorable_fails_pod():
    hub = Hub()
    hub.create_node(Node(
        metadata=ObjectMeta(name="n", labels={LABEL_HOSTNAME: "n"}),
        status=NodeStatus(allocatable={"cpu": "8", "memory": "16Gi",
                                       "pods": "110"})))
    cfg = default_config()
    cfg.batch_size = 16
    cfg.extenders = [ExtenderConfig(
        url_prefix="http://127.0.0.1:1", filter_verb="filter",
        timeout_seconds=0.2)]
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    p = Pod(metadata=ObjectMeta(name="p"),
            spec=PodSpec(containers=[Container(
                name="c", resources=ResourceRequirements(
                    requests={"cpu": "1"}))]))
    hub.create_pod(p)
    sched.run_until_idle()
    assert hub.get_pod(p.metadata.uid).spec.node_name == ""
    sched.close()


def test_extender_unreachable_ignorable_skipped():
    hub = Hub()
    hub.create_node(Node(
        metadata=ObjectMeta(name="n", labels={LABEL_HOSTNAME: "n"}),
        status=NodeStatus(allocatable={"cpu": "8", "memory": "16Gi",
                                       "pods": "110"})))
    cfg = default_config()
    cfg.batch_size = 16
    cfg.extenders = [ExtenderConfig(
        url_prefix="http://127.0.0.1:1", filter_verb="filter",
        ignorable=True, timeout_seconds=0.2)]
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    p = Pod(metadata=ObjectMeta(name="p"),
            spec=PodSpec(containers=[Container(
                name="c", resources=ResourceRequirements(
                    requests={"cpu": "1"}))]))
    hub.create_pod(p)
    sched.run_until_idle()
    assert hub.get_pod(p.metadata.uid).spec.node_name == "n"
    sched.close()


def test_config_file_loading(tmp_path):
    """cmd-level config loading: profiles, plugin args, extenders, knobs."""
    from kubernetes_tpu.config.load import load_config

    doc = {
        "batch_size": 128,
        "async_binding": False,
        "profiles": [
            {"scheduler_name": "default-scheduler",
             "plugin_config": [
                 {"name": "NodeResourcesFit",
                  "args": {"scoring_strategy": {"type": "MostAllocated"}}}]},
            {"scheduler_name": "second",
             "plugins": {"score": {"disabled": [{"name": "ImageLocality"}]}}},
        ],
        "extenders": [
            {"url_prefix": "http://127.0.0.1:9999", "filter_verb": "filter",
             "weight": 3, "ignorable": True}],
    }
    path = tmp_path / "config.json"
    path.write_text(json.dumps(doc))
    cfg = load_config(str(path))
    assert cfg.batch_size == 128
    assert cfg.async_binding is False
    assert [p.scheduler_name for p in cfg.profiles] == [
        "default-scheduler", "second"]
    assert cfg.profiles[0].plugin_config["NodeResourcesFit"][
        "scoring_strategy"]["type"] == "MostAllocated"
    assert cfg.extenders[0].weight == 3
    assert cfg.extenders[0].ignorable is True
    # the loaded config actually constructs a working scheduler
    hub = Hub()
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
    assert "second" in sched.frameworks
    sched.close()


def test_cli_validate_only(tmp_path):
    from kubernetes_tpu.__main__ import main

    path = tmp_path / "cfg.json"
    path.write_text(json.dumps({"batch_size": 64}))
    assert main(["--config", str(path), "--validate-only"]) == 0
    path.write_text(json.dumps({"batch_size": 0}))
    assert main(["--config", str(path), "--validate-only"]) == 1


def test_config_document_with_a_retired_key_still_loads():
    """A document written for an older release may carry `parallelism`
    (loaded and read by nothing, then removed): it is ignored like every
    unknown key, and the rest of the document takes effect."""
    from kubernetes_tpu.config.load import config_from_dict
    from kubernetes_tpu.config.validation import validate_config

    cfg = config_from_dict({"parallelism": 0, "batch_size": 32})
    assert cfg.batch_size == 32
    assert not hasattr(cfg, "parallelism")
    assert validate_config(cfg) == []


def test_feature_gates():
    """Gates toggle hint consultation and async preemption; unknown gates
    fail validation."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.config.validation import validate_config
    from kubernetes_tpu.plugins.registry import in_tree_registry

    cfg = default_config()
    cfg.feature_gates["NoSuchGate"] = True
    assert any("NoSuchGate" in e
               for e in validate_config(cfg, in_tree_registry()))

    # hints OFF: an unhelpful node still requeues the parked pod
    cfg2 = default_config()
    cfg2.batch_size = 16
    cfg2.feature_gates["SchedulerQueueingHints"] = False
    hub = Hub()
    sched = Scheduler(hub, cfg2, caps=Capacities(nodes=16, pods=64))
    hub.create_node(Node(
        metadata=ObjectMeta(name="small", labels={LABEL_HOSTNAME: "small"}),
        status=NodeStatus(allocatable={"cpu": "1", "memory": "8Gi",
                                       "pods": "110"})))
    big = Pod(metadata=ObjectMeta(name="big"),
              spec=PodSpec(containers=[Container(
                  name="c", resources=ResourceRequirements(
                      requests={"cpu": "8"}))]))
    hub.create_pod(big)
    sched.run_until_idle()
    assert sched.queue.pending_counts()["unschedulable"] == 1
    hub.create_node(Node(
        metadata=ObjectMeta(name="small2",
                            labels={LABEL_HOSTNAME: "small2"}),
        status=NodeStatus(allocatable={"cpu": "1", "memory": "8Gi",
                                       "pods": "110"})))
    assert sched.queue.pending_counts()["unschedulable"] == 0, \
        "hints disabled: any matching event requeues"
    sched.close()


# ------------------- extender bind / preempt / payload verbs -------------------


def test_extender_bind_verb_delegates_binding():
    """extender.go:361 Bind: the first interested binder extender performs
    the binding instead of the default binder; the hub still reflects it."""
    _StubExtender.reject = set()
    _StubExtender.scores = {}
    _StubExtender.calls = []
    _StubExtender.bound = []

    def run(url):
        hub = Hub()
        hub.create_node(Node(
            metadata=ObjectMeta(name="n0", labels={LABEL_HOSTNAME: "n0"}),
            status=NodeStatus(allocatable={"cpu": "8", "memory": "16Gi",
                                           "pods": "110"})))
        cfg = default_config()
        cfg.batch_size = 16
        cfg.extenders = [ExtenderConfig(url_prefix=url, bind_verb="bind")]
        sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
        p = Pod(metadata=ObjectMeta(name="delegated"),
                spec=PodSpec(containers=[Container(
                    name="c", resources=ResourceRequirements(
                        requests={"cpu": "1"}))]))
        hub.create_pod(p)
        sched.run_until_idle()
        assert hub.get_pod(p.metadata.uid).spec.node_name == "n0"
        assert _StubExtender.bound == [("delegated", "n0")]
        sched.close()

    _with_stub(run)


def test_extender_process_preemption_vetoes_candidate():
    """preemption.go:335 callExtenders: a ProcessPreemption veto removes
    the candidate node; the preemptor lands on a surviving candidate."""
    _StubExtender.reject = set()
    _StubExtender.scores = {}
    _StubExtender.calls = []
    _StubExtender.preempt_veto = {"n0"}

    def run(url):
        hub = Hub()
        for n in ("n0", "n1"):
            hub.create_node(Node(
                metadata=ObjectMeta(name=n, labels={LABEL_HOSTNAME: n}),
                status=NodeStatus(allocatable={"cpu": "4",
                                               "memory": "16Gi",
                                               "pods": "110"})))
        cfg = default_config()
        cfg.batch_size = 16
        cfg.extenders = [ExtenderConfig(url_prefix=url,
                                        preempt_verb="preempt")]
        clock = [1000.0]
        sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64),
                          now=lambda: clock[0])
        # saturate both nodes with evictable low-priority pods
        for n in ("n0", "n1"):
            for j in range(2):
                hub.create_pod(Pod(
                    metadata=ObjectMeta(name=f"low-{n}-{j}"),
                    spec=PodSpec(containers=[Container(
                        name="c", resources=ResourceRequirements(
                            requests={"cpu": "1800m"}))], priority=0)))
        sched.run_until_idle()
        high = Pod(metadata=ObjectMeta(name="high"),
                   spec=PodSpec(containers=[Container(
                       name="c", resources=ResourceRequirements(
                           requests={"cpu": "1800m"}))], priority=100))
        hub.create_pod(high)
        for _ in range(6):
            sched.run_until_idle()
            clock[0] += 3.0
            sched.queue.flush_backoff_completed()
        sched.run_until_idle()
        assert hub.get_pod(high.metadata.uid).spec.node_name == "n1", \
            "vetoed candidate n0 must not be chosen"
        assert any(path.endswith("/preempt")
                   for path, _ in _StubExtender.calls)
        # the payload carried the FULL pod (priority visible to extender)
        preempt_body = next(b for path, b in _StubExtender.calls
                            if path.endswith("/preempt"))
        assert preempt_body["pod"]["spec"]["priority"] == 100
        victims = next(iter(
            preempt_body["nodeNameToVictims"].values()))["pods"]
        assert victims[0]["spec"]["containers"][0]["resources"][
            "requests"]["cpu"] == "1800m"
        sched.close()

    _with_stub(run)


def test_extender_non_node_cache_capable_gets_full_nodes():
    """extender.go:258: a non-nodeCacheCapable extender receives full
    node objects in the filter payload."""
    _StubExtender.reject = {"n0"}
    _StubExtender.scores = {}
    _StubExtender.calls = []

    def run(url):
        hub = Hub()
        for n in ("n0", "n1"):
            hub.create_node(Node(
                metadata=ObjectMeta(name=n, labels={LABEL_HOSTNAME: n}),
                status=NodeStatus(allocatable={"cpu": "8",
                                               "memory": "16Gi",
                                               "pods": "110"})))
        cfg = default_config()
        cfg.batch_size = 16
        cfg.extenders = [ExtenderConfig(url_prefix=url,
                                        filter_verb="filter",
                                        node_cache_capable=False)]
        sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))
        p = Pod(metadata=ObjectMeta(name="p"),
                spec=PodSpec(containers=[Container(
                    name="c", resources=ResourceRequirements(
                        requests={"cpu": "1"}))]))
        hub.create_pod(p)
        sched.run_until_idle()
        assert hub.get_pod(p.metadata.uid).spec.node_name == "n1"
        body = next(b for path, b in _StubExtender.calls
                    if path.endswith("/filter"))
        assert "nodes" in body and "nodenames" not in body
        names = {n["metadata"]["name"] for n in body["nodes"]}
        assert names == {"n0", "n1"}
        assert body["nodes"][0]["status"]["allocatable"]["cpu"] == "8"
        sched.close()

    _with_stub(run)


def test_extender_preempt_meta_victims_for_cache_capable():
    """extender.go:150: a nodeCacheCapable extender exchanges
    NodeNameToMetaVictims — pod uid references, not full objects."""
    _StubExtender.reject = set()
    _StubExtender.scores = {}
    _StubExtender.calls = []
    _StubExtender.preempt_veto = set()

    class _MetaStub(_StubExtender):
        def do_POST(self):  # noqa: N802
            body = json.loads(self.rfile.read(
                int(self.headers["Content-Length"])).decode())
            _StubExtender.calls.append((self.path, body))
            assert "nodeNameToMetaVictims" in body
            out = {"nodeNameToMetaVictims": body["nodeNameToMetaVictims"]}
            data = json.dumps(out).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), _MetaStub)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{srv.server_address[1]}"
        hub = Hub()
        hub.create_node(Node(
            metadata=ObjectMeta(name="n0", labels={LABEL_HOSTNAME: "n0"}),
            status=NodeStatus(allocatable={"cpu": "4", "memory": "16Gi",
                                           "pods": "110"})))
        cfg = default_config()
        cfg.batch_size = 16
        cfg.extenders = [ExtenderConfig(url_prefix=url,
                                        preempt_verb="preempt",
                                        node_cache_capable=True)]
        clock = [1000.0]
        sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64),
                          now=lambda: clock[0])
        for j in range(2):
            hub.create_pod(Pod(
                metadata=ObjectMeta(name=f"low-{j}"),
                spec=PodSpec(containers=[Container(
                    name="c", resources=ResourceRequirements(
                        requests={"cpu": "1800m"}))], priority=0)))
        sched.run_until_idle()
        high = Pod(metadata=ObjectMeta(name="high"),
                   spec=PodSpec(containers=[Container(
                       name="c", resources=ResourceRequirements(
                           requests={"cpu": "1800m"}))], priority=100))
        hub.create_pod(high)
        for _ in range(6):
            sched.run_until_idle()
            clock[0] += 3.0
            sched.queue.flush_backoff_completed()
        sched.run_until_idle()
        assert hub.get_pod(high.metadata.uid).spec.node_name == "n0"
        body = next(b for path, b in _StubExtender.calls
                    if path.endswith("/preempt"))
        victims = next(iter(
            body["nodeNameToMetaVictims"].values()))["pods"]
        assert victims and set(victims[0]) == {"uid"}
        sched.close()
    finally:
        srv.shutdown()
        srv.server_close()


# suite-tier discipline (tests/test_markers.py): area marker
import pytest  # noqa: E402
pytestmark = pytest.mark.core
