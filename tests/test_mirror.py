"""Mirror packing semantics: the f32 representability boundary, and the
packed-row cache of _pack_batch_np (a hit is byte for byte the slow path's
row; what is not a function of a pod's content bypasses it)."""

import copy
import glob
import json
import os

import numpy as np
import pytest

from kubernetes_tpu.api.objects import (
    Affinity,
    Container,
    ContainerPort,
    LABEL_HOSTNAME,
    LABEL_ZONE,
    LabelSelector,
    LabelSelectorRequirement,
    Node,
    NodeAffinity,
    NodeSelector,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    PodSpec,
    ResourceRequirements,
    Toleration,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)
from kubernetes_tpu.backend import mirror as mirror_mod
from kubernetes_tpu.backend.cache import Cache
from kubernetes_tpu.backend.mirror import Mirror
from kubernetes_tpu.backend.snapshot import Snapshot
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.utils.interner import Interner

pytestmark = pytest.mark.core   # tests/test_markers.py: area marker


def test_non_mi_granular_quantities_round_conservatively():
    """Exact-integer fit semantics at the f32 boundary (fitsRequest,
    fit.go:509-592): odd-byte memory requests beyond float32's 2^24-MiB
    exact range must never FALSELY fit. Demand rounds UP, capacity
    rounds DOWN, so free = alloc_down - req_up understates headroom."""
    import numpy as np

    from kubernetes_tpu.api.objects import (
        Container,
        Node,
        NodeStatus,
        ObjectMeta,
        Pod,
        PodSpec,
        ResourceRequirements,
    )
    from kubernetes_tpu.backend.cache import Cache
    from kubernetes_tpu.backend.mirror import MI, Mirror, _f32_ceil, \
        _f32_floor
    from kubernetes_tpu.backend.snapshot import Snapshot
    from kubernetes_tpu.ops.features import COL_MEM, Capacities

    tib16 = 16 * 1024 ** 4              # 16 TiB = 2^24 MiB: f32-exact edge
    # one byte above: 2^24 MiB + 2^-20 MiB is NOT f32-representable
    odd = tib16 + 1

    assert float(_f32_ceil(odd / MI)) > odd / MI
    assert float(_f32_floor(odd / MI)) < odd / MI
    # Mi-granular values stay EXACT (no rounding perturbation)
    assert float(_f32_ceil(tib16 / MI)) == tib16 / MI
    assert float(_f32_floor(tib16 / MI)) == tib16 / MI

    cache = Cache()
    node = Node(metadata=ObjectMeta(name="n"),
                status=NodeStatus(allocatable={
                    "cpu": "64", "memory": str(odd), "pods": "110"}))
    cache.add_node(node)
    snap = Snapshot()
    cache.update_snapshot(snap)
    mirror = Mirror(caps=Capacities(nodes=8, pods=16))
    mirror.sync(snap)
    row = mirror.row_of("n")
    free_mem = mirror.free_matrix()[row, COL_MEM]
    # capacity rounded DOWN: the node never advertises the odd byte
    assert float(free_mem) <= odd / MI

    # a pod requesting the full odd size: request rounds UP, so the
    # device compare req <= free must REJECT (capacity was floored)
    pod = Pod(metadata=ObjectMeta(name="p"),
              spec=PodSpec(containers=[Container(
                  name="c", resources=ResourceRequirements(
                      requests={"memory": str(odd)}))]))
    from kubernetes_tpu.api.resources import pod_request

    req = mirror._res_row(pod_request(pod))
    assert float(req[COL_MEM]) >= odd / MI
    assert not bool(np.all(req[COL_MEM] <= free_mem)), \
        "odd-byte request must not falsely fit the floored capacity"

    # the Mi-granular pod of the same nominal size still fits exactly
    pod2 = Pod(metadata=ObjectMeta(name="p2"),
               spec=PodSpec(containers=[Container(
                   name="c", resources=ResourceRequirements(
                       requests={"memory": str(tib16)}))]))
    req2 = mirror._res_row(pod_request(pod2))
    assert float(req2[COL_MEM]) == tib16 / MI
    assert bool(req2[COL_MEM] <= free_mem)


# ---------------------------------------------------------------------------
# the packed-row cache (ISSUE 29)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 8


def _sel(labels=None, exprs=()):
    return LabelSelector(
        match_labels=dict(labels or {}),
        match_expressions=[LabelSelectorRequirement(*e) for e in exprs])


def _tsc(skew=1, key=LABEL_ZONE, when="DoNotSchedule", sel=None, **kw):
    return TopologySpreadConstraint(
        max_skew=skew, topology_key=key, when_unsatisfiable=when,
        label_selector=_sel({"app": "web"}) if sel is None else sel, **kw)


def _term(key=LABEL_ZONE, sel=None, **kw):
    return PodAffinityTerm(
        topology_key=key,
        label_selector=_sel({"app": "web"}) if sel is None else sel, **kw)


def _pod(name, labels=None, namespace="default", requests=None, **spec):
    """A pod of one container; `spec` goes to PodSpec as it stands."""
    spec.setdefault("containers", [Container(
        name="c", image="registry/pause:3", resources=ResourceRequirements(
            requests=dict(requests or {"cpu": "100m", "memory": "500Mi"})))])
    return Pod(metadata=ObjectMeta(name=name, uid=f"uid-{name}",
                                   namespace=namespace,
                                   labels=dict(labels or {})),
               spec=PodSpec(**spec))


def _template_shapes():
    """Every pod template the benchmark has, through its own builder."""
    from benchmark.objects import PodMaker

    shapes = {}
    for path in sorted(glob.glob(
            os.path.join(REPO, "benchmark", "templates", "*.json"))):
        with open(path) as f:
            tmpl = json.load(f)
        if tmpl.get("kind") == "pod":
            shapes[os.path.basename(path)[:-5]] = PodMaker(tmpl).make
    return shapes


WEB = {"app": "web", "tier": "front"}
# name -> maker(pod name): pods of one shape, apart from name and uid
HAND_SHAPES = {
    "tolerations": lambda n: _pod(n, tolerations=[
        Toleration(key="dedicated", operator="Equal", value="infra",
                   effect="NoSchedule"),
        Toleration(operator="Exists")]),
    "two-spread-constraints": lambda n: _pod(
        n, WEB, topology_spread_constraints=[
            _tsc(2, LABEL_ZONE, min_domains=2),
            _tsc(1, LABEL_HOSTNAME, "ScheduleAnyway",
                 node_taints_policy="Honor",
                 node_affinity_policy="Ignore")]),
    "spread-match-label-keys": lambda n: _pod(
        n, WEB, topology_spread_constraints=[
            _tsc(match_label_keys=["tier", "absent"])]),
    "spread-nil-selector-and-expressions": lambda n: _pod(
        n, WEB, topology_spread_constraints=[
            TopologySpreadConstraint(1, LABEL_ZONE, "DoNotSchedule"),
            _tsc(sel=_sel(exprs=[("app", "In", ["web", "api"]),
                                 ("canary", "DoesNotExist", [])]))]),
    "required-affinity": lambda n: _pod(n, WEB, affinity=Affinity(
        pod_affinity=PodAffinity(required=[
            _term(namespaces=["default", "other"])]))),
    "required-anti-affinity": lambda n: _pod(n, WEB, affinity=Affinity(
        pod_anti_affinity=PodAntiAffinity(required=[
            _term(LABEL_HOSTNAME, match_label_keys=["tier"],
                  mismatch_label_keys=["app"])]))),
    "preferred-affinity-and-anti": lambda n: _pod(n, WEB, affinity=Affinity(
        pod_affinity=PodAffinity(preferred=[
            WeightedPodAffinityTerm(10, _term()),
            WeightedPodAffinityTerm(3, _term(LABEL_HOSTNAME))]),
        pod_anti_affinity=PodAntiAffinity(preferred=[
            WeightedPodAffinityTerm(7, _term(
                sel=_sel(exprs=[("app", "NotIn", ["db"])])))]))),
    "required-and-preferred-with-spread": lambda n: _pod(
        n, WEB, namespace="team-a", priority=5,
        topology_spread_constraints=[_tsc()],
        affinity=Affinity(
            pod_affinity=PodAffinity(
                required=[_term()],
                preferred=[WeightedPodAffinityTerm(4, _term())]),
            pod_anti_affinity=PodAntiAffinity(
                required=[_term(LABEL_HOSTNAME)]))),
    "init-containers-overhead-extended": lambda n: _pod(
        n, requests={"cpu": "250m", "memory": "1Gi", "example.com/gpu": "2"},
        init_containers=[
            Container(name="i", resources=ResourceRequirements(
                requests={"cpu": "1"})),
            Container(name="s", restart_policy="Always",
                      resources=ResourceRequirements(
                          requests={"memory": "64Mi"}))],
        overhead={"cpu": "10m"}),
    "host-ports": lambda n: _pod(n, containers=[Container(
        name="c", ports=[ContainerPort(host_port=8080),
                         ContainerPort(host_port=53, protocol="UDP",
                                       host_ip="10.0.0.1")])]),
}
BYPASS_SHAPES = {
    "nominated-node": lambda n: _nominated(_pod(n), "node-0"),
    "node-selector": lambda n: _pod(n, node_selector={"disk": "ssd"}),
    "node-affinity": lambda n: _pod(n, affinity=Affinity(
        node_affinity=NodeAffinity(required=NodeSelector([NodeSelectorTerm(
            match_expressions=[NodeSelectorRequirement(
                "disk", "In", ["ssd"])])])))),
    "namespace-selector-term": lambda n: _pod(n, WEB, affinity=Affinity(
        pod_affinity=PodAffinity(required=[_term(
            namespace_selector=_sel({"team": "a"}))]))),
    "empty-namespace-selector-term": lambda n: _pod(n, WEB, affinity=Affinity(
        pod_anti_affinity=PodAntiAffinity(preferred=[
            WeightedPodAffinityTerm(1, _term(namespace_selector=_sel()))]))),
    "spec-node-name": lambda n: _pod(n, node_name="node-0"),
}
# the benchmark's templates whose row is not a function of content alone
TEMPLATE_BYPASS = {"pod-with-node-affinity"}


def _nominated(pod, node):
    pod.status.nominated_node_name = node
    return pod


def _all_fields(m):
    return tuple(sorted(m.pod_codec.schema))


def _mirror_pair(**caps):
    """(cached, never_cached): two mirrors over one interner. The second
    gives every pod the slow path, as a mirror with no cache would."""
    interner = Interner()
    c = Capacities(nodes=8, pods=16, **caps)
    cached = Mirror(interner=interner, caps=c)
    plain = Mirror(interner=interner, caps=c)
    plain._pod_row_key = lambda pod: None
    return cached, plain


def _same_rows(a, b):
    return a[0].tobytes() == b[0].tobytes() and a[1].tobytes() == b[1].tobytes()


def _pack_both(cached, plain, pods, fields=None):
    fields = fields or _all_fields(cached)
    got = cached._pack_batch_np(pods, BATCH, fields)
    want = plain._pack_batch_np(pods, BATCH, fields)
    assert _same_rows(got, want)
    return got


SHAPES = {**_template_shapes(), **HAND_SHAPES, **BYPASS_SHAPES}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_cached_rows_equal_the_slow_path_rows(shape):
    """Pods that differ in name and uid alone: the rows a warm cache
    returns are byte for byte those of a mirror that never cached, over
    the whole schema and over the launch's own field subset."""
    make = SHAPES[shape]
    cached, plain = _mirror_pair()
    pods = [make(f"{shape}-{i}") for i in range(5)]
    _pack_both(cached, plain, pods[:1])
    _pack_both(cached, plain, pods)
    launch = cached.pod_fields(cached.launch_features(pods),
                               Mirror.batch_has_topology(pods))
    _pack_both(cached, plain, pods, launch)
    st = cached.row_cache_stats()
    if shape in BYPASS_SHAPES or shape in TEMPLATE_BYPASS:
        assert (st["hits"], st["misses"], st["bypass"]) == (0, 0, 11)
    else:
        # one miss a field set, every other pod a hit
        assert (st["hits"], st["misses"], st["bypass"]) == (9, 2, 0)
    assert plain.row_cache_stats()["bypass"] == 11


def _grow_pod_label_column(cached, plain):
    _pack_both(cached, plain, [_pod("grow-l", {"a-new-label-key": "v"})])


def _grow_topology_key(cached, plain):
    _pack_both(cached, plain, [_pod("grow-t", WEB, topology_spread_constraints=[
        _tsc(key="example.com/rack")])])


def _grow_namespace(cached, plain):
    for m in (cached, plain):
        m._namespaces["fresh-ns"] = {"team": "a"}
    _pack_both(cached, plain, [_pod("grow-n", namespace="fresh-ns")])


def _grow_node_label_key(cached, plain):
    cache = Cache()
    cache.add_node(Node(
        metadata=ObjectMeta(name="node-0", labels={
            LABEL_HOSTNAME: "node-0", LABEL_ZONE: "z9", "disk": "ssd"}),
        status=NodeStatus(allocatable={"cpu": "8", "memory": "8Gi",
                                       "pods": "110"})))
    snap = Snapshot()
    cache.update_snapshot(snap)
    for m in (cached, plain):
        m.sync(snap)


GROWTHS = {"pod-label-column": _grow_pod_label_column,
           "topology-key": _grow_topology_key,
           "namespace": _grow_namespace,
           "node-label-key": _grow_node_label_key}


@pytest.mark.parametrize("growth", sorted(GROWTHS))
def test_hit_after_the_registries_grew(growth):
    """The registries a row is derived from only append, so a row cached
    before one of them grew is still what the slow path packs after."""
    cached, plain = _mirror_pair()
    makers = [HAND_SHAPES[k] for k in sorted(HAND_SHAPES)][:BATCH]
    before = [mk(f"b-{i}") for i, mk in enumerate(makers)]
    _pack_both(cached, plain, before)
    misses = cached.row_cache_misses
    assert misses == len(before)
    GROWTHS[growth](cached, plain)
    grown = cached.row_cache_stats()
    after = [mk(f"a-{i}") for i, mk in enumerate(makers)]
    _pack_both(cached, plain, after)
    st = cached.row_cache_stats()
    assert st["misses"] == grown["misses"]
    assert st["hits"] == grown["hits"] + len(after)


@pytest.mark.parametrize("shape", sorted(BYPASS_SHAPES))
def test_rows_of_mutable_state_bypass_the_cache(shape):
    """A pod whose row reads state that changes under it gets no key and
    is counted as a bypass, every time it is packed."""
    make = BYPASS_SHAPES[shape]
    assert Mirror._pod_row_key(make("p")) is None
    cached, plain = _mirror_pair()
    pods = [make(f"{shape}-{i}") for i in range(3)]
    first = _pack_both(cached, plain, pods)
    # the state moves: a node brings the label key, a namespace appears,
    # the pods become nominated pods with a reservation on that node
    _grow_node_label_key(cached, plain)
    _grow_namespace(cached, plain)
    for m in (cached, plain):
        m.set_nominated({"node-0": pods})
    second = _pack_both(cached, plain, pods)
    st = cached.row_cache_stats()
    # _grow_namespace packs one plain pod of its own: the one miss
    assert (st["hits"], st["misses"], st["bypass"]) == (0, 1, 6)
    if shape in ("nominated-node", "node-selector", "node-affinity",
                 "namespace-selector-term"):
        assert not _same_rows(first, second), \
            "the moved state should show in the row: else why bypass"


def _base():
    return _pod(
        "base", WEB, priority=0,
        tolerations=[Toleration("k", "Equal", "v", "NoSchedule")],
        topology_spread_constraints=[_tsc(min_domains=1,
                                          match_label_keys=["tier"])],
        init_containers=[Container(name="i", resources=ResourceRequirements(
            requests={"cpu": "50m"}))],
        overhead={"cpu": "10m"},
        affinity=Affinity(
            pod_affinity=PodAffinity(
                required=[_term(namespaces=["default"],
                                match_label_keys=["tier"],
                                mismatch_label_keys=["app"])],
                preferred=[WeightedPodAffinityTerm(5, _term())]),
            pod_anti_affinity=PodAntiAffinity(
                required=[_term(LABEL_HOSTNAME)])))


def _tsc0(p):
    return p.spec.topology_spread_constraints[0]


def _req0(p):
    return p.spec.affinity.pod_affinity.required[0]


def _set(obj_of, attr, value):
    def edit(p):
        setattr(obj_of(p), attr, value)
    return edit


def _move_required_to_preferred(p):
    pa = p.spec.affinity.pod_affinity
    pa.preferred.insert(0, WeightedPodAffinityTerm(0, pa.required.pop()))


def _swap_affinity_and_anti(p):
    a = p.spec.affinity
    a.pod_affinity, a.pod_anti_affinity = (
        PodAffinity(required=a.pod_anti_affinity.required),
        PodAntiAffinity(required=a.pod_affinity.required,
                        preferred=a.pod_affinity.preferred))


# one keyed field a case: an edit of the base pod that changes its row
KEYED_FIELDS = {
    "namespace": _set(lambda p: p.metadata, "namespace", "other"),
    "priority": _set(lambda p: p.spec, "priority", 7),
    "label-value": lambda p: p.metadata.labels.update(tier="back"),
    "label-key": lambda p: p.metadata.labels.update(extra="1"),
    "container-image": _set(lambda p: p.spec.containers[0], "image", "other"),
    "container-request": lambda p:
        p.spec.containers[0].resources.requests.update(cpu="200m"),
    "container-count": lambda p: p.spec.containers.append(Container("d")),
    "host-port": lambda p: p.spec.containers[0].ports.append(
        ContainerPort(host_port=80)),
    "init-container-request": lambda p:
        p.spec.init_containers[0].resources.requests.update(cpu="2"),
    "init-container-restart-policy": _set(
        lambda p: p.spec.init_containers[0], "restart_policy", "Always"),
    "overhead": lambda p: p.spec.overhead.update(cpu="20m"),
    "spread-max-skew": _set(_tsc0, "max_skew", 3),
    "spread-topology-key": _set(_tsc0, "topology_key", LABEL_HOSTNAME),
    "spread-when-unsatisfiable": _set(_tsc0, "when_unsatisfiable",
                                      "ScheduleAnyway"),
    "spread-min-domains": _set(_tsc0, "min_domains", 2),
    "spread-selector-labels": lambda p:
        _tsc0(p).label_selector.match_labels.update(app="api"),
    "spread-selector-expressions": lambda p:
        _tsc0(p).label_selector.match_expressions.append(
            LabelSelectorRequirement("canary", "Exists")),
    "spread-nil-selector": _set(_tsc0, "label_selector", None),
    "spread-match-label-keys": _set(_tsc0, "match_label_keys", ["app"]),
    "spread-node-affinity-policy": _set(_tsc0, "node_affinity_policy",
                                        "Ignore"),
    "spread-node-taints-policy": _set(_tsc0, "node_taints_policy", "Honor"),
    "spread-count": lambda p:
        p.spec.topology_spread_constraints.append(_tsc(key=LABEL_HOSTNAME)),
    "toleration-key": _set(lambda p: p.spec.tolerations[0], "key", "k2"),
    "toleration-operator": _set(lambda p: p.spec.tolerations[0], "operator",
                                "Exists"),
    "toleration-value": _set(lambda p: p.spec.tolerations[0], "value", "w"),
    "toleration-effect": _set(lambda p: p.spec.tolerations[0], "effect",
                              "NoExecute"),
    "term-topology-key": _set(_req0, "topology_key", LABEL_HOSTNAME),
    "term-namespaces": _set(_req0, "namespaces", ["default", "other"]),
    "term-selector": lambda p:
        _req0(p).label_selector.match_labels.update(app="api"),
    "term-match-label-keys": _set(_req0, "match_label_keys", []),
    "term-mismatch-label-keys": _set(_req0, "mismatch_label_keys", ["tier"]),
    "term-weight": _set(
        lambda p: p.spec.affinity.pod_affinity.preferred[0], "weight", 6),
    "term-required-or-preferred": _move_required_to_preferred,
    "term-affinity-or-anti": _swap_affinity_and_anti,
}


@pytest.mark.parametrize("field", sorted(KEYED_FIELDS))
def test_pods_that_differ_in_one_keyed_field_get_two_keys(field):
    base, other = _base(), copy.deepcopy(_base())
    other.metadata.name, other.metadata.uid = "other", "uid-other"
    assert Mirror._pod_row_key(base) == Mirror._pod_row_key(other)
    assert hash(Mirror._pod_row_key(base)) is not None
    KEYED_FIELDS[field](other)
    assert Mirror._pod_row_key(base) != Mirror._pod_row_key(other)
    # and the edit is one the row shows, packed on one batch of two
    cached, plain = _mirror_pair()
    f32, i32 = _pack_both(cached, plain, [base, other])
    ident = [cached.pod_codec.subset_layout(_all_fields(cached))[1][n][0]
             for n in Mirror.GROUP_IGNORED_FIELDS]
    i32[:, ident] = 0
    assert not _same_rows((f32[0], i32[0]), (f32[1], i32[1]))
    assert cached.row_cache_misses == 2


def test_the_cache_clears_at_its_bound(monkeypatch):
    monkeypatch.setattr(mirror_mod, "POD_ROW_CACHE_ENTRIES", 4)
    cached, plain = _mirror_pair()
    shapes = [_pod(f"s-{i}", {"shape": str(i)}) for i in range(BATCH)]
    _pack_both(cached, plain, shapes)
    st = cached.row_cache_stats()
    # the sixth shape finds five entries, more than the bound, and clears
    assert (st["misses"], st["clears"], st["entries"]) == (8, 1, 3)
    again = [_pod(f"t-{i}", {"shape": str(i)}) for i in range(BATCH)]
    _pack_both(cached, plain, again)
    st = cached.row_cache_stats()
    assert st["hits"] + st["misses"] == 16 and st["clears"] >= 2
    assert st["entries"] <= 5


def test_every_packed_pod_is_a_hit_a_miss_or_a_bypass():
    cached, plain = _mirror_pair()
    packed = 0
    for rnd in range(3):
        pods = ([HAND_SHAPES["tolerations"](f"t-{rnd}-{i}") for i in range(3)]
                + [BYPASS_SHAPES["node-selector"](f"n-{rnd}-{i}")
                   for i in range(2)]
                + [_pod(f"u-{rnd}", {"round": str(rnd)})])
        _pack_both(cached, plain, pods)
        packed += len(pods)
        st = cached.row_cache_stats()
        assert st["hits"] + st["misses"] + st["bypass"] == packed
    assert (st["hits"], st["misses"], st["bypass"]) == (8, 4, 6)
    # a subset without the identity columns cannot patch them: all bypass
    cached._pack_batch_np(pods, BATCH, ("req", "valid"))
    assert cached.row_cache_bypass == 6 + len(pods)
    # a re-bucketed mirror starts with an empty cache and the old counts
    fresh = Mirror(caps=Capacities(nodes=8, pods=32))
    fresh.adopt_hysteresis(cached)
    assert fresh.row_cache_stats() == {**cached.row_cache_stats(),
                                       "entries": 0}
