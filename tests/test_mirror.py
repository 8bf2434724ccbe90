"""Mirror packing semantics: the f32 representability boundary, and the
packed-row cache of _pack_batch_np (a hit is byte for byte the slow path's
row; what is not a function of a pod's content bypasses it), Mirror.sync
against a fresh mirror, and the pod-table row cache of slots with terms."""

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
from kubernetes_tpu.backend.node_info import PodInfo
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


# ---------------------------------------------------------------------------
# Mirror.sync does the work of the delta (ISSUE 31): whatever it skips, the
# host tables hold what a fresh Mirror synced from the same snapshot holds

SYNC_CAPS = dict(nodes=16, pods=128)
ZONES = ("moon-1", "moon-2", "moon-3")
# pods of the shapes a slot can hold: labels alone, a host port (the node
# row's port fields), (anti-)affinity terms (the slow arm of _pack_pod_slot)
SYNC_SHAPES = {
    "plain": lambda n: _pod(n),
    "labelled": lambda n: _pod(n, {"color": "blue"}),
    "web": lambda n: _pod(n, WEB),
    "host-port": lambda n: _pod(n, {"color": "blue"}, containers=[Container(
        name="c", ports=[ContainerPort(host_port=8080)],
        resources=ResourceRequirements(requests={"cpu": "50m"}))]),
    "required-anti-affinity": HAND_SHAPES["required-anti-affinity"],
    "preferred-affinity-and-anti": HAND_SHAPES["preferred-affinity-and-anti"],
    "required-and-preferred-with-spread":
        HAND_SHAPES["required-and-preferred-with-spread"],
    "extended-resource": HAND_SHAPES["init-containers-overhead-extended"],
}


def _node(name, zone, cpu="8", **labels):
    return Node(
        metadata=ObjectMeta(name=name, labels={
            LABEL_HOSTNAME: name, LABEL_ZONE: zone, **labels}),
        status=NodeStatus(allocatable={
            "cpu": cpu, "memory": "64Gi", "pods": "110",
            "example.com/gpu": "64"}))


def _fresh_mirror(inc, snap, nominated):
    """A Mirror that meets ``snap`` for the first time. The naming
    registries (interner, label columns, topology keys and their domains,
    extended-resource columns) only append, so the ids a mirror packs
    depend on the order it met the names in: the fresh one is given the
    incremental one's names, and row and slot numbers are left to differ.
    It keeps no pod-table row: every slot with terms is the full pack, so
    the comparison holds a hit of ``inc`` to the bytes of a full pack."""
    m = Mirror(interner=inc.interner, caps=inc.caps)
    m._slot_row_key = lambda pi: None
    m._label_col = dict(inc._label_col)
    m._pod_label_col = dict(inc._pod_label_col)
    m._topo_col = dict(inc._topo_col)
    m._tk_key = list(inc._tk_key)
    m._tk_domains = [dict(d) for d in inc._tk_domains]
    m._ext_index = dict(inc._ext_index)
    m.sync(snap)
    m.set_nominated(nominated)
    return m


def _fields_of(codec, f32_row, i32_row):
    out = {}
    for name, (off, size) in codec._f32_off.items():
        out[name] = f32_row[off:off + size].tobytes()
    for name, (off, size) in codec._i32_off.items():
        out[name] = i32_row[off:off + size].tobytes()
    return out


def _assert_same_tables(inc, fresh):
    """Every field of node_f32, node_i32 and pods_i32, by node name and
    pod uid; rows and slots in use, and nothing but zeros outside them."""
    assert set(inc._row_of) == set(fresh._row_of)
    for name in inc._row_of:
        a, b = inc.row_of(name), fresh.row_of(name)
        got = _fields_of(inc.node_codec, inc.node_f32[a], inc.node_i32[a])
        want = _fields_of(fresh.node_codec, fresh.node_f32[b],
                          fresh.node_i32[b])
        diff = [f for f in want if got[f] != want[f]]
        assert not diff, f"node {name}: fields {diff} differ"
    assert set(inc._pod_slot) == set(fresh._pod_slot)
    node_off = inc.table_codec._i32_off["pod_node"][0]
    empty = np.zeros((0,), np.float32)
    for uid, a in inc._pod_slot.items():
        b = fresh._pod_slot[uid]
        got = _fields_of(inc.table_codec, empty, inc.pods_i32[a])
        want = _fields_of(fresh.table_codec, empty, fresh.pods_i32[b])
        assert (inc.name_of_row(int(inc.pods_i32[a, node_off]))
                == fresh.name_of_row(int(fresh.pods_i32[b, node_off]))), uid
        diff = [f for f in want if f != "pod_node" and got[f] != want[f]]
        assert not diff, f"pod {uid}: fields {diff} differ"
    for m in (inc, fresh):
        assert int(m.node_i32.any(axis=1).sum()) == len(m._row_of)
        assert int(m.pods_i32.any(axis=1).sum()) == len(m._pod_slot)
    # what launch_features and table_has_topology read
    names = lambda m, rows: {m.name_of_row(r) for r in rows}
    assert names(inc, inc._rows_with_ports) == names(fresh,
                                                     fresh._rows_with_ports)
    assert set(inc._uids_with_terms) == set(fresh._uids_with_terms)
    assert set(inc._uids_with_nssel) == set(fresh._uids_with_nssel)


def _assert_device_is_host(m):
    """to_blobs scatters the dirty rows and slots alone: a write that
    skipped its dirty mark would leave the device behind the host."""
    blobs = m.to_blobs()
    assert np.asarray(blobs.node_f32).tobytes() == m.node_f32.tobytes()
    assert np.asarray(blobs.node_i32).tobytes() == m.node_i32.tobytes()
    assert np.asarray(blobs.pods_i32).tobytes() == m.pods_i32.tobytes()


class _Cluster:
    """A Cache driven as the scheduler and its informers drive it, the
    mirror under test beside it."""

    def __init__(self, seed):
        import random

        self.rng = random.Random(seed)
        self.cache = Cache()
        self.snap = Snapshot()
        self.inc = Mirror(caps=Capacities(**SYNC_CAPS))
        self.nodes = {}                 # name -> Node in the cache
        self.assumed = {}               # uid -> assumed clone
        self.confirmed = {}             # uid -> confirmed Pod
        self.nominated = {}             # node name -> [Pod]
        self.serial = 0
        for i in range(6):
            self.add_node()

    def add_node(self):
        # ten names in all: each is a hostname domain for the mirror's life
        name = self.rng.choice(sorted(
            {f"node-{i}" for i in range(10)} - set(self.nodes)))
        node = _node(name, self.rng.choice(ZONES))
        self.nodes[name] = node
        self.cache.add_node(node)

    def new_pod(self):
        self.serial += 1
        shape = self.rng.choice(sorted(SYNC_SHAPES))
        return SYNC_SHAPES[shape](f"p{self.serial}")

    def relabelled(self, pod):
        new = pod.clone()
        self.serial += 1
        new.metadata.labels["rev"] = str(self.serial)
        return new

    def pick(self, pods):
        return pods[self.rng.choice(sorted(pods))] if pods else None

    # ---- the operations; each returns the nodes it touched
    def assume(self):
        if len(self.assumed) + len(self.confirmed) > 40 or not self.nodes:
            return self.remove_pod()
        a = self.new_pod().clone()
        a.spec.node_name = self.rng.choice(sorted(self.nodes))
        self.cache.assume_pod(a)
        self.assumed[a.metadata.uid] = a
        return [a.spec.node_name]

    def confirm_equal(self):
        a = self.pick(self.assumed)
        if a is None:
            return self.assume()
        c = a.clone()                   # the informer's own object
        self.cache.add_pod(c)
        del self.assumed[c.metadata.uid]
        self.confirmed[c.metadata.uid] = c
        return [c.spec.node_name]

    def confirm_relabelled(self):
        a = self.pick(self.assumed)
        if a is None:
            return self.assume()
        c = self.relabelled(a)
        self.cache.add_pod(c)
        del self.assumed[c.metadata.uid]
        self.confirmed[c.metadata.uid] = c
        return [c.spec.node_name]

    def update_pod(self, relabel=True):
        old = self.pick(self.confirmed)
        if old is None:
            return self.confirm_equal()
        new = self.relabelled(old) if relabel else old.clone()
        self.cache.update_pod(old, new)
        self.confirmed[new.metadata.uid] = new
        return [new.spec.node_name]

    def update_pod_same_content(self):
        return self.update_pod(relabel=False)

    def update_pod_terms(self):
        """Other (anti-)affinity terms, or none, under the same labels:
        one of a few, so that pods come to share them."""
        old = self.pick(self.confirmed)
        if old is None:
            return self.confirm_equal()
        new = old.clone()
        new.spec.affinity = self.rng.choice((None, Affinity(
            pod_anti_affinity=PodAntiAffinity(required=[_term(LABEL_ZONE)])),
            Affinity(pod_affinity=PodAffinity(preferred=[
                WeightedPodAffinityTerm(self.rng.choice((2, 7)),
                                        _term(LABEL_HOSTNAME))]))))
        self.cache.update_pod(old, new)
        self.confirmed[new.metadata.uid] = new
        return [new.spec.node_name]

    def move_pod(self):
        old = self.pick(self.confirmed)
        if old is None or len(self.nodes) < 2:
            return self.confirm_equal()
        new = old.clone()
        new.spec.node_name = self.rng.choice(
            sorted(set(self.nodes) - {old.spec.node_name}))
        self.cache.add_pod(new)         # informer truth wins
        self.confirmed[new.metadata.uid] = new
        return [old.spec.node_name, new.spec.node_name]

    def remove_pod(self):
        p = self.pick(self.confirmed)
        if p is None:
            return self.forget_pod()
        self.cache.remove_pod(p)
        del self.confirmed[p.metadata.uid]
        return [p.spec.node_name]

    def forget_pod(self):
        a = self.pick(self.assumed)
        if a is None:
            return []
        self.cache.forget_pod(a)
        del self.assumed[a.metadata.uid]
        return [a.spec.node_name]

    def host_port_comes_and_goes(self):
        """The only host-port pod of a node arrives, then leaves."""
        if not self.nodes:
            return []
        self.serial += 1
        p = SYNC_SHAPES["host-port"](f"hp{self.serial}")
        p.spec.node_name = self.rng.choice(sorted(self.nodes))
        self.cache.add_pod(p)
        self.sync_and_compare()
        self.cache.remove_pod(p)
        return [p.spec.node_name]

    def update_node(self):
        old = self.pick(self.nodes)
        if old is None:
            return self.grow_nodes()
        self.serial += 1
        new = _node(old.metadata.name, old.metadata.labels[LABEL_ZONE],
                    cpu=str(self.rng.choice((4, 8, 16))),
                    rev=str(self.serial))
        self.cache.update_node(old, new)
        self.nodes[new.metadata.name] = new
        return [new.metadata.name]

    def grow_nodes(self):
        if len(self.nodes) >= 10:
            return self.remove_node()
        self.add_node()
        return []

    def remove_node(self):
        node = self.pick(self.nodes)
        if node is None:
            return []
        self.cache.remove_node(node)
        del self.nodes[node.metadata.name]
        return []

    def nominate(self):
        self.nominated = {}
        for _ in range(self.rng.randrange(3)):
            if self.nodes:
                p = self.new_pod()
                self.nominated.setdefault(
                    self.rng.choice(sorted(self.nodes)), []).append(p)
        return []

    def refresh_twice(self):
        """Two update_snapshot calls between two syncs: the newest one's
        changed_nodes are not all that moved."""
        touched = self.assume()
        self.cache.update_snapshot(self.snap)
        return touched + self.confirm_equal()

    OPS = ("assume", "assume", "assume", "confirm_equal", "confirm_equal",
           "confirm_equal", "confirm_relabelled", "update_pod",
           "update_pod_same_content", "update_pod_terms", "move_pod", "remove_pod", "forget_pod",
           "host_port_comes_and_goes", "update_node", "grow_nodes",
           "remove_node", "nominate", "refresh_twice")

    def patch(self, names):
        """Scheduler._apply_chain_patches: the live aggregate of a node,
        outside the snapshot."""
        for name in names:
            info = self.cache.node_info(name)
            got = self.inc.patch_node(name, info)
            if got is not None and info is not None and info.node is not None:
                row, free, nzr = got
                off, size = self.inc.node_codec._f32_off["free"]
                assert (free.tobytes()
                        == self.inc.node_f32[row, off:off + size].tobytes())
                assert nzr.shape == (2,)

    def sync_and_compare(self):
        self.cache.update_snapshot(self.snap)
        self.inc.sync(self.snap)
        self.inc.set_nominated(self.nominated)
        _assert_same_tables(
            self.inc, _fresh_mirror(self.inc, self.snap, self.nominated))
        _assert_device_is_host(self.inc)


@pytest.mark.parametrize("seed", range(12))
def test_incremental_mirror_equals_a_fresh_one_after_every_sync(seed):
    c = _Cluster(seed)
    c.sync_and_compare()
    for step in range(60):
        touched = []
        for _ in range(c.rng.randrange(1, 5)):
            touched += getattr(c, c.rng.choice(c.OPS))()
        if c.rng.random() < 0.3:
            c.patch(touched)
        c.sync_and_compare()
    st = c.inc.sync_stats()
    assert st["slots_kept"] > 0 and st["slots_released"] > 0
    assert st["slots_packed"] - st["slots_released"] == len(c.inc._pod_slot)
    # pods of one shape share their terms: among the slots the comparisons
    # above held to a full pack were copies of a kept row
    rc = c.inc.slot_row_cache_stats()
    assert rc["hits"] > 0 and rc["misses"] > 0 and rc["bypass"] == 0
    assert rc["hits"] + rc["misses"] == st["slots_packed_terms"]


def _bound_cluster(n_pods=3):
    """One node carrying ``n_pods`` assumed pods, synced."""
    cache, snap = Cache(), Snapshot()
    cache.add_node(_node("n0", "moon-1"))
    m = Mirror(caps=Capacities(**SYNC_CAPS))
    assumed = []
    for i in range(n_pods):
        a = _pod(f"a{i}", {"color": "blue"}).clone()
        a.spec.node_name = "n0"
        cache.assume_pod(a)
        assumed.append(a)
    cache.update_snapshot(snap)
    m.sync(snap)
    m.to_blobs()
    return cache, snap, m, assumed


def _one_more(cache, name="extra"):
    p = _pod(name).clone()
    p.spec.node_name = "n0"
    cache.assume_pod(p)
    return p


def test_a_content_neutral_confirmation_dirties_no_slot():
    cache, snap, m, assumed = _bound_cluster()
    before = m.sync_stats()
    for a in assumed:
        cache.add_pod(a.clone())        # the informer's new object
    cache.update_snapshot(snap)
    assert m.sync(snap) == 0, "a confirmation alone bumps no generation"
    extra = _one_more(cache)            # the node's next change
    cache.update_snapshot(snap)
    assert m.sync(snap) == 1
    st = m.sync_stats()
    assert st["slots_kept"] == before["slots_kept"] + 3
    assert st["slots_packed"] == before["slots_packed"] + 1
    assert st["slots_released"] == before["slots_released"]
    assert m._dirty_slots == {m._pod_slot[extra.metadata.uid]}
    # re-pointed: the next change of the node finds the objects it holds
    _one_more(cache, "extra2")
    cache.update_snapshot(snap)
    m.sync(snap)
    assert m.sync_stats()["slots_kept"] == st["slots_kept"]


@pytest.mark.parametrize("how", ["confirmation", "update_pod"])
def test_a_changed_label_repacks_the_slot(how):
    cache, snap, m, assumed = _bound_cluster()
    before = m.sync_stats()
    new = assumed[0].clone()
    new.metadata.labels["color"] = "green"
    if how == "confirmation":
        cache.add_pod(new)
    else:
        cache.add_pod(assumed[0].clone())
        cache.update_pod(assumed[0], new)
    cache.update_snapshot(snap)
    assert m.sync(snap) == 1, "the cache says the node changed"
    st = m.sync_stats()
    assert st["slots_packed"] == before["slots_packed"] + 1
    assert st["slots_released"] == before["slots_released"] + 1
    assert st["slots_kept"] == before["slots_kept"]
    slot = m._pod_slot[new.metadata.uid]
    off, size = m.table_codec._i32_off["pt_label_vals"]
    assert (m.pods_i32[slot, off:off + size].tobytes()
            == m.pod_labels_row({"color": "green"}).tobytes())
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))


def test_an_update_to_other_affinity_terms_repacks_the_slot():
    cache, snap, m, _ = _bound_cluster(0)
    old = HAND_SHAPES["required-anti-affinity"]("t")
    old.spec.node_name = "n0"
    cache.add_pod(old)
    cache.update_snapshot(snap)
    m.sync(snap)
    packed = m.slots_packed
    same = old.clone()                  # new object and PodInfo, equal terms
    cache.update_pod(old, same)
    cache.update_snapshot(snap)
    m.sync(snap)
    assert (m.slots_packed, m.slots_kept) == (packed, 1)
    other = same.clone()
    other.spec.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(
        required=[_term(LABEL_ZONE)]))
    cache.update_pod(same, other)
    cache.update_snapshot(snap)
    m.sync(snap)
    assert (m.slots_packed, m.slots_kept) == (packed + 1, 1)
    bare = other.clone()
    bare.spec.affinity = None           # the terms go: the fast arm again
    cache.update_pod(other, bare)
    cache.update_snapshot(snap)
    m.sync(snap)
    assert (m.slots_packed, m.slots_kept) == (packed + 2, 1)
    assert not m.table_has_topology()
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))


def test_a_row_that_loses_its_last_host_port_reads_none_again():
    from kubernetes_tpu.utils.interner import NONE

    cache, snap, m, _ = _bound_cluster()
    row = m.row_of("n0")

    def port_fields():
        return np.concatenate([
            m.node_i32[row, off:off + size] for off, size in (
                m.node_codec._i32_off[f]
                for f in ("port_ips", "port_protos", "port_nums"))])

    assert (port_fields() == NONE).all() and not m._rows_with_ports
    hp = SYNC_SHAPES["host-port"]("hp")
    hp.spec.node_name = "n0"
    cache.add_pod(hp)
    cache.update_snapshot(snap)
    m.sync(snap)
    assert m._rows_with_ports == {row}
    assert 8080 in port_fields() and "ports" in m.launch_features([])
    cache.remove_pod(hp)
    cache.update_snapshot(snap)
    m.sync(snap)
    assert (port_fields() == NONE).all() and not m._rows_with_ports
    assert "ports" not in m.launch_features([])
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))


def test_a_touched_node_costs_the_pods_that_are_new_to_it():
    """18 pods on the node and one new: one slot packed, no release scan
    (nothing left), nothing else dirtied."""
    cache, snap, m, assumed = _bound_cluster(18)
    for a in assumed:
        cache.add_pod(a.clone())
    before = m.sync_stats()
    extra = _one_more(cache)
    cache.update_snapshot(snap)
    m.sync(snap)
    st = m.sync_stats()
    assert st["slots_packed"] == before["slots_packed"] + 1
    assert st["slots_kept"] == before["slots_kept"] + 18
    assert st["slots_released"] == before["slots_released"]
    assert m._dirty_slots == {m._pod_slot[extra.metadata.uid]}
    assert m._dirty_rows == {m.row_of("n0")}


def test_patch_node_followed_by_a_full_sync_repacks_nothing():
    cache, snap, m, _ = _bound_cluster()
    _one_more(cache)
    row, free, nzr = m.patch_node("n0", cache.node_info("n0"))
    st = m.sync_stats()
    m.to_blobs()
    cache.update_snapshot(snap)
    assert m.sync(snap) == 0
    assert m.sync_stats() == st and not m._dirty_slots and not m._dirty_rows
    fresh = _fresh_mirror(m, snap, {})
    _assert_same_tables(m, fresh)
    off, size = m.node_codec._f32_off["free"]
    assert free.tobytes() == fresh.node_f32[
        fresh.row_of("n0"), off:off + size].tobytes()
    off, size = m.node_codec._f32_off["nonzero_requested"]
    assert nzr.tobytes() == fresh.node_f32[
        fresh.row_of("n0"), off:off + size].tobytes()


def test_sync_visits_the_changed_nodes_alone_when_the_snapshot_names_them():
    cache, snap = Cache(), Snapshot()
    for i in range(5):
        cache.add_node(_node(f"n{i}", ZONES[i % 3]))
    m = Mirror(caps=Capacities(**SYNC_CAPS))
    cache.update_snapshot(snap)
    assert snap.changed_nodes is None, "the node set moved: a full pass"
    assert m.sync(snap) == 5
    p = _pod("p").clone()
    p.spec.node_name = "n3"
    cache.assume_pod(p)
    cache.update_snapshot(snap)
    assert snap.changed_nodes == ["n3"]
    # a generation the refresh did not name is not looked at
    m._row_gen["n1"] = -1
    assert m.sync(snap) == 1
    # two refreshes since the last sync: the newest names one node only,
    # so the full pass runs and finds the stale row as well
    for node in ("n0", "n2"):
        q = _pod(f"q-{node}").clone()
        q.spec.node_name = node
        cache.assume_pod(q)
        cache.update_snapshot(snap)
    assert snap.changed_nodes == ["n2"]
    assert m.sync(snap) == 3            # n0, n2 and the stale n1
    # a node removed: the refresh cannot name what moved
    cache.remove_node(_node("n4", ZONES[1]))
    cache.update_snapshot(snap)
    assert snap.changed_nodes is None
    assert m.sync(snap) == 1 and m.row_of("n4") == -1
    # another Snapshot object: a full pass, nothing to repack
    other = Snapshot()
    cache.update_snapshot(other)
    assert m.sync(other) == 0
    _assert_same_tables(m, _fresh_mirror(m, other, {}))


def test_a_sync_that_raised_leaves_the_next_one_a_full_pass():
    from kubernetes_tpu.api.objects import NodeSpec, Taint
    from kubernetes_tpu.backend.mirror import CapacityError

    cache, snap = Cache(), Snapshot()
    nodes = [_node(f"n{i}", ZONES[i]) for i in range(3)]
    for node in nodes:
        cache.add_node(node)
    m = Mirror(caps=Capacities(**SYNC_CAPS))
    cache.update_snapshot(snap)
    m.sync(snap)
    for i in range(2):
        p = _pod(f"p{i}").clone()
        p.spec.node_name = f"n{i}"
        cache.assume_pod(p)
    tainted = _node("n2", ZONES[2])
    tainted.spec = NodeSpec(taints=[
        Taint(key=f"k{i}", value="v", effect="NoSchedule")
        for i in range(m.caps.node_taints + 1)])
    cache.update_node(nodes[2], tainted)
    cache.update_snapshot(snap)
    with pytest.raises(CapacityError):
        m.sync(snap)
    assert m._last_sync is None
    cache.update_node(tainted, _node("n2", ZONES[2]))
    cache.update_snapshot(snap)
    assert snap.changed_nodes == ["n2"]
    assert m.sync(snap) == 3            # not the delta: every row again
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))


def test_sync_stats_survive_a_rebucketed_mirror():
    cache, snap, m, assumed = _bound_cluster()
    for a in assumed:
        cache.add_pod(a.clone())
    cache.remove_pod(assumed[0])
    cache.update_snapshot(snap)
    m.sync(snap)
    st = m.sync_stats()
    assert st == {"rows_synced": 2, "slots_packed": 3,
                  "slots_packed_terms": 0, "slots_kept": 2,
                  "slots_released": 1}
    grown = Mirror(caps=Capacities(nodes=16, pods=256))
    grown.adopt_hysteresis(m)
    assert grown.sync_stats() == st
    assert not grown._label_rows and not grown._pod_slot


def test_label_rows_are_shared_read_only_and_bounded(monkeypatch):
    monkeypatch.setattr(mirror_mod, "POD_ROW_CACHE_ENTRIES", 4)
    m = Mirror(caps=Capacities(**SYNC_CAPS))
    a = m.pod_labels_row({"color": "blue"})
    assert m.pod_labels_row({"color": "blue"}) is a
    with pytest.raises(ValueError):
        a[0] = 7
    plain = Mirror(interner=m.interner, caps=m.caps)
    plain._pod_label_col = dict(m._pod_label_col)
    for i in range(12):
        labels = {"color": "blue", f"k{i % 3}": str(i)}
        want = np.full((m.caps.pod_label_cols,), -1, np.int32)
        for k, v in labels.items():
            want[plain.pod_label_col(k)] = plain._i(v)
        assert m.pod_labels_row(labels).tobytes() == want.tobytes()
        assert len(m._label_rows) <= 5


# ---------------------------------------------------------------------------
# the pod-table row cache (ISSUE 33): a slot with terms is packed once a
# content, then copied; the copy is byte for byte the full pack


def _match_label_keys_pod(n):
    return _pod(n, {**WEB, "pod-template-hash": "abc12"}, affinity=Affinity(
        pod_affinity=PodAffinity(required=[
            _term(match_label_keys=["pod-template-hash", "absent"])]),
        pod_anti_affinity=PodAntiAffinity(preferred=[
            WeightedPodAffinityTerm(5, _term(
                LABEL_HOSTNAME, mismatch_label_keys=["tier"]))])))


# every benchmark template that carries terms, and hand shapes around them
TERM_SHAPES = {
    **{name: make for name, make in _template_shapes().items()
       if "affinity" in name and name not in TEMPLATE_BYPASS},
    "match-label-keys": _match_label_keys_pod,
    **{name: HAND_SHAPES[name] for name in (
        "required-affinity", "required-anti-affinity",
        "preferred-affinity-and-anti", "required-and-preferred-with-spread")},
}


def _bind(cache, pod, node):
    a = pod.clone()
    a.spec.node_name = node
    cache.assume_pod(a)
    return a


def _slot_cluster(n_nodes=3):
    cache, snap = Cache(), Snapshot()
    for i in range(n_nodes):
        cache.add_node(_node(f"n{i}", ZONES[i % 3]))
    m = Mirror(caps=Capacities(**SYNC_CAPS))
    cache.update_snapshot(snap)
    m.sync(snap)
    return cache, snap, m


def test_the_benchmark_templates_with_terms_are_all_here():
    assert {"pod-with-pod-affinity", "pod-with-pod-affinity-init",
            "pod-with-pod-anti-affinity", "pod-with-preferred-pod-affinity",
            "pod-with-preferred-pod-anti-affinity"} <= set(TERM_SHAPES)


@pytest.mark.parametrize("shape", sorted(TERM_SHAPES))
def test_a_slot_written_by_a_hit_is_what_a_full_pack_writes(shape):
    """One miss a content; the slots after it, on other rows, of other
    uids and as a nominated overlay, are copies with three columns
    patched, and read what a mirror that keeps no row packs in full."""
    make = TERM_SHAPES[shape]
    cache, snap, m = _slot_cluster()
    _bind(cache, make(f"{shape}-0"), "n0")
    cache.update_snapshot(snap)
    m.sync(snap)
    assert m.slot_row_cache_stats() == {
        "hits": 0, "misses": 1, "bypass": 0, "clears": 0, "entries": 1}
    for i, node in enumerate(("n1", "n2", "n1", "n0"), start=1):
        _bind(cache, make(f"{shape}-{i}"), node)
    cache.update_snapshot(snap)
    m.sync(snap)
    nominated = {"n2": [make(f"{shape}-nominated")]}
    m.set_nominated(nominated)
    st = m.slot_row_cache_stats()
    assert (st["hits"], st["misses"], st["bypass"]) == (5, 1, 0)
    assert m.sync_stats()["slots_packed_terms"] == 6
    fresh = _fresh_mirror(m, snap, nominated)
    assert fresh.slot_row_cache_stats() == {
        "hits": 0, "misses": 0, "bypass": 6, "clears": 0, "entries": 0}
    _assert_same_tables(m, fresh)
    _assert_device_is_host(m)
    # the overlay slot carries the flag, the bound ones do not
    o_nom = m.table_codec._i32_off["pod_nominated"][0]
    flags = {uid: int(m.pods_i32[slot, o_nom])
             for uid, slot in m._pod_slot.items()}
    assert [u for u, f in flags.items() if f] == [
        f"nominated:{nominated['n2'][0].metadata.uid}"]
    # the overlay refreshed, as every cycle does: a hit again, same bytes
    m.set_nominated(nominated)
    assert m.slot_row_cache_stats()["hits"] == 6
    _assert_same_tables(m, fresh)


def _no_listed_namespaces(n, namespace="default", labels=None):
    return _pod(n, labels or WEB, namespace=namespace, affinity=Affinity(
        pod_affinity=PodAffinity(required=[
            _term(match_label_keys=["tier"])])))


@pytest.mark.parametrize("differ", ["labels", "namespace"])
def test_equal_terms_under_other_labels_or_namespace_get_other_rows(differ):
    """The terms are equal, but match_label_keys copies the owner's label
    and a term with no listed namespaces selects in the owner's: two keys,
    two rows, each the full pack of its pod."""
    cache, snap, m = _slot_cluster()
    a = _bind(cache, _no_listed_namespaces("a"), "n0")
    b = _bind(cache, _no_listed_namespaces("b", **(
        {"labels": {**WEB, "tier": "back"}} if differ == "labels"
        else {"namespace": "team-b"})), "n0")
    assert (PodInfo(a).required_affinity_terms
            == PodInfo(b).required_affinity_terms)
    assert Mirror._slot_row_key(PodInfo(a)) != Mirror._slot_row_key(PodInfo(b))
    cache.update_snapshot(snap)
    m.sync(snap)
    st = m.slot_row_cache_stats()
    assert (st["hits"], st["misses"], st["entries"]) == (0, 2, 2)
    sa, sb = (m._pod_slot[p.metadata.uid] for p in (a, b))
    field = "pod_aff_sel_vals" if differ == "labels" else "pod_aff_ns"
    off, size = m.table_codec._i32_off[field]
    assert (m.pods_i32[sa, off:off + size].tobytes()
            != m.pods_i32[sb, off:off + size].tobytes())
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))


def test_a_namespace_selector_term_bypasses_the_slot_row_cache():
    """Such a row reads the namespace store and the known namespaces: it
    is packed in full every time, is in _uids_with_nssel, and is packed
    again when the namespaces move, as before the cache."""
    make = lambda n: _pod(n, WEB, affinity=Affinity(
        pod_affinity=PodAffinity(required=[_term(
            namespace_selector=_sel({"team": "a"}))])))
    assert Mirror._slot_row_key(PodInfo(make("x"))) is None
    assert Mirror._slot_row_key(PodInfo(
        BYPASS_SHAPES["empty-namespace-selector-term"]("y"))) is None
    cache, snap, m = _slot_cluster()
    pods = [_bind(cache, make(f"s{i}"), "n0") for i in range(3)]
    uids = {p.metadata.uid for p in pods}
    cache.update_snapshot(snap)
    m.sync(snap)
    assert m.slot_row_cache_stats() == {
        "hits": 0, "misses": 0, "bypass": 3, "clears": 0, "entries": 0}
    assert m._uids_with_nssel == uids
    off, size = m.table_codec._i32_off["pod_aff_ns"]

    def listed():
        return {frozenset(m.pods_i32[m._pod_slot[u], off:off + size]) - {-1}
                for u in uids}

    assert listed() == {frozenset()}, "no namespace carries the label yet"
    # a Namespace object the selector matches: the three are packed again
    cache.set_namespace("alpha", {"team": "a"})
    cache.update_snapshot(snap)
    packed = m.slots_packed
    m.sync(snap)
    assert m.slots_packed == packed + 3
    assert listed() == {frozenset({m._i("alpha")})}
    # a pod in a namespace no packed pod lived in: again, and the new slot
    _bind(cache, _pod("newcomer", namespace="other"), "n1")
    cache.update_snapshot(snap)
    m.sync(snap)
    assert m.slots_packed == packed + 7
    st = m.slot_row_cache_stats()
    assert (st["hits"], st["misses"], st["bypass"]) == (0, 0, 9)
    assert m.sync_stats()["slots_packed_terms"] == 9
    assert m._uids_with_nssel == uids
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))
    # the empty selector matches every namespace whatever the store holds:
    # still a bypass (the key draws _pod_row_key's line), and not in
    # _uids_with_nssel, since nothing can move its row
    e = _bind(cache, BYPASS_SHAPES["empty-namespace-selector-term"]("e"),
              "n2")
    cache.update_snapshot(snap)
    m.sync(snap)
    assert m.slot_row_cache_stats()["bypass"] == 10
    assert m._uids_with_nssel == uids
    assert e.metadata.uid in m._uids_with_terms
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))


def test_slot_rows_are_read_only_bounded_and_left_behind_by_a_rebucket(
        monkeypatch):
    monkeypatch.setattr(mirror_mod, "POD_ROW_CACHE_ENTRIES", 4)
    cache, snap, m = _slot_cluster()
    first = _bind(cache, _no_listed_namespaces("first"), "n0")
    cache.update_snapshot(snap)
    m.sync(snap)
    (kept,) = m._slot_rows.values()
    with pytest.raises(ValueError):
        kept[0] = 7
    assert (kept.tobytes()
            == m.pods_i32[m._pod_slot[first.metadata.uid]].tobytes())
    # pods that all differ in a label the term copies: a miss each
    for i in range(12):
        _bind(cache, _no_listed_namespaces(
            f"u{i}", labels={**WEB, "tier": f"t{i}"}), f"n{i % 3}")
        cache.update_snapshot(snap)
        m.sync(snap)
        assert len(m._slot_rows) <= 5
    st = m.slot_row_cache_stats()
    assert (st["hits"], st["misses"], st["clears"]) == (0, 13, 2)
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))
    # after a clear the old content is a miss again, then a hit
    for name in ("again", "and-again"):
        _bind(cache, _no_listed_namespaces(name), "n1")
    cache.update_snapshot(snap)
    m.sync(snap)
    st = m.slot_row_cache_stats()
    assert (st["hits"], st["misses"]) == (1, 14)
    _assert_same_tables(m, _fresh_mirror(m, snap, {}))
    # a re-bucketed mirror: other row widths, an empty cache, the counts
    grown = Mirror(caps=Capacities(nodes=16, pods=256, aff_terms=8))
    grown.adopt_hysteresis(m)
    assert grown.slot_row_cache_stats() == {**st, "entries": 0}
    assert grown.pods_i32.shape[1] != m.pods_i32.shape[1]
    grown.sync(snap)
    after = grown.slot_row_cache_stats()
    assert after["hits"] + after["misses"] == st["hits"] + st["misses"] + 15
    assert (grown.sync_stats()["slots_packed_terms"]
            == m.sync_stats()["slots_packed_terms"] + 15)
