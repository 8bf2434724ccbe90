"""Required node affinity where it decides something. The cell
`node-affinity-5k.backlog` keeps upstream's one zone, in which the term
rejects no node, so the system is tied to benchmark/reference_node_affinity.py
here: the reference alone on each operator and on placements that are each
wrong in one way; the production Scheduler over an in-process Hub on small
clusters built by hand from a seed (three zones, nodes with no zone label, a
numeric label and a tier label), its end state held to the reference; the
`node_affinity` kernel's [N] mask against the reference's verdict per node;
and the host's full pack of a bypassed row, with the `pack_full` view that
times it."""

import os
import random
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cell, objects  # noqa: E402
from benchmark import reference_node_affinity as ref  # noqa: E402

ZONE = objects.ZONE_KEY
GPUS = "example.com/gpus"
TIER = "example.com/tier"
MEASURED = objects.load_template("pod-with-node-affinity")
TERMS = ref.required_node_terms(MEASURED)
SEEDS = (3_900_000_019, 2_147_483_659)


def _req(key, op, *values):
    return {"key": key, "operator": op, "values": list(values)}


def _term(*exprs, fields=()):
    return {"match_expressions": list(exprs), "match_fields": list(fields)}


def test_reference_node_affinity_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark",
                           "reference_node_affinity.py")) as f:
        lines = [ln.split() for ln in f.read().splitlines()]
    imported = [ln[1] for ln in lines if ln[:1] in (["import"], ["from"])]
    assert imported == ["__future__"]
    assert TERMS == [_term(_req(ZONE, "In", "zone1", "zone2"))]
    assert ref.required_node_terms(objects.load_template("pod-default")) == []


# ------------------------------------------------- the reference alone

OPERATORS = [
    # (requirement, node labels, whether the node satisfies it)
    (_req(ZONE, "In", "zone1", "zone2"), {ZONE: "zone2"}, True),
    (_req(ZONE, "In", "zone1", "zone2"), {ZONE: "zone3"}, False),
    (_req(ZONE, "In", "zone1"), {}, False),
    (_req(ZONE, "NotIn", "zone1"), {ZONE: "zone2"}, True),
    (_req(ZONE, "NotIn", "zone1"), {ZONE: "zone1"}, False),
    (_req(ZONE, "NotIn", "zone1"), {}, True),
    (_req(TIER, "Exists"), {TIER: ""}, True),
    (_req(TIER, "Exists"), {}, False),
    (_req(TIER, "DoesNotExist"), {}, True),
    (_req(TIER, "DoesNotExist"), {TIER: "gold"}, False),
    (_req(GPUS, "Gt", "1"), {GPUS: "2"}, True),
    (_req(GPUS, "Gt", "1"), {GPUS: "1"}, False),
    (_req(GPUS, "Gt", "-3"), {GPUS: "+0"}, True),
    (_req(GPUS, "Gt", "1"), {GPUS: "2.5"}, False),
    (_req(GPUS, "Gt", "1"), {GPUS: "many"}, False),
    (_req(GPUS, "Gt", "1"), {}, False),
    (_req(GPUS, "Lt", "3"), {GPUS: "2"}, True),
    (_req(GPUS, "Lt", "3"), {GPUS: "3"}, False),
    (_req(GPUS, "Lt", "x"), {GPUS: "2"}, False),
    (_req(GPUS, "Lt", "3"), {}, False),
]


@pytest.mark.parametrize("req, labels, want", OPERATORS,
                         ids=lambda v: str(v) if isinstance(v, bool) else None)
def test_reference_holds_each_operator(req, labels, want):
    assert ref.node_matches([_term(req)], "n0", labels) is want


def test_reference_ors_terms_ands_requirements_and_reads_the_name_field():
    zone3_or_gold = [_term(_req(ZONE, "In", "zone3")),
                     _term(_req(TIER, "In", "gold"))]
    assert ref.node_matches(zone3_or_gold, "n", {ZONE: "zone1", TIER: "gold"})
    assert ref.node_matches(zone3_or_gold, "n", {ZONE: "zone3"})
    assert not ref.node_matches(zone3_or_gold, "n", {ZONE: "zone1"})
    both = [_term(_req(ZONE, "In", "zone2"), _req(GPUS, "Lt", "2"))]
    assert ref.node_matches(both, "n", {ZONE: "zone2", GPUS: "1"})
    assert not ref.node_matches(both, "n", {ZONE: "zone2", GPUS: "2"})
    pin = [_term(fields=[_req(ref.NAME_FIELD, "In", "node-4")])]
    assert ref.node_matches(pin, "node-4", {})
    assert not ref.node_matches(pin, "node-5", {ref.NAME_FIELD: "node-4"})
    # a term with no requirement matches nothing; no terms, every node
    assert not ref.node_matches([_term()], "n", {ZONE: "zone1"})
    assert ref.node_matches([], "n", {})


WRONG = {
    # placement of m0 (the others right), and how many pods it breaks
    "the node is in a zone the term does not list": (
        [("m0", "c0"), ("m1", "a0")], 1),
    "the node has no zone label": ([("m0", "bare"), ("m1", "b0")], 1),
    "the node is not in the cluster": ([("m0", "gone"), ("m1", "a0")], 1),
    "two pods, each outside the term": ([("m0", "c0"), ("m1", "bare")], 2),
}
NODE_LABELS = {"a0": {ZONE: "zone1"}, "b0": {ZONE: "zone2"},
               "c0": {ZONE: "zone3"}, "bare": {}}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_reference_counts_a_placement_that_is_wrong_in_one_way(wrong):
    pods, bad = WRONG[wrong]
    assert ref.node_affinity_violated(TERMS, NODE_LABELS, pods,
                                      ["m0", "m1"]) == bad


def test_reference_judges_only_bound_judged_pods():
    pods = [("m0", "a0"), ("m1", "b0"), ("other", "c0")]
    assert ref.node_affinity_violated(TERMS, NODE_LABELS, pods,
                                      ["m0", "m1", "pending"]) == 0
    assert ref.node_affinity_violated([], NODE_LABELS, pods, ["other"]) == 0
    assert ref.node_affinity_feasible_nodes(TERMS, NODE_LABELS) == ["a0", "b0"]


# ------------------------------------------------- the production scheduler


def _node_labels(i):
    """Node i of twelve: three a zone and three with none; a whole-number
    gpu count on most (one reads `many`), a tier on some."""
    labels = {}
    if i < 9:
        labels[ZONE] = ("zone1", "zone2", "zone3")[i % 3]
    if i % 4:
        labels[GPUS] = "many" if i == 7 else str(i % 5)
    if i % 3 == 1:
        labels[TIER] = "gold" if i % 2 else "silver"
    return labels


def _selector_term(t):
    from kubernetes_tpu.api.objects import (
        NodeSelectorRequirement, NodeSelectorTerm)

    def reqs(rs):
        return [NodeSelectorRequirement(key=r["key"], operator=r["operator"],
                                        values=list(r["values"])) for r in rs]
    return NodeSelectorTerm(match_expressions=reqs(t["match_expressions"]),
                            match_fields=reqs(t["match_fields"]))


def _pod(name, terms, preferred=(), cpu="100m"):
    """A pod whose required terms are `terms` (the reference's form) and
    whose preferred terms are [(weight, term)]."""
    from kubernetes_tpu.api.objects import (
        Affinity, Container, NodeAffinity, NodeSelector, ObjectMeta, Pod,
        PodSpec, PreferredSchedulingTerm, ResourceRequirements)

    na = NodeAffinity(
        required=NodeSelector(node_selector_terms=[
            _selector_term(t) for t in terms]),
        preferred=[PreferredSchedulingTerm(weight=w,
                                           preference=_selector_term(t))
                   for w, t in preferred])
    return Pod(metadata=ObjectMeta(name=name, uid=f"p-{name}"),
               spec=PodSpec(affinity=Affinity(node_affinity=na),
                            containers=[Container(
                                name="pause", resources=ResourceRequirements(
                                    requests={"cpu": cpu,
                                              "memory": "500Mi"}))]))


def _run(seed, pods, cpu_of=lambda labels: "4"):
    """Twelve nodes of node-default (cpu as `cpu_of(labels)` says) created
    in the seed's order, `pods` through Scheduler.run_until_idle. Returns
    (node labels, bound (uid, node) of the offered pods, uids offered)."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    rng = random.Random(seed)
    tmpl = objects.load_template("node-default")
    nodes = []
    for i in range(12):
        labels = _node_labels(i)
        node = objects.make_node(dict(tmpl, allocatable={
            "cpu": cpu_of(labels), "memory": "32Gi", "pods": "110"}), i, [])
        node.metadata.labels.update(labels)
        nodes.append(node)
    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 16
    cfg.tie_break_seed = seed & 0xffffffff
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=256))
    try:
        for n in rng.sample(nodes, len(nodes)):
            hub.create_node(n)
        for p in pods:
            hub.create_pod(p)
        sched.run_until_idle()
        labels = {n.metadata.name: n.metadata.labels
                  for n in hub.list_nodes()}
        offered = {p.metadata.uid for p in pods}
        bound = [(p.metadata.uid, p.spec.node_name) for p in hub.list_pods()
                 if p.spec.node_name and p.metadata.uid in offered]
        assert sched.stats["device_fallbacks"] == 0
    finally:
        sched.close()
    return labels, bound, sorted(offered)


CASES = {
    # name -> (required terms, preferred [(weight, term)])
    "the template's term: zone In [zone1, zone2]": (TERMS, ()),
    "NotIn [zone1, zone2]: zone3 and the unlabelled nodes": (
        [_term(_req(ZONE, "NotIn", "zone1", "zone2"))], ()),
    "Exists tier": ([_term(_req(TIER, "Exists"))], ()),
    "DoesNotExist zone": ([_term(_req(ZONE, "DoesNotExist"))], ()),
    "Gt gpus 2 (a label `many` is no number)": (
        [_term(_req(GPUS, "Gt", "2"))], ()),
    "Lt gpus 3 and In zone2, one term": (
        [_term(_req(GPUS, "Lt", "3"), _req(ZONE, "In", "zone2"))], ()),
    "two terms ORed: zone3, or tier gold": (
        [_term(_req(ZONE, "In", "zone3")), _term(_req(TIER, "In", "gold"))],
        ()),
    "a metadata.name pin": (
        [_term(fields=[_req(ref.NAME_FIELD, "In", "node-4")])], ()),
    "preferred zone3 beside required zone1: feasibility stands": (
        [_term(_req(ZONE, "In", "zone1"))],
        [(100, _term(_req(ZONE, "In", "zone3")))]),
    "preferred terms inside the feasible set only steer": (
        TERMS, [(80, _term(_req(ZONE, "In", "zone2"))),
                (20, _term(_req(TIER, "Exists")))]),
}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_scheduler_binds_only_where_the_reference_says_the_terms_hold(
        case, seed):
    terms, preferred = CASES[case]
    pods = [_pod(f"m-{seed}-{i}", terms, preferred) for i in range(24)]
    labels, bound, offered = _run(seed, pods)
    feasible = ref.node_affinity_feasible_nodes(terms, labels)
    assert 0 < len(feasible) < len(labels), "the terms decide"
    assert len(bound) == 24, "every pod is bound"
    assert {node for _uid, node in bound} <= set(feasible)
    assert ref.node_affinity_violated(terms, labels, bound, offered) == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_pods_left_pending_have_no_feasible_node_with_room(seed):
    """zone2's three nodes have 1 cpu, ten of these pods each; 36 are
    offered: 30 bind there and six wait, though nine other nodes have
    room, because by the reference no node they may take is left."""
    terms = [_term(_req(ZONE, "In", "zone2"))]
    pods = [_pod(f"m-{seed}-{i}", terms) for i in range(36)]
    labels, bound, offered = _run(
        seed, pods, cpu_of=lambda lb: "1" if lb.get(ZONE) == "zone2" else "4")
    feasible = ref.node_affinity_feasible_nodes(terms, labels)
    assert len(feasible) == 3
    assert len(bound) == 30
    assert ref.node_affinity_violated(terms, labels, bound, offered) == 0
    per_node = {n: sum(1 for _u, b in bound if b == n) for n in feasible}
    assert per_node == dict.fromkeys(feasible, 10), "no feasible node has room"


# ------------------------------------------------- the kernel


def _random_term(rng, node_names):
    exprs, fields = [], []
    for _ in range(rng.randint(1, 3)):
        key = rng.choice((ZONE, GPUS, TIER))
        op = rng.choice(("In", "NotIn", "Exists", "DoesNotExist", "Gt", "Lt"))
        if op in ("Gt", "Lt"):
            key, values = GPUS, [str(rng.randint(0, 4))]
        elif op in ("In", "NotIn"):
            pool = {ZONE: ["zone1", "zone2", "zone3"], GPUS: ["0", "2", "many"],
                    TIER: ["gold", "silver"]}[key]
            values = rng.sample(pool, rng.randint(1, 2))
        else:
            values = []
        exprs.append(_req(key, op, *values))
    if rng.random() < 0.25:
        fields.append(_req(ref.NAME_FIELD, rng.choice(("In", "NotIn")),
                           *rng.sample(node_names, 2)))
    return _term(*exprs, fields=fields)


@pytest.mark.parametrize("seed", (7, 3_900_000_031, 2_147_483_647))
def test_node_affinity_kernel_mask_is_the_references_verdict(seed):
    """ops/filters.node_affinity's [N] mask, over seeded random node labels
    and terms (every operator, ORed terms, a metadata.name field), against
    the reference's verdict node by node."""
    import jax

    from kubernetes_tpu.backend.cache import Cache
    from kubernetes_tpu.backend.mirror import Mirror
    from kubernetes_tpu.backend.snapshot import Snapshot
    from kubernetes_tpu.ops import filters
    from kubernetes_tpu.ops.features import Capacities

    rng = random.Random(seed)
    tmpl = objects.load_template("node-default")
    cache = Cache()
    labels = {}
    for i in range(12):
        node = objects.make_node(tmpl, i, [])
        pool = {ZONE: ["zone1", "zone2", "zone3", None],
                GPUS: ["0", "1", "2", "3", "4", "many", None],
                TIER: ["gold", "silver", None]}
        for key, values in pool.items():
            value = rng.choice(values)
            if value is not None:
                node.metadata.labels[key] = value
        labels[node.metadata.name] = node.metadata.labels
        cache.add_node(node)
    snap = Snapshot()
    cache.update_snapshot(snap)
    mirror = Mirror(caps=Capacities(nodes=16, pods=64))
    mirror.sync(snap)
    ct = mirror.to_device()
    names = [ni.name for ni in snap.node_info_list]
    rows = [mirror.row_of(n) for n in names]
    kernel = jax.jit(filters.node_affinity)
    decided = 0
    for k in range(24):
        terms = [_random_term(rng, names) for _ in range(rng.randint(1, 3))]
        pf = jax.tree.map(lambda x: x[0], mirror.pack_batch(
            [_pod(f"k-{k}", terms)], 1))
        mask = np.asarray(kernel(ct, pf))
        got = {n: bool(mask[r]) for n, r in zip(names, rows)}
        want = {n: ref.node_matches(terms, n, labels[n]) for n in names}
        assert got == want, terms
        decided += 0 < sum(want.values()) < len(names)
    assert decided >= 6, "the random terms decide on enough pods"


# ------------------------------------------------- the full pack and its view


def _bypass_mirror():
    from kubernetes_tpu.backend.cache import Cache
    from kubernetes_tpu.backend.mirror import Mirror
    from kubernetes_tpu.backend.snapshot import Snapshot
    from kubernetes_tpu.ops.features import Capacities

    cache, snap = Cache(), Snapshot()
    for i in range(4):
        cache.add_node(objects.make_node(
            objects.load_template("node-default"), i, ["zone1"]))
    cache.update_snapshot(snap)
    m = Mirror(caps=Capacities(nodes=16, pods=128))
    m.sync(snap)
    return m


class _CountingClock:
    def __init__(self):
        self.reads, self.t = 0, 0.0

    def perf_counter(self):
        self.reads += 1
        self.t += 0.001
        return self.t


def test_bypassed_row_is_pack_pods_row_and_the_view_reads_no_hit(
        monkeypatch):
    """A pod with node affinity has no key: _pack_batch_np packs it in full,
    and that row is byte for byte what pack_pod packs for it (name and uid
    patched per pod); pack_full_s grows by a clock pair a bypassed row and
    by nothing on a batch whose rows the cache serves, which reads no
    clock at all."""
    from kubernetes_tpu.backend import mirror as mirror_mod

    m = _bypass_mirror()
    maker = objects.PodMaker(MEASURED)
    pods = [maker.make(f"na-{i}") for i in range(6)]
    feats = m.launch_features(pods)
    assert "nodeaffinity" in feats
    fields = m.pod_fields(feats, False)
    clock = _CountingClock()
    monkeypatch.setattr(mirror_mod, "time",
                        types.SimpleNamespace(perf_counter=clock.perf_counter))
    f32, i32 = m._pack_batch_np(pods, 8, fields)
    assert m.row_cache_stats()["bypass"] == 6
    assert m.row_cache_stats()["hits"] == m.row_cache_stats()["misses"] == 0
    assert clock.reads == 12 and m.pack_full_s == pytest.approx(0.006)
    fresh = _bypass_mirror()
    fresh._pack_batch_np(pods[:1], 8, fields)     # the same registries
    tf, ti = fresh._subset_tmpl[fields]
    for b, pod in enumerate(pods):
        rf, ri = fresh.pod_codec.alloc_subset(fields, 1)
        rf[0], ri[0] = tf, ti
        fresh.pod_codec.pack_into_subset(fields, rf[0], ri[0],
                                         fresh.pack_pod(pod, active_only=True))
        assert f32[b].tobytes() == rf[0].tobytes(), pod.metadata.name
        assert i32[b].tobytes() == ri[0].tobytes(), pod.metadata.name
    # plain pods: one miss (a full pack, timed), then every row a hit
    plain = objects.PodMaker(objects.load_template("pod-default"))
    m._pack_batch_np([plain.make("p-0")], 8, fields)
    reads, full_s = clock.reads, m.pack_full_s
    m._pack_batch_np([plain.make(f"p-{i}") for i in range(1, 8)], 8, fields)
    assert m.row_cache_stats()["hits"] == 7
    assert clock.reads == reads and m.pack_full_s == full_s


def test_pack_full_view_through_the_scheduler():
    """The view is reported once a launch inside pack: 0.0 where every row
    was a hit, above 0 where the launch carried pods with node affinity; its
    sum stays under pack's; a re-bucketed mirror takes pack_full_s over."""
    from kubernetes_tpu.backend.mirror import Mirror
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.utils.tracing import (
        LOOP_VIEW_PHASES, UNCOUNTED_PHASES, VIEW_PHASES)

    assert "pack_full" in LOOP_VIEW_PHASES
    assert "pack_full" in VIEW_PHASES and "pack_full" in UNCOUNTED_PHASES
    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 8
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=128))
    try:
        hub.create_node(objects.make_node(
            objects.load_template("node-default"), 0, ["zone1"]))
        seen = []
        observe = sched.flight.observe_phase
        sched.flight.observe_phase = lambda p, s: (
            seen.append((p, s)), observe(p, s))[1]
        plain = objects.PodMaker(objects.load_template("pod-default"))
        for rnd in range(3):
            for i in range(8):
                hub.create_pod(plain.make(f"p-{rnd}-{i}"))
            sched.run_until_idle()
        views = [s for p, s in seen if p == "pack_full"]
        assert len(views) == sched.profiler.launches == 3
        assert views[0] > 0.0 and views[1:] == [0.0, 0.0]
        maker = objects.PodMaker(MEASURED)
        for i in range(8):
            hub.create_pod(maker.make(f"na-{i}"))
        sched.run_until_idle()
        views = [s for p, s in seen if p == "pack_full"]
        assert len(views) == 4 and views[-1] > 0.0
        assert sched.mirror.row_cache_stats()["bypass"] == 8
        sums = {k.split("'")[3]: rec["sum"] for k, rec in
                sched.metrics.phase_duration.snapshot().items()
                if k.count("'") >= 4}
        assert 0.0 < sums["pack_full"] <= sums["pack"]
        grown = Mirror(caps=Capacities(nodes=16, pods=256))
        grown.adopt_hysteresis(sched.mirror)
        assert grown.pack_full_s == sched.mirror.pack_full_s > 0.0
    finally:
        sched.close()


# ------------------------------------------------- the cell's checks and reader


def _end(shapes, template=MEASURED, names_features=True):
    """What node_affinity_scan looks at: the profiler's shapes (active
    features, launches), the mix's template."""
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.telemetry.profiler import shape_key

    caps = Capacities(nodes=8192, pods=262144)
    recs = {}
    for active, n in shapes:
        key = shape_key(caps, 4096, False, 0, 8, False, False, False, False,
                        active=active)
        if not names_features:
            key = tuple(kv for kv in key if kv[0] != "active")
        recs[key] = {"launches": n}
    return types.SimpleNamespace(
        sched=types.SimpleNamespace(
            profiler=types.SimpleNamespace(shapes=recs)),
        pod_template=template)


SCANS = {
    # profiler shapes (active features, launches) -> launches missing
    "the full node-affinity kernels": ([(("nodeaffinity",), 7)], 0),
    "beside taints, and another shape without": (
        [(("nodeaffinity", "taints"), 3), ((), 2)], 0),
    "the pin compare only": ([(("nodeaffinity_pin",), 7)], 1),
    "no feature at all": ([((), 7)], 1),
    "no launch": ([], 1),
}


@pytest.mark.parametrize("scan", sorted(SCANS))
def test_node_affinity_scan_reads_the_launch_features(scan):
    check = cell.compare_mod.load_by_name("checks", "node_affinity_scan").check
    shapes, missing = SCANS[scan]
    got = check(_end(shapes))
    assert got == {"node_affinity_terms_missing": False,
                   "node_affinity_launches_missing": bool(missing)}
    plain = check(_end(shapes, objects.load_template("pod-default")))
    assert plain["node_affinity_terms_missing"] is True


def test_node_affinity_scan_leaves_out_what_a_program_cannot_show(
        monkeypatch):
    """A program whose shape key names no launch features (before it did)
    is not held to node_affinity_launches_missing; it is not guessed."""
    from kubernetes_tpu.telemetry import profiler

    check = cell.compare_mod.load_by_name("checks", "node_affinity_scan").check
    end = _end([((), 7)], names_features=False)
    old = profiler.shape_key

    def shape_key(caps, b_bucket, enable_topology, d_cap, g_cap,
                  serial_scan, dra, learned, with_feats, gang=0,
                  alts=False, soft=False):
        return old(caps, b_bucket, enable_topology, d_cap, g_cap,
                   serial_scan, dra, learned, with_feats, gang, alts, soft)

    monkeypatch.setattr(profiler, "shape_key", shape_key)
    assert check(end) == {"node_affinity_terms_missing": False}


def test_required_node_affinity_judges_offered_and_init_pods():
    check = cell.compare_mod.load_by_name(
        "checks", "required_node_affinity").check

    def node(name, zone):
        return types.SimpleNamespace(metadata=types.SimpleNamespace(
            name=name, labels={ZONE: zone} if zone else {}))

    def pod(uid, name, node_name):
        return types.SimpleNamespace(
            metadata=types.SimpleNamespace(uid=uid, name=name),
            spec=types.SimpleNamespace(node_name=node_name))

    nodes = [node("node-0", "zone1"), node("node-1", "zone2"),
             node("stray", None)]
    bound = [pod("p-init-a-0", "init-a-0", "stray"),
             pod("p-init-a-1", "init-a-1", "node-0"),
             pod("p-m-a-0", "m-a-0", "stray"), pod("p-m-a-1", "m-a-1", "node-1"),
             pod("p-x", "x", "stray")]
    end = types.SimpleNamespace(nodes=nodes, bound=bound,
                                offered=["p-m-a-0", "p-m-a-1", "p-m-a-2"],
                                pod_template=MEASURED)
    assert check(end) == {"node_affinity_violated": 2}


@pytest.mark.parametrize("phase_s, want", [
    ({"pack_full": 1.5, "pack": 2.0}, 750.0),
    ({"pack_full": 0.0, "pack": 2.0}, 0.0),
    ({"pack": 2.0}, None),
])
def test_pack_full_reader(phase_s, want):
    obs = {"seconds": 30, "bound_in_window": 2000, "phase_s": phase_s,
           "launches": 4, "launch_cache_delta": 0, "compiles": [],
           "gc_pauses_ms": [], "trace": None}
    got = cell.load_reader("mirror.pack_full_ms_per_kpod.drain")(obs)
    assert got == (pytest.approx(want) if want is not None else None)
    assert cell.load_reader("mirror.pack_full_ms_per_kpod.drain")(
        dict(obs, bound_in_window=0)) is None
