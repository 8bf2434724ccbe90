"""Required inter-pod anti-affinity against its plain reference. The cell
`anti-affinity-5k.required` ends with free nodes, so a pod always finds one;
here the term decides more: benchmark/reference_anti_affinity.py alone on
placements made by hand, each wrong in one way; then the production
Scheduler over an in-process Hub on small clusters drawn from a seed
(hostname and zone keys, some nodes without either, three namespaces, pods
whose terms select themselves, another colour, or nothing), its end state
held to the reference: no violation, and no pod left pending that the
reference finds a node for (exact, because a node only ever becomes
forbidden). Last the cell's own check and reader on hand-made inputs."""

import copy
import os
import random
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cell, compare, objects  # noqa: E402
from benchmark import reference_anti_affinity as ref  # noqa: E402

HOST, ZONE = objects.HOST_KEY, objects.ZONE_KEY
MEASURED = objects.load_template("pod-with-pod-anti-affinity")
INIT = objects.load_template("pod-with-pod-anti-affinity-init")
TERMS = ref.required_anti_terms(MEASURED)
SEEDS = (3_000_000_019, 2_147_483_659, 3_400_000_007)
GREEN = {"color": "green"}


def test_reference_imports_nothing_of_the_program_and_reads_the_template():
    path = os.path.join(REPO, "benchmark", "reference_anti_affinity.py")
    with open(path) as f:
        lines = [ln.split() for ln in f.read().splitlines()]
    imported = [ln[1] for ln in lines if ln[:1] in (["import"], ["from"])]
    assert imported == ["__future__"]
    assert TERMS == [{"topology_key": HOST, "match_labels": GREEN,
                      "namespaces": ["sched-1", "sched-0"]}]
    assert ref.required_anti_terms(INIT) == TERMS
    assert INIT["namespace"] == "sched-0" and MEASURED["namespace"] == "sched-1"
    assert {k: v for k, v in INIT.items() if k not in ("namespace", "source")} \
        == {k: v for k, v in MEASURED.items()
            if k not in ("namespace", "source")}
    assert ref.required_anti_terms(objects.load_template("pod-default")) == []
    # the affinity sibling's terms are not anti-affinity terms
    assert ref.required_anti_terms(
        objects.load_template("pod-with-pod-affinity")) == []


# ------------------------------------------------- the reference alone

NODE_LABELS = {"a0": {HOST: "a0", ZONE: "z-a"}, "a1": {HOST: "a1", ZONE: "z-a"},
               "b0": {HOST: "b0", ZONE: "z-b"}, "bare": {}}

PLACED = {
    # bound pods (uid, node, namespace, labels), the judged uids, the count
    "every green pod on a node of its own": (
        [("i0", "a0", "sched-0", GREEN), ("m0", "a1", "sched-1", GREEN),
         ("m1", "b0", "sched-1", GREEN)], ["i0", "m0", "m1"], 0),
    "two selected pods on one node count both": (
        [("m0", "a0", "sched-1", GREEN), ("m1", "a0", "sched-1", GREEN),
         ("m2", "b0", "sched-1", GREEN)], ["m0", "m1", "m2"], 2),
    "a measured pod beside an init pod: the init pod is counted too": (
        [("i0", "a0", "sched-0", GREEN), ("m0", "a0", "sched-1", GREEN)],
        ["i0", "m0"], 2),
    "the same pair with the measured pod alone judged": (
        [("i0", "a0", "sched-0", GREEN), ("m0", "a0", "sched-1", GREEN)],
        ["m0"], 1),
    "three on one node count three": (
        [("m0", "b0", "sched-1", GREEN), ("m1", "b0", "sched-0", GREEN),
         ("m2", "b0", "sched-1", GREEN)], ["m0", "m1", "m2"], 3),
    "the other pod is in a namespace the term does not list": (
        [("x", "a0", "elsewhere", GREEN), ("m0", "a0", "sched-1", GREEN)],
        ["m0"], 0),
    "the other pod's label does not match": (
        [("x", "a0", "sched-0", {"color": "red"}),
         ("m0", "a0", "sched-1", GREEN)], ["m0"], 0),
    "a node without the key has no domain": (
        [("m0", "bare", "sched-1", GREEN), ("m1", "bare", "sched-1", GREEN)],
        ["m0", "m1"], 0),
    "a node that is not in the cluster has none either": (
        [("m0", "gone", "sched-1", GREEN), ("m1", "gone", "sched-1", GREEN)],
        ["m0", "m1"], 0),
    "a judged pod that is not bound is not judged": (
        [("m0", "a0", "sched-1", GREEN)], ["m0", "pending"], 0),
    "a pod alone never violates against itself": (
        [("m0", "a0", "sched-1", GREEN)], ["m0", "m0"], 0),
}


@pytest.mark.parametrize("placed", sorted(PLACED))
def test_reference_counts_the_pods_that_share_a_domain_with_a_selected_pod(
        placed):
    pods, judged, bad = PLACED[placed]
    assert ref.anti_affinity_violated(TERMS, NODE_LABELS, pods, judged) == bad


def test_reference_reads_the_terms_key_namespaces_and_both_directions():
    zone = [dict(TERMS[0], topology_key=ZONE)]
    pods = [("m0", "a0", "sched-1", GREEN), ("m1", "a1", "sched-1", GREEN),
            ("m2", "b0", "sched-1", GREEN)]
    # one zone key: a0 and a1 are one domain, b0 another
    assert ref.anti_affinity_violated(zone, NODE_LABELS, pods,
                                      ["m0", "m1", "m2"]) == 2
    assert ref.anti_affinity_violated([], NODE_LABELS, pods, ["m0"]) == 0
    # a term without namespaces means the carrier's own
    own = [dict(TERMS[0], namespaces=[])]
    mixed = [("i0", "a0", "sched-0", GREEN), ("m0", "a0", "sched-1", GREEN)]
    assert ref.anti_affinity_violated(own, NODE_LABELS, mixed,
                                      ["i0", "m0"]) == 0
    assert ref.anti_affinity_violated(
        own, NODE_LABELS, mixed + [("m1", "a0", "sched-1", GREEN)],
        ["i0", "m0", "m1"]) == 2
    # a carrier its own term does not select (blue, against green): the
    # pair is one breach, found from the carrier's side
    blue = [("b", "a0", "sched-1", {"color": "blue"}),
            ("g", "a0", "sched-1", GREEN)]
    assert ref.anti_affinity_violated(TERMS, NODE_LABELS, blue, ["b"]) == 1
    assert ref.selected_by(TERMS, "sched-1", blue) == ["g"]
    assert ref.selected_by(own, "elsewhere", blue) == []


def test_feasible_nodes_are_those_neither_direction_forbids():
    pods = [("i0", "a0", "sched-0", GREEN),
            ("x", "a1", "elsewhere", GREEN),
            ("b", "b0", "sched-1", {"color": "blue"})]
    every = set(NODE_LABELS)
    # the incoming pod's own term: a0 holds a green pod of a listed space
    assert ref.anti_affinity_feasible_nodes(
        TERMS, NODE_LABELS, pods, {}, "sched-1", GREEN) == every - {"a0"}
    # a bound pod's term against the incoming pod: blue on b0 carries it
    assert ref.anti_affinity_feasible_nodes(
        TERMS, NODE_LABELS, pods, {"b": TERMS}, "sched-1", GREEN) \
        == every - {"a0", "b0"}
    # a plain incoming pod is forbidden only where a bound term selects it
    assert ref.anti_affinity_feasible_nodes(
        [], NODE_LABELS, pods, {"i0": TERMS, "b": TERMS}, "sched-0", GREEN) \
        == every - {"a0", "b0"}
    assert ref.anti_affinity_feasible_nodes(
        [], NODE_LABELS, pods, {"i0": TERMS}, "elsewhere", GREEN) == every
    # a zone term forbids the whole zone, and never the node without a zone
    zone = [dict(TERMS[0], topology_key=ZONE)]
    assert ref.anti_affinity_feasible_nodes(
        zone, NODE_LABELS, pods, {}, "sched-1", GREEN) == {"b0", "bare"}


# ------------------------------------------------- the production scheduler


def _kind(name, namespace, color, terms):
    """A pod template made by hand: `terms` = [(topology key, colour it
    selects, namespaces)]."""
    tmpl = {"kind": "pod", "source": f"tests: {name}", "container": "pause",
            "requests": {"cpu": "100m", "memory": "500Mi"},
            "namespace": namespace, "labels": {"color": color}}
    if terms:
        tmpl["pod_anti_affinity"] = {"required": [
            {"topology_key": key, "match_labels": {"color": sel},
             "namespaces": list(spaces)} for key, sel, spaces in terms]}
    return tmpl


KINDS = {
    "green": MEASURED,                   # upstream's: hostname, vs green
    "green-init": INIT,
    # selects its own colour in its own namespace, a zone each
    "red-zone": _kind("red-zone", "sched-1", "red", [(ZONE, "red", [])]),
    # carries a term that does not select itself: keeps off green's nodes,
    # and by the other direction keeps sched-1's green pods off its own
    "blue-vs-green": _kind("blue-vs-green", "elsewhere", "blue",
                           [(HOST, "green", ["sched-1"])]),
    # two terms: a node of its own among green pods, a zone free of red
    "green-two-terms": _kind(
        "green-two-terms", "sched-0", "green",
        [(HOST, "green", ["sched-1", "sched-0"]),
         (ZONE, "red", ["sched-1"])]),
    # green in a namespace no term lists, and no term: goes anywhere
    "plain-green": _kind("plain-green", "elsewhere", "green", []),
}


def _cluster(rng, n_nodes, no_host, no_zone):
    """`n_nodes` nodes over three zones, `no_zone` of them drawn from the
    seed without the zone label and `no_host` without the hostname label
    (such a node has no domain of that key: it takes any number of pods)."""
    tmpl = objects.load_template("node-default")
    zoneless = set(rng.sample(range(n_nodes), no_zone))
    nodes = [objects.make_node(
        tmpl, i, [] if i in zoneless else ["z-a", "z-b", "z-c"])
        for i in range(n_nodes)]
    for i in rng.sample(range(n_nodes), no_host):
        del nodes[i].metadata.labels[HOST]
    return nodes


def _run(seed, shape, existing, offered):
    """The production Scheduler over an in-process Hub on a cluster of
    `shape` = (nodes, of them without hostname, without zone): `existing` pods
    (kind names) are created already bound, each on a node the reference
    finds feasible, drawn from the seed (one that has none is left out);
    `offered` pods (kind names) go through Scheduler.run_until_idle.
    Returns (node labels, bound pods as the reference takes them, {uid:
    kind} of every pod created, the pending uids in offer order)."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    rng = random.Random(seed)
    nodes = _cluster(rng, *shape)
    labels = {n.metadata.name: dict(n.metadata.labels) for n in nodes}
    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 16
    cfg.tie_break_seed = seed & 0xffffffff
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=256))
    makers = {k: objects.PodMaker(t) for k, t in KINDS.items()}
    kind_of, placed, carried = {}, [], {}
    try:
        for n in rng.sample(nodes, len(nodes)):
            hub.create_node(n)
        for ns in ("sched-0", "sched-1", "elsewhere"):
            hub.create_namespace(_namespace(ns))
        for i, kind in enumerate(existing):
            tmpl = KINDS[kind]
            terms = ref.required_anti_terms(tmpl)
            free = sorted(ref.anti_affinity_feasible_nodes(
                terms, labels, placed, carried, tmpl["namespace"],
                tmpl["labels"]))
            if not free:
                continue
            pod = makers[kind].make(f"init-{i}", node_name=rng.choice(free))
            hub.create_pod(pod)
            kind_of[pod.metadata.uid] = kind
            carried[pod.metadata.uid] = terms
            placed.append((pod.metadata.uid, pod.spec.node_name,
                           tmpl["namespace"], tmpl["labels"]))
        order = []
        for i, kind in enumerate(offered):
            pod = makers[kind].make(f"m-{seed}-{i}")
            kind_of[pod.metadata.uid] = kind
            order.append(pod.metadata.uid)
            hub.create_pod(pod)
        sched.run_until_idle()
        bound = [(p.metadata.uid, p.spec.node_name, p.metadata.namespace,
                  p.metadata.labels) for p in hub.list_pods()
                 if p.spec.node_name]
        assert sched.stats["device_fallbacks"] == 0
    finally:
        sched.close()
    is_bound = {uid for uid, *_rest in bound}
    return labels, bound, kind_of, [u for u in order if u not in is_bound]


def _held_to_the_reference(labels, bound, kind_of, pending):
    """No violation among the bound pods, every carrier judged by its own
    kind's terms; no pending pod that the reference finds a node for."""
    for kind, tmpl in KINDS.items():
        judged = [u for u, k in kind_of.items() if k == kind]
        assert ref.anti_affinity_violated(
            ref.required_anti_terms(tmpl), labels, bound, judged) == 0, kind
    carried = {u: ref.required_anti_terms(KINDS[k])
               for u, k in kind_of.items()}
    for uid in pending:
        tmpl = KINDS[kind_of[uid]]
        assert ref.anti_affinity_feasible_nodes(
            ref.required_anti_terms(tmpl), labels, bound, carried,
            tmpl["namespace"], tmpl["labels"]) == set(), (uid, kind_of[uid])


MIXES = {
    # name: ((nodes, of them without hostname, without zone), weights of
    # the kinds among the existing pods, count, weights among the offered
    # pods, count)
    "upstream's pod alone, more pods than nodes": (
        (12, 0, 2), {"green-init": 1}, 4, {"green": 1}, 20),
    "upstream's pod with a node that has no hostname": (
        (12, 1, 0), {"green-init": 1}, 4, {"green": 1}, 20),
    "zone terms fill three zones and the rest wait": (
        (12, 1, 0), {"plain-green": 1}, 3,
        {"red-zone": 1, "plain-green": 2}, 18),
    "zone terms with nodes that have no zone": (
        (12, 0, 2), {"red-zone": 1}, 2, {"red-zone": 1}, 12),
    "a term that selects another colour, both directions": (
        (8, 0, 1), {"green-init": 1, "blue-vs-green": 1}, 4,
        {"green": 2, "blue-vs-green": 2, "plain-green": 1}, 24),
    "every kind at once": (
        (14, 0, 0), {k: 1 for k in KINDS}, 6, {k: 1 for k in KINDS}, 36),
    "every kind at once, with nodes that lack a key": (
        (14, 2, 2), {k: 1 for k in KINDS}, 6, {k: 1 for k in KINDS}, 36),
}


def _draw(rng, weights, count):
    kinds = sorted(weights)
    return rng.choices(kinds, [weights[k] for k in kinds], k=count)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_scheduler_breaks_no_term_and_leaves_pending_only_what_has_no_node(
        mix, seed):
    shape, was, n_was, offer, n_offer = MIXES[mix]
    rng = random.Random(seed ^ 0x5eed)
    labels, bound, kind_of, pending = _run(
        seed, shape, _draw(rng, was, n_was), _draw(rng, offer, n_offer))
    _held_to_the_reference(labels, bound, kind_of, pending)
    assert len(bound) + len(pending) == len(kind_of)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("no_host", [0, 1])
def test_more_green_pods_than_nodes_one_a_node_and_the_rest_pending(
        no_host, seed):
    """Where the term decides: twelve nodes, three green init pods, twenty
    measured green pods. Every node with the hostname key ends with exactly
    one green pod and the eleven pods that are left stay pending, with no
    node left for them; a node without the key has no domain and takes
    them all."""
    labels, bound, kind_of, pending = _run(
        seed, (12, no_host, 0), ["green-init"] * 3, ["green"] * 20)
    _held_to_the_reference(labels, bound, kind_of, pending)
    keyed = {n for n, lab in labels.items() if HOST in lab}
    assert len(keyed) == 12 - no_host
    per_node = {}
    for _uid, node, _ns, _labels in bound:
        per_node[node] = per_node.get(node, 0) + 1
    assert {n: per_node.get(n, 0) for n in keyed} == {n: 1 for n in keyed}
    if no_host:
        assert pending == [] and len(bound) == 23
    else:
        assert len(bound) == 12 and len(pending) == 11


def test_debug_trace_tells_the_hostname_scan_from_the_zone_scan():
    """/debug/trace names each launch shape's d_cap, serial and soft and
    gives its pods and fill: green pods launch at a domain bucket as wide
    as the nodes, zone-spreading pods at the smallest."""
    import json
    import urllib.request

    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.serving import ServingEndpoints, token_auth

    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=256))
    srv = None
    try:
        for i in range(12):
            hub.create_node(objects.make_node(
                objects.load_template("node-default"), i, ["z-a", "z-b"]))
        spread = objects.PodMaker(
            objects.load_template("pod-spread-required"))
        for i in range(6):
            hub.create_pod(spread.make(f"s-{i}"))
        sched.run_until_idle()
        for kind in ("green-init", "green"):
            hub.create_namespace(_namespace(KINDS[kind]["namespace"]))
        green = objects.PodMaker(MEASURED)
        for i in range(5):
            hub.create_pod(green.make(f"g-{i}"))
        sched.run_until_idle()
        srv = ServingEndpoints(sched, port=0, debug_auth=token_auth("t"))
        srv.start()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/debug/trace")
        req.add_header("Authorization", "Bearer t")
        shapes = json.loads(urllib.request.urlopen(
            req, timeout=10.0).read())["device"]["shapes"]
    finally:
        if srv is not None:
            srv.stop()
        sched.close()
        hub.close()
    by_label = {s["shape"]: s for s in shapes}
    zone = next(s for lab, s in by_label.items()
                if "topo=1 d_cap=8 serial=1 soft=0" in lab)
    host = next(s for lab, s in by_label.items()
                if "topo=1 d_cap=16 serial=1 soft=0" in lab)
    assert (zone["pods"], host["pods"]) == (6, 5)
    for s in (zone, host):
        assert s["fill"] == round(s["pods"] / (s["launches"] * 16), 4)
        assert 0.0 < s["fill"] < 1.0


def _namespace(name):
    from kubernetes_tpu.api.objects import Namespace, ObjectMeta

    return Namespace(metadata=ObjectMeta(name=name, uid=f"ns-{name}"))


# ------------------------------------------------- the cell's checks


def _end(shapes, template=MEASURED, n_nodes=5000):
    """What anti_affinity_scan looks at: the profiler's shapes, the mix's
    template, the nodes."""
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.telemetry.profiler import shape_key

    caps = Capacities(nodes=8192, pods=131072)
    recs = {shape_key(caps, 1024, topo, d_cap, 2, serial, False, False,
                      False, soft=soft): {"launches": n}
            for topo, d_cap, serial, soft, n in shapes}
    return types.SimpleNamespace(
        sched=types.SimpleNamespace(
            profiler=types.SimpleNamespace(shapes=recs)),
        pod_template=template, nodes=[None] * n_nodes)


SCANS = {
    # DeviceProfiler shapes (topo, d_cap, serial, soft, launches) -> missing
    "the hostname-wide hard scan": ([(True, 8192, True, False, 7)], 0),
    "a zone scan only: the domain maps are 8 wide": (
        [(True, 8, True, False, 7)], 1),
    "a soft-only scan, as a batch of plain pods makes": (
        [(True, 8192, True, True, 7)], 1),
    "an auction launch": ([(True, 8192, False, False, 7)], 1),
    "no topology at all": ([(False, 0, True, False, 7)], 1),
    "a domain bucket under the node count": (
        [(True, 4096, True, False, 7)], 1),
    "both kinds of launch: one hostname scan is enough": (
        [(True, 8, True, False, 3), (True, 8192, True, False, 1)], 0),
}


@pytest.mark.parametrize("scan", sorted(SCANS))
def test_anti_affinity_scan_wants_a_hard_serial_launch_as_wide_as_the_nodes(
        scan):
    shapes, missing = SCANS[scan]
    check = compare.load_by_name("checks", "anti_affinity_scan").check
    assert check(_end(shapes)) == {
        "anti_affinity_terms_missing": 0,
        "hostname_scan_launches_missing": missing}
    plain = objects.load_template("pod-default")
    assert check(_end(shapes, plain))["anti_affinity_terms_missing"] == 1


def test_required_anti_affinity_check_counts_init_and_pre_warm_pods_too():
    """The check judges the offered pods and every other bound pod the
    template's terms select: a pre-warm pod on an init pod's node is two."""
    from kubernetes_tpu.hub import Hub

    hub = Hub()
    for i in range(3):
        hub.create_node(objects.make_node(
            objects.load_template("node-default"), i, ["zone1"]))
    names = [n.metadata.name for n in hub.list_nodes()]
    init, measured = objects.PodMaker(INIT), objects.PodMaker(MEASURED)
    hub.create_pod(init.make("init-0", node_name=names[0]))
    offered = measured.make("m-0", node_name=names[1])
    hub.create_pod(offered)
    end = compare.EndState(hub, None, MEASURED, [offered.metadata.uid], None,
                           "cpu")
    check = compare.load_by_name("checks", "required_anti_affinity").check
    assert check(end) == {"anti_affinity_violated": 0}
    hub.create_pod(measured.make("w-0", node_name=names[0]))   # pre-warm
    end = compare.EndState(hub, None, MEASURED, [offered.metadata.uid], None,
                           "cpu")
    assert check(end) == {"anti_affinity_violated": 2}
    # a plain pod beside a green one is no breach of anybody's term
    plain = objects.PodMaker(objects.load_template("pod-default"))
    hub.create_pod(plain.make("plain-0", node_name=names[1]))
    end = compare.EndState(hub, None, MEASURED, [offered.metadata.uid], None,
                           "cpu")
    assert check(end) == {"anti_affinity_violated": 2}
    hub.close()


# ------------------------------------------------- the new reader


def test_device_wait_share_is_the_device_launch_phase_over_the_window():
    read = cell.load_reader("loop.device_wait_share.arrive")
    obs = {"seconds": 30, "phase_s": {"device_launch": 24.0,
                                      "idle_wait": 0.3}}
    assert read(obs) == pytest.approx(0.8)
    assert read(dict(obs, phase_s={"device_launch": 0.0})) == 0.0
    # a program without the phase gives nothing, never 0 for lack of data
    assert read(dict(obs, phase_s={"idle_wait": 29.0})) is None
    assert read(dict(obs, seconds=0)) is None
    entry = next(m for m in cell.load_manifest(REPO)["per_layer"]
                 if m["name"] == "loop.device_wait_share.arrive")
    assert entry == {"name": "loop.device_wait_share.arrive", "unit": "share",
                     "better": "lower", "source": "program_span",
                     "layer": "scheduling loop", "moves": "bind_p50_ms",
                     "workloads": ["anti-affinity-5k.required"]}


def test_the_cells_data_files_say_what_the_issue_fixed():
    """The numbers ISSUE 34 fixes, read from the files a run reads."""
    manifest = cell.load_manifest(REPO)
    w, entry = cell.find_cell(manifest, "anti-affinity-5k.required")
    assert (w["config"], w["traffic"], w["chips"]) == (
        "sched-perf-anti-affinity-5k", "anti-affinity-required", 1)
    assert [m["name"] for m in cell.metrics_of(
        manifest, "end_to_end", w["name"])] == ["bind_p50_ms", "setup_s"]
    cfg = cell.load_config(entry, False, REPO)
    assert cfg["reduced"] == [] and cfg["nodes"]["count"] == 5000
    assert cfg["init_pods"] == {"count": 1000,
                                "template": "pod-with-pod-anti-affinity-init"}
    assert cfg["scheduler"]["batch_size"] == 1024
    assert cfg["capacities"] == {"nodes": 8192, "pods": 131072}
    assert cfg["checks"] == ["bound_exactly_once", "node_allocatable",
                             "required_anti_affinity", "device_path"]
    mix = cell.traffic_mod.load_mix(w["traffic"])
    assert (mix["kind"], mix["base_rate"], mix["group_ms"],
            mix["burst_pods"], mix["prewarm_pods"], mix["trace_slice_s"]) \
        == ("arrivals", 50, 100, 0, 110, 1.0)
    assert mix["warm_periods"] >= 4
    schedule = cell.traffic_mod.arrival_schedule(mix, 30)
    assert {n for _o, n in schedule} == {5}
    due = sum(n for o, n in schedule if o >= 0)
    warm = sum(n for o, n in schedule if o < 0)
    assert (due, warm) == (1500, 100 * mix["warm_periods"])
    # every green pod of a run finds a node of its own
    assert cfg["init_pods"]["count"] + mix["prewarm_pods"] + warm + due \
        < cfg["nodes"]["count"]
    small = cell.load_config(entry, True, REPO)
    small_mix = cell.traffic_mod.load_mix(w["traffic"], True)
    offered = sum(n for _o, n in cell.traffic_mod.arrival_schedule(
        small_mix, 3))
    assert small["init_pods"]["count"] + small_mix["prewarm_pods"] + offered \
        < small["nodes"]["count"]
    assert copy.deepcopy(mix)["checks"] == ["required_anti_affinity",
                                            "anti_affinity_scan"]
