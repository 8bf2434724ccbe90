"""Every cell of BENCHMARK.json end to end at its rehearsal size on the CPU,
in this process: the same path as a chip run, without the look for a chip.
One file, so that one worker compiles each tiny program once. Then the
benchmark copied with one addition of every kind a later PR makes: the toy
cells run, and every invariant of bench_invariants.py holds on the copy."""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_invariants as inv  # noqa: E402
from benchmark import cell  # noqa: E402

# read at collection: a cell that a later PR adds is rehearsed without that
# PR writing the test
CELLS = [w["name"] for w in cell.load_manifest(REPO)["workloads"]]
# numbers a cell is held to exactly when its configuration or its mix names
# the check that gives them
GIVEN_BY = {"skew_excess": "required_skew",
            "required_rules_missing": "serial_scan",
            "scan_launches_missing": "serial_scan",
            "soft_launches_missing": "soft_auction"}
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def _quiet(_msg):
    pass


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_correct_and_reports_its_end_to_end_metrics(
        workload, monkeypatch):
    manifest = cell.load_manifest(REPO)
    gave = {}       # check -> the numbers it returned, in the order called
    load = cell.compare_mod.load_by_name

    def recording(kind, name, *root):
        mod = load(kind, name, *root)
        if kind != "checks":
            return mod

        def check(end):
            assert name not in gave, f"check {name} ran twice"
            gave[name] = mod.check(end)
            return gave[name]
        return types.SimpleNamespace(check=check)

    monkeypatch.setattr(cell.compare_mod, "load_by_name", recording)
    r = cell.run_cell(workload, 3_000_000_017, 2, False, rehearse=True,
                      log=_quiet)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    want = {m["name"] + cell.NOT_DEVICE
            for m in cell.metrics_of(manifest, "end_to_end", workload)}
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"
    # exactly the contract's keys, `compared` last, every number by its limit
    assert list(r) == KEYS
    assert all(set(c) == {"value", "limit"} for c in r["compared"].values())
    assert {"unbound", "double_binds", "acknowledged_binds_missing",
            "overpacked_nodes", "left_device_path"} <= set(r["compared"])
    # the checks that ran are those the cell's configuration and its mix
    # name, each once; a number is compared exactly when the check that
    # gives it is named, and no check takes another's number
    w, entry = cell.find_cell(manifest, workload)
    cfg = cell.load_config(entry, True, REPO)
    mix = cell.traffic_mod.load_mix(w["traffic"], True)
    named = list(dict.fromkeys(cfg["checks"] + mix.get("checks", [])))
    assert list(gave) == named
    numbers = [n for got in gave.values() for n in got]
    assert len(numbers) == len(set(numbers)) and all(gave.values())
    assert set(r["compared"]) == set(numbers)
    for number, check in GIVEN_BY.items():
        assert (number in r["compared"]) == (check in named), number
    json.dumps(r)


@pytest.mark.parametrize("workload", ["basic-5k.backlog", "basic-5k.arrivals"])
def test_traced_run_reports_the_cells_layer_metrics_by_name(workload):
    manifest = cell.load_manifest(REPO)
    r = cell.run_cell(workload, 11, 2, True, rehearse=True, log=_quiet)
    allowed = {m["name"] + cell.NOT_DEVICE
               for m in cell.metrics_of(manifest, "per_layer", workload)}
    assert set(r["metrics"]) <= allowed
    suffix = ".arrive" if workload.endswith("arrivals") else ".drain"
    for name in ("queue.pop_ms_per_kpod", "loop.pods_per_launch",
                 "device.compiles_in_window"):
        assert name + suffix + cell.NOT_DEVICE in r["metrics"]
    # no device plane in a CPU trace: the device readers return nothing,
    # never 0, and the run is not correct without device time
    assert "device.program_ms_per_kpod" + suffix + cell.NOT_DEVICE \
        not in r["metrics"]
    assert r["compared"]["device_busy_missing"]["value"] == 1
    assert r["correct"] is False
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(r) == KEYS[:5] + ["breakdown", "compared"]
    if workload.endswith("arrivals"):
        assert "gen.late_p99_ms.arrive" + cell.NOT_DEVICE in r["metrics"]
        assert "loop.bind_p99_ms.arrive" + cell.NOT_DEVICE in r["metrics"]


def test_a_run_without_a_tpu_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "basic-5k.backlog",
         "--seed", "2147483900", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_rehearsal_command_prints_the_contracts_last_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "basic-5k.arrivals",
         "--seed", "2147483901", "--seconds", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line) == KEYS
    assert line["correct"] is True
    assert all(k.endswith(cell.NOT_DEVICE) for k in line["metrics"])
    assert {"bind_p50_ms" + cell.NOT_DEVICE, "setup_s" + cell.NOT_DEVICE} \
        <= set(line["metrics"])
    assert "pods_per_s" + cell.NOT_DEVICE not in line["metrics"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # each number compared, beside its limit, closes standard error
    tail = p.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and "(limit " in t for t in tail)
    assert sum(ln.startswith("[bench] diag ")
               for ln in p.stderr.splitlines()) == 1


def _toy_checkout(tmp_path):
    """The benchmark as it stands, copied, with one addition of every kind
    a later PR makes beside it: configurations (one with init groups on new
    templates), templates, mixes (one on a template no cell used), checks,
    a fault with its control's case, a per-layer metric appended with its
    reader, and four cells, one of them under every `.drain` metric.
    Returns (root, the copied files' bytes)."""
    root = tmp_path / "checkout"
    manifest, before = inv.copy_benchmark(root)

    def add(path, text):
        assert not (root / path).exists(), f"{path}: not a toy's name"
        (root / path).write_text(text)

    src = json.loads((root / "benchmark/configs/sched-perf-basic-5k.json")
                     .read_text())
    toy = dict(src, name="toy-2zone", source="a toy for the tests",
               **src["rehearse"])
    toy["nodes"] = dict(toy["nodes"], zones=["a", "b"], count=300)
    toy["rehearse"] = {}
    toy["checks"] = src["checks"] + ["toy_labels"]
    add("benchmark/checks/toy_labels.py",
        "def check(end):\n    return {'toy_label_missing': sum(\n"
        "        p.metadata.labels.get('toy') != 'yes' for p in end.bound\n"
        "        if p.metadata.uid in set(end.offered))}\n")
    add("benchmark/configs/toy-2zone.json", json.dumps(toy))
    add("benchmark/templates/pod-toy.json", json.dumps({
        "kind": "pod", "requests": {"cpu": "10m", "memory": "10Mi"},
        "labels": {"toy": "yes"}, "spread": []}))
    add("benchmark/traffic/toy-mix.json", json.dumps({
        "kind": "backlog", "pod_template": "pod-toy", "depth": 96,
        "slab": 32, "warm_pods_batches": 1, "warm_seconds": 0.2,
        "grace_seconds": 60.0, "checks": []}))
    add("benchmark/layer_metrics/toy.launches.py",
        "def read(obs):\n    return float(obs['launches']) or None\n")
    # upstream's other pod shapes: two init groups, the second and the
    # measured pods in a namespace of their own, with a priority and a
    # required affinity term over two namespaces
    add("benchmark/templates/pod-toy-ns.json", json.dumps({
        "kind": "pod", "requests": {"cpu": "10m", "memory": "10Mi"},
        "labels": {"toy": "yes"}, "namespace": "toy-ns", "priority": 5,
        "pod_affinity": {"required": [{
            "topology_key": "topology.kubernetes.io/zone",
            "match_labels": {"toy": "yes"},
            "namespaces": ["toy-ns", "toy-other"]}]}}))
    groups = dict(toy, name="toy-groups", init_pods=[
        {"count": 200, "template": "pod-toy"},
        {"count": 250, "template": "pod-toy-ns"}])
    groups["checks"] = toy["checks"] + ["toy_groups"]
    add("benchmark/configs/toy-groups.json", json.dumps(groups))
    add("benchmark/checks/toy_groups.py",
        "import collections\n"
        "def check(end):\n"
        "    offered = set(end.offered)\n"
        "    mine = [p for p in end.bound if p.metadata.uid in offered]\n"
        "    init = [p for p in end.bound\n"
        "            if p.metadata.name.startswith('init-')]\n"
        "    per_node = collections.Counter(p.spec.node_name for p in init)\n"
        "    spaces = {n.metadata.name for n in end.hub.list_namespaces()}\n"
        "    return {\n"
        "        'toy_namespaces_missing':\n"
        "            len({'toy-ns', 'toy-other'} - spaces),\n"
        "        'toy_init_pods_missing': abs(450 - len(init)) + abs(250 - sum(\n"
        "            p.metadata.namespace == 'toy-ns' for p in init)),\n"
        "        # one continued round: 450 pods over 300 nodes are two on\n"
        "        # 150 nodes and one on the rest, not three anywhere\n"
        "        'toy_round_restarted': sum(\n"
        "            n > 2 for n in per_node.values())\n"
        "            + abs(300 - len(per_node)),\n"
        "        'toy_pod_shape_lost': sum(\n"
        "            p.metadata.namespace != 'toy-ns' or p.spec.priority != 5\n"
        "            or not p.spec.affinity.pod_affinity.required\n"
        "            for p in mine) + (not mine)}\n")
    add("benchmark/traffic/toy-ns-mix.json", json.dumps({
        "kind": "backlog", "pod_template": "pod-toy-ns", "depth": 96,
        "slab": 32, "warm_pods_batches": 1, "warm_seconds": 0.2,
        "grace_seconds": 60.0, "checks": []}))
    # an arrivals mix judged on the pods it completes: steady, no bursts
    add("benchmark/traffic/toy-steady.json", json.dumps({
        "kind": "arrivals", "pod_template": "pod-toy", "base_rate": 100,
        "group_ms": 100, "burst_pods": 0, "burst_period_s": 1.0,
        "warm_periods": 1, "prewarm_pods": 8, "grace_seconds": 60.0,
        "checks": []}))
    # the next deployment's shape, SchedulingPodAffinity: one zone, init
    # groups (a list) on a new `-init` sibling in sched-0, the measured pods
    # of a template that no cell used, and a check of its own
    measured = json.loads(
        (root / "benchmark/templates/pod-with-pod-affinity.json").read_text())
    add("benchmark/templates/pod-toy-affinity-init.json",
        json.dumps(dict(measured, namespace="sched-0")))
    affinity = dict(toy, name="toy-affinity", init_pods=[
        {"count": 120, "template": "pod-toy-affinity-init"}])
    affinity["nodes"] = dict(toy["nodes"], zones=["zone1"])
    affinity["checks"] = src["checks"] + ["toy_affinity"]
    add("benchmark/configs/toy-affinity.json", json.dumps(affinity))
    add("benchmark/checks/toy_affinity.py",
        "def check(end):\n"
        "    offered = set(end.offered)\n"
        "    mine = [p for p in end.bound if p.metadata.uid in offered]\n"
        "    return {'toy_affinity_lost': sum(\n"
        "        p.metadata.namespace != 'sched-1'\n"
        "        or not p.spec.affinity.pod_affinity.required\n"
        "        for p in mine) + (not mine)}\n")
    add("benchmark/traffic/toy-affinity-mix.json", json.dumps({
        "kind": "backlog", "pod_template": "pod-with-pod-affinity",
        "depth": 96, "slab": 32, "warm_pods_batches": 1, "warm_seconds": 0.2,
        "grace_seconds": 60.0, "checks": []}))
    # a fault as a new file, its control's case as a new file
    add("benchmark/faults/toy_unlabelled.py",
        "def pod_template(mix):\n    return 'pod-default'\n")
    add(os.path.join(inv.CONTROLS, "toy.json"), json.dumps([
        {"cell": "toy.cell", "fault": "toy_unlabelled",
         "caught_by": "toy_label_missing"}]))
    for name in ("toy-2zone", "toy-groups", "toy-affinity"):
        manifest["configs"].append({
            "name": name, "source": "a toy for the tests",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "toy"})
    manifest["workloads"] += [
        {"name": "toy.cell", "config": "toy-2zone", "traffic": "toy-mix",
         "chips": 1, "why": "toy"},
        {"name": "toy.groups", "config": "toy-groups",
         "traffic": "toy-ns-mix", "chips": 1, "why": "toy"},
        {"name": "toy.steady", "config": "toy-2zone",
         "traffic": "toy-steady", "chips": 1, "why": "toy"},
        {"name": "toy.affinity", "config": "toy-affinity",
         "traffic": "toy-affinity-mix", "chips": 1, "why": "toy"}]
    toys = [w["name"] for w in manifest["workloads"]
            if w["name"].startswith("toy.")]
    for m in manifest["end_to_end"]:
        if m["name"] == "pods_per_s":
            m["workloads"] += toys
    for m in manifest["per_layer"]:     # every `.drain` reader, PR 25's too
        if m["name"].endswith(".drain"):
            m["workloads"].append("toy.affinity")
    manifest["per_layer"].append({
        "name": "toy.launches", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "scheduling loop",
        "moves": "pods_per_s", "workloads": toys})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root, before


def _run(root, script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / script), *args,
         "--seed", "5", "--seconds", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def _rehearse(root, workload, trace):
    p = _run(root, "run.py", "--workload", workload, "--trace", trace)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["toy.cell", "toy.groups", "toy.steady",
                                      "toy.affinity"])
def test_a_toy_config_mix_cell_check_and_layer_metric_are_new_files_only(
        tmp_path, workload):
    """A later PR adds entries and files and edits none: copy the benchmark
    as it stands, add a toy of each kind beside it, run the toy cells. One
    is a plain backlog, with a fault of its own planted from its case file;
    one has two init groups, namespaces, a priority and a required affinity
    term; one is an arrivals mix listed under `pods_per_s`; one has the next
    deployment's shape and reports every `.drain` metric."""
    root, before = _toy_checkout(tmp_path)
    line = _rehearse(root, workload, "0")
    assert line["compared"]["unbound"]["value"] == 0, line
    own = "toy_affinity_lost" if workload == "toy.affinity" \
        else "toy_label_missing"
    assert line["compared"][own] == {"value": 0, "limit": 0}
    assert line["correct"] is True, line["compared"]
    assert line["metrics"]["pods_per_s" + cell.NOT_DEVICE]["value"] > 0
    if workload == "toy.groups":
        assert {"toy_namespaces_missing", "toy_init_pods_missing",
                "toy_round_restarted", "toy_pod_shape_lost"} \
            <= set(line["compared"])
    elif workload == "toy.steady":
        # 100 pods/s offered for 1 s, all bound inside the window or just
        # past it: the rate is of the pods due in the window, no others
        assert 0 < line["metrics"]["pods_per_s" + cell.NOT_DEVICE]["value"] \
            <= 100.0
        assert line["attempted"] == 200        # one warm period, the window
    elif workload == "toy.affinity":
        traced = _rehearse(root, workload, "1")
        for name in ("toy.launches", "queue.done_ms_per_kpod.drain",
                     "mirror.snapshot_cache_ms_per_kpod.drain",
                     "mirror.sync_ms_per_kpod.drain"):
            assert traced["metrics"][name + cell.NOT_DEVICE]["value"] >= 0
        assert traced["metrics"]["toy.launches" + cell.NOT_DEVICE][
            "value"] > 0
    else:
        (case,) = [c for c in inv.load_controls(root)
                   if c["cell"] == workload]
        p = _run(root, "control.py", "--workload", workload, "--fault",
                 case["fault"])
        assert p.returncode == 0, p.stderr[-3000:]
        verdict = json.loads(p.stdout.strip().splitlines()[-1])
        assert verdict["correct"] is False
        assert verdict["failed_by"][case["caught_by"]] > 0
    assert inv.unchanged(before)


@pytest.fixture(scope="module")
def toy_checkout(tmp_path_factory):
    return _toy_checkout(tmp_path_factory.mktemp("invariants"))


@pytest.mark.parametrize("invariant", inv.INVARIANTS,
                         ids=lambda f: f.__name__)
def test_every_invariant_holds_with_one_addition_of_every_kind(
        toy_checkout, invariant):
    """What the tests hold of the repo, they hold of the copy with the
    additions too: a test that pins today's inventory fails here, in the PR
    that writes it, not in the PR that brings the next deployment."""
    root, before = toy_checkout
    manifest = cell.load_manifest(root)
    added = {w["name"] for w in manifest["workloads"]} - set(CELLS)
    assert added == {"toy.cell", "toy.groups", "toy.steady", "toy.affinity"}
    invariant(manifest, root)
    assert inv.unchanged(before)


def test_arrivals_cell_reports_the_metric_names_it_reported():
    """`pods_per_s` is computed for an arrivals mix too; which cells print
    it stays what the metric's `workloads` list says."""
    manifest = cell.load_manifest(REPO)
    names = [m["name"] for m in cell.metrics_of(
        manifest, "end_to_end", "basic-5k.arrivals")]
    assert names[:2] == ["bind_p50_ms", "setup_s"]
    assert "pods_per_s" not in names
