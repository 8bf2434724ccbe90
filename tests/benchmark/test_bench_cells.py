"""Every cell end to end at its rehearsal size on the CPU, in this process:
the same path as a chip run, without the look for a chip. One file, so that
one worker compiles each tiny program once."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cell  # noqa: E402

CELLS = ["basic-5k.backlog", "basic-5k.arrivals", "topology-5k.required",
         "topology-5k.preferred"]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def _quiet(_msg):
    pass


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_correct_and_reports_its_end_to_end_metrics(workload):
    manifest = cell.load_manifest(REPO)
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
    # the required cell is held to its skew and its scan launches, the
    # preferred cell to its soft launches, and neither to the other's
    extra = set(r["compared"]) & {"skew_excess", "required_rules_missing",
                                  "scan_launches_missing",
                                  "soft_launches_missing"}
    assert extra == {
        "topology-5k.required": {"skew_excess", "required_rules_missing",
                                 "scan_launches_missing"},
        "topology-5k.preferred": {"skew_excess", "soft_launches_missing"},
    }.get(workload, set())
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
    """The benchmark as it stands, copied, with a toy of each kind beside
    it: a configuration, templates, mixes, a check, a per-layer metric and
    three cells. Returns (root, the copied files' bytes)."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    manifest = cell.load_manifest(REPO)
    src = json.loads((root / "benchmark/configs/sched-perf-basic-5k.json")
                     .read_text())
    toy = dict(src, name="toy-2zone", source="a toy for the tests",
               **src["rehearse"])
    toy["nodes"] = dict(toy["nodes"], zones=["a", "b"], count=300)
    toy["rehearse"] = {}
    toy["checks"] = src["checks"] + ["toy_labels"]
    (root / "benchmark/checks/toy_labels.py").write_text(
        "def check(end):\n    return {'toy_label_missing': sum(\n"
        "        p.metadata.labels.get('toy') != 'yes' for p in end.bound\n"
        "        if p.metadata.uid in set(end.offered))}\n")
    (root / "benchmark/configs/toy-2zone.json").write_text(json.dumps(toy))
    (root / "benchmark/templates/pod-toy.json").write_text(json.dumps({
        "kind": "pod", "requests": {"cpu": "10m", "memory": "10Mi"},
        "labels": {"toy": "yes"}, "spread": []}))
    (root / "benchmark/traffic/toy-mix.json").write_text(json.dumps({
        "kind": "backlog", "pod_template": "pod-toy", "depth": 96,
        "slab": 32, "warm_pods_batches": 1, "warm_seconds": 0.2,
        "grace_seconds": 60.0, "checks": []}))
    (root / "benchmark/layer_metrics/toy.launches.py").write_text(
        "def read(obs):\n    return float(obs['launches']) or None\n")
    # upstream's other pod shapes: two init groups, the second and the
    # measured pods in a namespace of their own, with a priority and a
    # required affinity term over two namespaces
    (root / "benchmark/templates/pod-toy-ns.json").write_text(json.dumps({
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
    (root / "benchmark/configs/toy-groups.json").write_text(
        json.dumps(groups))
    (root / "benchmark/checks/toy_groups.py").write_text(
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
    (root / "benchmark/traffic/toy-ns-mix.json").write_text(json.dumps({
        "kind": "backlog", "pod_template": "pod-toy-ns", "depth": 96,
        "slab": 32, "warm_pods_batches": 1, "warm_seconds": 0.2,
        "grace_seconds": 60.0, "checks": []}))
    # an arrivals mix judged on the pods it completes: steady, no bursts
    (root / "benchmark/traffic/toy-steady.json").write_text(json.dumps({
        "kind": "arrivals", "pod_template": "pod-toy", "base_rate": 100,
        "group_ms": 100, "burst_pods": 0, "burst_period_s": 1.0,
        "warm_periods": 1, "prewarm_pods": 8, "grace_seconds": 60.0,
        "checks": []}))
    for name in ("toy-2zone", "toy-groups"):
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
         "traffic": "toy-steady", "chips": 1, "why": "toy"}]
    for m in manifest["end_to_end"]:
        if m["name"] == "pods_per_s":
            m["workloads"] += ["toy.cell", "toy.groups", "toy.steady"]
    manifest["per_layer"].append({
        "name": "toy.launches", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "scheduling loop",
        "moves": "pods_per_s",
        "workloads": ["toy.cell", "toy.groups", "toy.steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root, before


def _rehearse(root, workload, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark/run.py"), "--workload",
         workload, "--seed", "5", "--seconds", "1", "--trace", trace,
         "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["toy.cell", "toy.groups",
                                      "toy.steady"])
def test_a_toy_config_mix_cell_check_and_layer_metric_are_new_files_only(
        tmp_path, workload):
    """A later PR adds entries and files and edits none: copy the benchmark
    as it stands, add a toy of each kind beside it, run the toy cells. One
    is a plain backlog; one has two init groups, namespaces, a priority and
    a required affinity term; one is an arrivals mix listed under
    `pods_per_s`."""
    root, before = _toy_checkout(tmp_path)
    line = _rehearse(root, workload, "0")
    assert line["compared"]["unbound"]["value"] == 0, line
    assert line["compared"]["toy_label_missing"] == {"value": 0, "limit": 0}
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
    else:
        traced = _rehearse(root, workload, "1")
        assert traced["metrics"]["toy.launches" + cell.NOT_DEVICE][
            "value"] > 0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_arrivals_cell_reports_the_metric_names_it_reported():
    """`pods_per_s` is computed for an arrivals mix too; which cells print
    it stays what the metric's `workloads` list says."""
    manifest = cell.load_manifest(REPO)
    names = [m["name"] for m in cell.metrics_of(
        manifest, "end_to_end", "basic-5k.arrivals")]
    assert names[:2] == ["bind_p50_ms", "setup_s"]
    assert "pods_per_s" not in names
