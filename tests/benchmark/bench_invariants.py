"""What a correct addition to the benchmark keeps, as functions of a manifest
and the root of the tree that holds it. The tests call each on the repo and
again on a copied tree that carries one addition of every kind a later PR
makes (test_bench_cells.py), so a test that pins today's inventory fails in
the PR that writes it. The code is the repo's; the data is the tree's. No
JAX here."""

import copy
import glob
import json
import os
import re
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cell, compare, objects, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CONTROLS = os.path.join("tests", "benchmark", "controls")
FAULT_HOOKS = ("wrap_hub", "after_scheduler", "pod_template")

# per_layer as PR 27 left it: a later entry goes anywhere, these keep their
# order among themselves, so a ledger line reads the same columns
PER_LAYER_AT_PR27 = [
    "queue.pop_ms_per_kpod.drain", "queue.pop_ms_per_kpod.arrive",
    "loop.pods_per_launch.drain", "loop.pods_per_launch.arrive",
    "loop.bind_p95_ms.arrive", "loop.commit_ms_per_kpod.drain",
    "loop.bind_p99_ms.arrive", "loop.gc_pause_max_ms.arrive",
    "gen.late_p99_ms.arrive", "mirror.sync_ms_per_kpod.drain",
    "mirror.sync_ms_per_kpod.arrive", "device.program_ms_per_kpod.drain",
    "device.program_ms_per_kpod.arrive", "device.compiles_in_window.drain",
    "device.compiles_in_window.arrive", "queue.done_ms_per_kpod.drain",
    "mirror.snapshot_cache_ms_per_kpod.drain", "loop.idle_share.arrive",
    "loop.turn_overhead_ms_per_kpod.arrive", "loop.gc_ms_per_kpod.arrive"]
_DRAIN = ["basic-5k.backlog", "topology-5k.required", "topology-5k.preferred"]
# PR 25's five readers: layer, moves, the cells each listed when it came
PR25_READERS = {
    "queue.done_ms_per_kpod.drain": ("queues", "pods_per_s", _DRAIN),
    "mirror.snapshot_cache_ms_per_kpod.drain":
        ("mirror / pack", "pods_per_s", _DRAIN[1:]),
    "loop.idle_share.arrive":
        ("scheduling loop", "bind_p50_ms", ["basic-5k.arrivals"]),
    "loop.turn_overhead_ms_per_kpod.arrive":
        ("scheduling loop", "bind_p50_ms", ["basic-5k.arrivals"]),
    "loop.gc_ms_per_kpod.arrive":
        ("scheduling loop", "bind_p50_ms", ["basic-5k.arrivals"]),
}


def _bench(root):
    return os.path.join(root, "benchmark")


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def copy_benchmark(root):
    """BENCHMARK.json, benchmark/ and the controls' cases, copied under
    `root`. Returns (the manifest, the copied files' bytes)."""
    shutil.copytree(_bench(REPO), _bench(root),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(REPO, CONTROLS),
                    os.path.join(root, CONTROLS))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    before = {}
    for top in (_bench(root), os.path.join(root, CONTROLS)):
        for d, _dirs, files in os.walk(top):
            for f in files:
                with open(os.path.join(d, f), "rb") as fh:
                    before[os.path.join(d, f)] = fh.read()
    return cell.load_manifest(root), before


def unchanged(before):
    """The copied files hold the bytes they were copied with."""
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, path
    return True


def load_controls(root=REPO):
    """Every control's case, {"cell", "fault", "caught_by"} and optionally
    "mix" (parameters laid over the mix at its rehearsal size, where the
    fault needs more pods than that to show), from every file of
    tests/benchmark/controls/: a later PR adds a file."""
    cases = []
    for path in sorted(glob.glob(os.path.join(root, CONTROLS, "*.json"))):
        with open(path) as f:
            cases += json.load(f)
    return cases


def each_cell(manifest, root):
    """(cell, its configuration's entry, rehearse, cfg, mix) for every cell
    at both of its sizes."""
    for w in manifest["workloads"]:
        _cell, entry = cell.find_cell(manifest, w["name"])
        for rehearse in (False, True):
            yield (w, entry, rehearse, cell.load_config(entry, rehearse, root),
                   traffic.load_mix(w["traffic"], rehearse, _bench(root)))


# ------------------------------------------------- BENCHMARK.json itself


def top_level_keys_and_limits(manifest, root=REPO):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) \
        + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 65536
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, len(manifest["workloads"]) // 2)


def every_name_unit_and_line_fits(manifest, root=REPO):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        names.append(w["name"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"]) and m["source"] in SOURCES
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}


def layer_metrics_move_a_metric_their_cells_report(manifest, root=REPO):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads", [])
        assert set(listed) <= cells and len(listed) == len(set(listed)), \
            m["name"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        reported_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reported_in, m["name"]
    for w in cells:
        assert len(cell.metrics_of(manifest, "end_to_end", w)) >= 2
        assert cell.metrics_of(manifest, "per_layer", w)


def every_cells_files_are_found_by_name(manifest, root=REPO):
    used = set()
    for w, entry, rehearse, cfg, mix in each_cell(manifest, root):
        used.add(entry["name"])
        assert entry["file"].startswith("benchmark/")
        names = [cfg["nodes"]["template"], mix["pod_template"]] \
            + [g["template"] for g in cell.init_groups(cfg)]
        for t in names:
            assert objects.load_template(t, _bench(root))["kind"] \
                in ("node", "pod")
        if rehearse:
            continue
        assert cfg["name"] == entry["name"]
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        assert cfg["guarantees"] and cfg["checks"]
        for m in cell.metrics_of(manifest, "per_layer", w["name"]):
            assert callable(cell.load_reader(m["name"], _bench(root)))
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))


def files_under_paths_are_named_from_allowed_characters(manifest, root=REPO):
    for top in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(root, top)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(d, f), root)
                assert PATH.match(rel), rel


# ------------------------------------------------- templates


def pod_namespaces(pod):
    """Every namespace a built pod names, read off the pod itself: its own
    where that is not the default, and those of its affinity terms."""
    out = {pod.metadata.namespace} - {"default"}
    aff = pod.spec.affinity
    for rule in (aff.pod_affinity, aff.pod_anti_affinity) if aff else ():
        if rule is not None:
            for t in rule.required:
                out.update(t.namespaces)
            for t in rule.preferred:
                out.update(t.pod_affinity_term.namespaces)
    return out


def every_template_named_exists_and_builds(manifest, root=REPO):
    """Every template that a configuration (each init group, at both
    sizes), a mix or a fault planted in the cell names is a file of its
    kind that the builders take, and every namespace its pods name is one
    cell.build_cluster creates: it creates those of the init groups'
    templates and of the mix's and the fault's."""
    bench = _bench(root)
    planted = {}
    for c in load_controls(root):
        planted.setdefault(c["cell"], []).append(c["fault"])
    for w, _entry, _rehearse, cfg, mix in each_cell(manifest, root):
        node = objects.load_template(cfg["nodes"]["template"], bench)
        assert node["kind"] == "node", cfg["nodes"]["template"]
        zones = list(cfg["nodes"].get("zones") or [])
        made = objects.make_node(node, 1, zones)
        assert made.status.allocatable and made.metadata.name
        init = [g["template"] for g in cell.init_groups(cfg)]
        assert all(int(g["count"]) >= 0 for g in cell.init_groups(cfg))
        offered = [mix["pod_template"]]
        for fault in planted.get(w["name"], []):
            mod = compare.load_by_name("faults", fault, bench)
            if hasattr(mod, "pod_template"):
                offered.append(mod.pod_template(mix))
        for pod_template in offered:    # one run: the mix's pods or a fault's
            names = init + [mix["pod_template"], pod_template]
            tmpls = [objects.load_template(t, bench) for t in names]
            created = {ns for t in tmpls
                       for ns in objects.template_namespaces(t)}
            for name, tmpl in zip(names, tmpls):
                assert tmpl["kind"] == "pod", name
                pod = objects.PodMaker(tmpl).make("x", "node-1")
                assert pod.spec.containers[0].resources.requests, name
                assert pod_namespaces(pod) <= created, name


# ------------------------------------------------- controls and checks


def every_fault_is_planted_and_every_check_is_a_file(manifest, root=REPO):
    bench = _bench(root)
    cases = load_controls(root)
    cells = {w["name"] for w in manifest["workloads"]}
    files = set(compare.names_in("faults", bench))
    for c in cases:
        assert set(c) - {"mix"} == {"cell", "fault", "caught_by"}, c
        assert c["cell"] in cells, c
        assert c["fault"] in files, c
        assert NAME.match(c["caught_by"]), c
    assert files - {c["fault"] for c in cases} == set(), \
        "a fault that no control's case plants"
    keys = [(c["cell"], c["fault"], c["caught_by"]) for c in cases]
    assert len(keys) == len(set(keys))
    for name in files:
        mod = compare.load_by_name("faults", name, bench)
        assert any(callable(getattr(mod, h, None)) for h in FAULT_HOOKS), name
    for _w, _entry, _rehearse, cfg, mix in each_cell(manifest, root):
        assert cfg["checks"]
        for name in cfg["checks"] + mix.get("checks", []):
            assert callable(compare.load_by_name("checks", name, bench).check)


# ------------------------------------------------- per-layer metrics


def per_layer_entries_keep_their_order_and_their_cells(manifest, root=REPO):
    names = iter(m["name"] for m in manifest["per_layer"])
    for want in PER_LAYER_AT_PR27:      # a subsequence: `in` consumes
        assert want in names, f"{want} is gone or was moved"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (layer, moves, cells) in PR25_READERS.items():
        m = by_name[name]
        assert (m["layer"], m["moves"]) == (layer, moves), name
        assert m["workloads"][:len(cells)] == cells, name


INVARIANTS = [
    top_level_keys_and_limits,
    every_name_unit_and_line_fits,
    layer_metrics_move_a_metric_their_cells_report,
    every_cells_files_are_found_by_name,
    files_under_paths_are_named_from_allowed_characters,
    every_template_named_exists_and_builds,
    every_fault_is_planted_and_every_check_is_a_file,
    per_layer_entries_keep_their_order_and_their_cells,
]


def edited(manifest, edit):
    """A deep copy of the manifest with `edit(copy)` applied."""
    out = copy.deepcopy(manifest)
    edit(out)
    return out
