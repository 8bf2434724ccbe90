"""BENCHMARK.json against the contract's limits, and every cell's files
found by name. No JAX here."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cell, objects, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return cell.load_manifest(REPO)


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) \
        + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert 1 <= len(manifest["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, len(manifest["workloads"]) // 2)


def test_every_name_unit_and_line_fits(manifest):
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


def test_layer_metrics_move_a_metric_their_cells_report(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        reported_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reported_in, m["name"]
    for w in cells:
        assert len(cell.metrics_of(manifest, "end_to_end", w)) >= 2
        assert cell.metrics_of(manifest, "per_layer", w)


def test_every_cells_files_are_found_by_name(manifest):
    used = set()
    for w in manifest["workloads"]:
        _cell, entry = cell.find_cell(manifest, w["name"])
        used.add(entry["name"])
        assert entry["file"].startswith("benchmark/")
        for rehearse in (False, True):
            cfg = cell.load_config(entry, rehearse, REPO)
            mix = traffic.load_mix(w["traffic"], rehearse)
            for t in (cfg["nodes"]["template"], cfg["init_pods"]["template"],
                      mix["pod_template"]):
                assert objects.load_template(t)["kind"] in ("node", "pod")
        full = cell.load_config(entry, False, REPO)
        assert full["name"] == entry["name"]
        assert full["source"] == entry["source"]
        assert full["reduced"] == entry["reduced"]
        assert full["guarantees"] and full["checks"]
        for m in cell.metrics_of(manifest, "per_layer", w["name"]):
            assert callable(cell.load_reader(m["name"]))
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))


def test_files_under_paths_are_named_from_allowed_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert ok.match(rel), rel


def test_peaks_table_knows_the_chip_and_refuses_others():
    assert cell.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cell.device_peaks("some other device")
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]
