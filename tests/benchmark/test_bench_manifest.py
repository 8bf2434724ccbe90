"""BENCHMARK.json against the contract's limits, and every cell's files
found by name: the invariants of bench_invariants.py, on the repo. No JAX
here."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_invariants as inv  # noqa: E402
from benchmark import cell  # noqa: E402


@pytest.fixture(scope="module")
def manifest():
    return cell.load_manifest(REPO)


def test_top_level_keys_and_limits(manifest):
    inv.top_level_keys_and_limits(manifest)


def test_every_name_unit_and_line_fits(manifest):
    inv.every_name_unit_and_line_fits(manifest)


def test_layer_metrics_move_a_metric_their_cells_report(manifest):
    inv.layer_metrics_move_a_metric_their_cells_report(manifest)


def test_every_cells_files_are_found_by_name(manifest):
    inv.every_cells_files_are_found_by_name(manifest)


def test_files_under_paths_are_named_from_allowed_characters(manifest):
    inv.files_under_paths_are_named_from_allowed_characters(manifest)


BREACHES = {
    "an unknown key on a cell": (
        inv.every_name_unit_and_line_fits,
        lambda m: m["workloads"][0].update(seed=1)),
    "a bound over the contract's": (
        inv.every_name_unit_and_line_fits,
        lambda m: m["end_to_end"][0].update(bound=0.3)),
    "a metric lists a cell that is not there": (
        inv.layer_metrics_move_a_metric_their_cells_report,
        lambda m: m["per_layer"][0]["workloads"].append("no-such.cell")),
    "a per-layer metric moves what its cell does not report": (
        inv.layer_metrics_move_a_metric_their_cells_report,
        lambda m: m["per_layer"][0]["workloads"].append("basic-5k.arrivals")),
    "a cell that reports no per-layer metric": (
        inv.layer_metrics_move_a_metric_their_cells_report,
        lambda m: m["workloads"].append(dict(
            m["workloads"][0], name="bare.cell", traffic="required"))),
    "a configuration no cell uses": (
        inv.every_cells_files_are_found_by_name,
        lambda m: m["configs"].append(dict(m["configs"][0], name="unused"))),
}


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_a_breach_of_the_manifest_fails_its_invariant(manifest, breach):
    invariant, edit = BREACHES[breach]
    invariant(manifest)
    with pytest.raises(AssertionError):
        invariant(inv.edited(manifest, edit))


def test_peaks_table_knows_the_chip_and_refuses_others():
    assert cell.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cell.device_peaks("some other device")
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]
