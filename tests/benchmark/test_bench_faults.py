"""The comparison has to fail what it should: the plain reference on planted
end states, and a whole rehearsal run (`rehearse=True`: the tiny size on
the CPU, metrics renamed) with the timed path broken underneath, once for
each fault a cell can have. The cases are data: every file of
tests/benchmark/controls/ is a list of them, and a later PR adds a file."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_invariants as inv  # noqa: E402
from benchmark import cell, reference  # noqa: E402

NODES = [("n0", "4", "32Gi", "110"), ("n1", "4", "32Gi", "110")]


def test_reference_counts_what_each_guarantee_forbids():
    ok = [("a", "n0", "100m", "500Mi"), ("b", "n1", "100m", "500Mi")]
    assert reference.check_nodes(NODES, ok) == {
        "overpacked_nodes": 0, "unknown_node_binds": 0}
    cpu_full = [(f"p{i}", "n0", "100m", "500Mi") for i in range(41)]
    assert reference.check_nodes(NODES, cpu_full)["overpacked_nodes"] == 1
    mem_full = [(f"p{i}", "n1", "1m", "1Gi") for i in range(33)]
    assert reference.check_nodes(NODES, mem_full)["overpacked_nodes"] == 1
    too_many = [(f"p{i}", "n1", "1m", "1Mi") for i in range(111)]
    assert reference.check_nodes(NODES, too_many)["overpacked_nodes"] == 1
    assert reference.check_nodes(
        NODES, [("x", "gone", "1m", "1Mi")])["unknown_node_binds"] == 1
    assert reference.milli("4") == 4000 and reference.milli("100m") == 100
    assert reference.to_bytes("500Mi") == 500 << 20


def test_journal_audit_wants_each_offered_pod_bound_exactly_once():
    rows = [(1, "add", "a", ""), (2, "add", "b", ""), (3, "update", "a", "n0"),
            (4, "add", "c", ""), (5, "update", "b", "n1"),
            (6, "update", "b", "n1")]            # a status patch, not a bind
    got = reference.audit_journal(rows, {"a", "b", "c"})
    assert got == {"unbound": 1, "double_binds": 0, "binds_audited": 2}
    moved = rows + [(7, "update", "a", "n1")]
    assert reference.audit_journal(moved, {"a", "b"})["double_binds"] == 1
    rebound = rows + [(7, "update", "a", ""), (8, "update", "a", "n0")]
    assert reference.audit_journal(rebound, {"a", "b"})["double_binds"] >= 1
    gone = rows + [(9, "delete", "a", "n0")]
    assert reference.audit_journal(gone, {"a"})["unbound"] == 1
    assert reference.audit_journal(list(reversed(rows)),
                                   {"a", "b"})["unbound"] == 0


def test_skew_is_held_over_every_domain_even_an_empty_one():
    rule = {"topology_key": "zone", "max_skew": 5,
            "match_labels": {"color": "blue"}}
    labels = {"n0": {"zone": "a"}, "n1": {"zone": "b"}, "n2": {"zone": "c"}}
    blue = {"color": "blue"}
    pods = [(f"p{i}", "n0", blue) for i in range(5)]
    assert reference.skew_excess(rule, labels, pods) == 0
    pods.append(("p5", "n0", blue))
    assert reference.skew_excess(rule, labels, pods) == 1
    pods.append(("other", "n0", {"color": "red"}))
    assert reference.skew_excess(rule, labels, pods) == 1
    assert reference.required_rules({"spread": [
        dict(rule, when_unsatisfiable="ScheduleAnyway")]}) == []


# (cell, fault planted under the timed path, a number that must catch it):
# a state left unchanged, half of each batch left out, an answer altered
# where it is produced, a pod lost and never bound
CONTROLS = inv.load_controls()


@pytest.mark.parametrize("case", CONTROLS, ids=lambda c: "-".join(
    (c["cell"], c["fault"], c["caught_by"])))
def test_a_run_with_the_timed_path_broken_is_not_correct(case, monkeypatch):
    if "mix" in case:
        # a fault of 1 in 997: give the tiny size enough pods to lose one
        load = cell.traffic_mod.load_mix
        monkeypatch.setattr(
            cell.traffic_mod, "load_mix", lambda name, rehearse: dict(
                load(name, rehearse), **case["mix"]))
    r = cell.run_cell(case["cell"], 41, 1, False, rehearse=True,
                      fault=case["fault"], log=lambda _m: None)
    assert r["correct"] is False
    assert case["caught_by"] in r["compared"], sorted(r["compared"])
    c = r["compared"][case["caught_by"]]
    assert c["value"] > c["limit"], r["compared"]
    if case["caught_by"] == "unbound":
        assert r["failed"] > 0


def test_every_fault_file_is_planted_in_some_cell_and_every_check_is_a_file():
    inv.every_fault_is_planted_and_every_check_is_a_file(
        cell.load_manifest(REPO))


def _orphan_fault(root):
    (root / "benchmark/faults/orphan.py").write_text(
        "def wrap_hub(hub, node_names, zone_of):\n    pass\n")


def _fault_without_a_hook(root):
    (root / "benchmark/faults/skew.py").write_text("def plant():\n    pass\n")


def _case(**keys):
    case = dict(CONTROLS[0], **keys)
    return lambda root: (root / inv.CONTROLS / "more.json").write_text(
        json.dumps([case]))


def _unknown_check(root):
    path = root / "benchmark/traffic/preferred.json"
    mix = json.loads(path.read_text())
    path.write_text(json.dumps(dict(mix, checks=["soft_auction", "gone"])))


INVENTORY_BREACHES = {
    "a fault file that no case plants": (AssertionError, _orphan_fault),
    "a fault file with none of the three hooks":
        (AssertionError, _fault_without_a_hook),
    "a case for a cell that is not in the manifest":
        (AssertionError, _case(cell="no-such.cell")),
    "a case for a fault that is no file":
        (AssertionError, _case(fault="no_such_fault")),
    "a case that is there twice": (AssertionError, _case()),
    "a case with a key that nothing reads":
        (AssertionError, _case(fault="skew", seconds=9)),
    "a mix names a check that is no file":
        (FileNotFoundError, _unknown_check),
}


@pytest.mark.parametrize("breach", sorted(INVENTORY_BREACHES))
def test_a_breach_of_the_controls_inventory_fails_the_invariant(
        tmp_path, breach):
    error, plant = INVENTORY_BREACHES[breach]
    manifest, _before = inv.copy_benchmark(tmp_path)
    inv.every_fault_is_planted_and_every_check_is_a_file(manifest, tmp_path)
    plant(tmp_path)
    with pytest.raises(error):
        inv.every_fault_is_planted_and_every_check_is_a_file(manifest,
                                                             tmp_path)
