"""The comparison has to fail what it should: the plain reference on planted
end states, and a whole rehearsal run (`rehearse=True`: the tiny size on
the CPU, metrics renamed) with the timed path broken underneath, once for
each fault a cell can have."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cell, compare, reference  # noqa: E402

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


FAULTS = [
    # (cell, fault planted under the timed path, a number that must catch it)
    # state left unchanged; half of each batch left out
    ("basic-5k.backlog", "no_bind", "acknowledged_binds_missing"),
    ("basic-5k.backlog", "half_batch", "acknowledged_binds_missing"),
    ("basic-5k.backlog", "overpack", "overpacked_nodes"),   # answer altered
    ("basic-5k.backlog", "move_bound", "double_binds"),
    ("basic-5k.backlog", "device_fault", "left_device_path"),
    ("basic-5k.backlog", "lost_in_queue", "unbound"),      # lost, never bound
    ("topology-5k.preferred", "lost_in_queue", "unbound"),
    ("basic-5k.arrivals", "drop_bind", "unbound"),
    ("basic-5k.arrivals", "overpack", "overpacked_nodes"),
    ("topology-5k.required", "skew", "skew_excess"),
    ("topology-5k.required", "plain_pods", "scan_launches_missing"),
    ("topology-5k.preferred", "plain_pods", "soft_launches_missing"),
    ("topology-5k.preferred", "half_batch", "acknowledged_binds_missing"),
]


@pytest.mark.parametrize("workload,fault,caught_by", FAULTS)
def test_a_run_with_the_timed_path_broken_is_not_correct(
        workload, fault, caught_by, monkeypatch):
    if fault in ("drop_bind", "lost_in_queue"):
        # 1 in 997: give the tiny size enough pods to lose one
        load = cell.traffic_mod.load_mix
        monkeypatch.setattr(
            cell.traffic_mod, "load_mix", lambda name, rehearse: dict(
                load(name, rehearse), burst_pods=700, depth=1100))
    r = cell.run_cell(workload, 41, 1, False, rehearse=True, fault=fault,
                      log=lambda _m: None)
    assert r["correct"] is False
    c = r["compared"][caught_by]
    assert c["value"] > c["limit"], r["compared"]
    if caught_by == "unbound":
        assert r["failed"] > 0


def test_every_fault_file_is_planted_in_some_cell_and_every_check_is_a_file():
    assert {f for _w, f, _c in FAULTS} == set(compare.names_in("faults"))
    manifest = cell.load_manifest(REPO)
    for w in manifest["workloads"]:
        _c, entry = cell.find_cell(manifest, w["name"])
        cfg = cell.load_config(entry, False, REPO)
        mix = cell.traffic_mod.load_mix(w["traffic"])
        for name in cfg["checks"] + mix.get("checks", []):
            assert callable(compare.load_by_name("checks", name).check)
