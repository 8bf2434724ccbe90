"""PR 37: room in the pod table of the three backlog scan cells, and the
readers that show it. The three mixes' warm-up and kept pods against what
the guarantee needs, a rehearsal of each cell with them, the reduction's
launch count and thirty names on a hand-made trace, the three new readers
on hand-made `obs`, and every per-layer name against its file."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cell, compare, readers, trace_reduce, traffic  # noqa: E402

SCAN_CELLS = ["topology-5k.required", "topology-5k.preferred",
              "affinity-5k.required"]
# what a scan cell has popped and not yet bound when its window closes: a
# launch in flight or in commit, the one before it with the binder, and the
# pop that races the withdrawal
LAUNCHES_IN_LIMBO = 3


def _cell_files(workload, rehearse):
    manifest = cell.load_manifest(REPO)
    w, entry = cell.find_cell(manifest, workload)
    return (cell.load_config(entry, rehearse, REPO),
            traffic.load_mix(w["traffic"], rehearse))


@pytest.mark.parametrize("workload", SCAN_CELLS)
def test_scan_mix_warms_four_seconds_and_keeps_four_batches(workload):
    cfg, mix = _cell_files(workload, False)
    batch = int(cfg["scheduler"]["batch_size"])
    assert mix["warm_seconds"] == 4.0
    assert mix["keep_oldest"] == 4096 == 4 * batch
    # the kept pods hold every pod the scheduler has popped and assumed
    assert mix["keep_oldest"] > LAUNCHES_IN_LIMBO * batch
    # a run at 3,500 pods/s still ends under the table's capacity: init
    # pods, the binds of the warm-up's seconds and the window's, the kept
    init = sum(int(g["count"]) for g in cell.init_groups(cfg))
    window = cell.load_manifest(REPO)["run_seconds"]
    at_end = init + 3500 * (mix["warm_seconds"] + window) \
        + mix["keep_oldest"]
    assert at_end < int(cfg["capacities"]["pods"])
    assert mix["assumed"]["warm_seconds"].startswith("4 s")


@pytest.mark.parametrize("workload", ["topology-5k.required",
                                      "affinity-5k.required"])
def test_rehearsal_keeps_the_batches_the_full_size_keeps(workload):
    cfg, mix = _cell_files(workload, True)
    full_cfg, full_mix = _cell_files(workload, False)
    assert mix["keep_oldest"] // int(cfg["scheduler"]["batch_size"]) \
        == full_mix["keep_oldest"] // int(full_cfg["scheduler"]["batch_size"])


@pytest.mark.parametrize("seed", [37, 3_700_000_011])
@pytest.mark.parametrize("workload", SCAN_CELLS)
def test_scan_cell_rehearses_correct_with_the_room_it_now_has(workload, seed):
    logs = []
    r = cell.run_cell(workload, seed, 2, False, rehearse=True,
                      log=logs.append)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in r["compared"].values())
    own = {"topology-5k.required": "skew_excess",
           "affinity-5k.required": "affinity_unsatisfied"}.get(workload)
    if own:
        assert r["compared"][own] == {"value": 0, "limit": 0}
    for number in ("unbound", "double_binds", "acknowledged_binds_missing"):
        assert r["compared"][number]["value"] == 0
    diag = json.loads(next(m for m in logs if m.startswith("diag "))[5:])
    assert diag["compiles_in_window"] == []
    if own:     # depth 512, 256 kept: the close did withdraw pods
        assert diag["withdrawn"] > 0


def test_traced_rehearsal_reports_the_pod_tables_fill():
    logs = []
    r = cell.run_cell("topology-5k.required", 37, 2, True, rehearse=True,
                      log=logs.append)
    fill = r["metrics"]["mirror.pod_table_fill.drain" + cell.NOT_DEVICE]
    assert fill["unit"] == "share" and 0.0 < fill["value"] < 1.0
    cfg, _mix = _cell_files("topology-5k.required", True)
    # init pods and every pod the run bound have a slot, to one launch
    # (a bound pod takes its slot at the next launch's sync)
    slots = fill["value"] * int(cfg["capacities"]["pods"])
    init = sum(int(g["count"]) for g in cell.init_groups(cfg))
    batch = int(cfg["scheduler"]["batch_size"])
    assert init + r["attempted"] - batch <= slots <= init + r["attempted"]
    # no device plane in a CPU trace: nothing to read, never 0
    assert "device.launch_ms.drain" + cell.NOT_DEVICE not in r["metrics"]
    assert len(r["breakdown"]["device_ops"]) <= cell.BREAKDOWN_NAMES


class _Sched:
    def __init__(self, mirror):
        self.mirror = mirror


class _Mirror:
    def __init__(self, capacity, free):
        self.caps = type("Caps", (), {"pods": capacity})()
        self._free_slots = list(range(free))


def test_pod_table_slots_reads_the_mirror_the_scheduler_has_now():
    sched = _Sched(_Mirror(1024, 1000))
    assert cell.pod_table_slots(sched) == (24, 1024)
    sched.mirror = _Mirror(2048, 1000)           # after a _grow
    assert cell.pod_table_slots(sched) == (1048, 2048)
    assert cell.pod_table_slots(_Sched(object())) is None
    assert cell.pod_table_slots(object()) is None


# ------------------------------------------------- the reduction


def _hand_made_trace():
    """One device, slice [1000, 11000): four launches of schedule_batch_jit
    (the first cut by the slice's head, the last by its tail), one whole
    launch of another program, and 35 operations of falling length."""
    mods = [("jit_schedule_batch_jit(1)", 500.0, 1000.0),      # cut: head
            ("jit_schedule_batch_jit(1)", 2000.0, 2000.0),
            ("jit_schedule_batch_jit(1)", 5000.0, 3000.0),
            ("jit__scatter_rows(7)", 8200.0, 300.0),
            ("jit_schedule_batch_jit(1)", 10000.0, 4000.0)]    # cut: tail
    ops = [(f"%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} %p)",
            2000.0 + 50.0 * i, 40.0 - i) for i in range(35)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "start_wall_ns": 0, "sync_ns": None}


def test_a_launch_cut_by_the_slices_edge_is_no_launch_and_thirty_names_stay():
    r = trace_reduce.reduce_events(_hand_made_trace(), 1000.0, 11000.0, [])
    # clipped seconds, as before: 0.5 + 2 + 3 + 1 us of the four launches
    assert r["program_s"] == {
        "schedule_batch_jit": pytest.approx(6.5e-6),
        "_scatter_rows": pytest.approx(0.3e-6)}
    # whole launches only, in the order they started: the two cut ones
    # are no launch
    assert r["program_launch_s"] == {
        "schedule_batch_jit": [pytest.approx(2e-6), pytest.approx(3e-6)],
        "_scatter_rows": [pytest.approx(0.3e-6)]}
    names = [n for n, _s in r["device_ops"]]
    assert len(names) == trace_reduce.NAMES_KEPT == 30
    assert names == [f"schedule_batch_jit/fusion.{i}" for i in range(30)]
    secs = [s for _n, s in r["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert secs[0] == pytest.approx(40e-9) and secs[-1] == pytest.approx(11e-9)
    # a slice that holds no whole launch counts none
    r = trace_reduce.reduce_events(_hand_made_trace(), 2500.0, 3500.0, [])
    assert r["program_launch_s"] == {}
    assert r["program_s"] == {"schedule_batch_jit": pytest.approx(1e-6)}


def test_the_launch_reader_on_the_hand_made_trace_is_the_median_whole_launch():
    red = trace_reduce.reduce_events(_hand_made_trace(), 1000.0, 11000.0, [])
    red["pods_bound"] = 2048
    got = cell.load_reader("device.launch_ms.drain")({"trace": red})
    # of 2 us and 3 us, by the benchmark's nearest-rank rule, in ms
    assert got == pytest.approx(2e-3)


# ------------------------------------------------- the readers


def _trace(launches_s, program="schedule_batch_jit"):
    return {"busy_s": 1.0, "window_s": 2.0, "pods_bound": 0,
            "program_s": {program: sum(launches_s) + 0.05},
            "program_launch_s": {program: launches_s} if launches_s else {}}


@pytest.mark.parametrize("name", ["device.launch_ms.drain",
                                  "device.launch_ms.arrive"])
@pytest.mark.parametrize("trace, want", [
    (_trace([0.171, 0.172, 0.170, 0.171, 0.171]), 171.0),
    # a launch that carried half a batch does not move the reading
    (_trace([0.2195, 0.2193, 0.146, 0.2197, 0.2194]), 219.4),
    (_trace([0.0106]), 10.6),
    (_trace([]), None),                      # a slice with no whole launch
    (_trace([0.1, 0.1], program="_scatter_rows"), None),
    ({"busy_s": 1.0, "window_s": 2.0, "pods_bound": 9,     # an older
      "program_s": {"schedule_batch_jit": 0.4}}, None),    # reduction's
    (None, None),                                          # no trace taken
])
def test_launch_reader_is_the_median_launch_and_has_nothing_without_one(
        name, trace, want):
    got = cell.load_reader(name)({"trace": trace})
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("table, want", [
    ({"capacity": 131072, "in_use_at_close": 99000, "in_use_at_end": 103500},
     103500 / 131072),
    ({"capacity": 131072, "in_use_at_close": 900, "in_use_at_end": 800},
     900 / 131072),
    ({"capacity": 131072, "in_use_at_close": 0, "in_use_at_end": 0}, 0.0),
    ({"capacity": 0, "in_use_at_close": 0, "in_use_at_end": 0}, None),
    (None, None),
])
def test_fill_reader_takes_the_fullest_of_the_two_readings(table, want):
    obs = {} if table is None else {"pod_table": table}
    got = cell.load_reader("mirror.pod_table_fill.drain")(obs)
    assert got == (pytest.approx(want) if want is not None else None)
    assert readers.pod_table_fill(obs) == got


# ------------------------------------------------- names and files


def test_every_per_layer_name_has_its_file_and_every_file_its_entry():
    manifest = cell.load_manifest(REPO)
    names = [m["name"] for m in manifest["per_layer"]]
    files = compare.names_in("layer_metrics")
    assert sorted(names) == sorted(files)
    for name in names:
        assert callable(cell.load_reader(name))


def test_the_new_readers_follow_what_was_there_under_their_layers_and_cells():
    manifest = cell.load_manifest(REPO)
    per = {m["name"]: m for m in manifest["per_layer"]}
    # appended (the driver reads the list by position), in this order; a
    # later PR's entries go after them
    names = iter(m["name"] for m in manifest["per_layer"])
    for want in ("loop.host_wait_share.arrive", "mirror.pod_table_fill.drain",
                 "device.launch_ms.drain", "device.launch_ms.arrive"):
        assert want in names, f"{want} is gone or was moved"
    fill = per["mirror.pod_table_fill.drain"]
    assert (fill["layer"], fill["moves"], fill["better"], fill["unit"]) \
        == ("mirror / pack", "pods_per_s", "lower", "share")
    assert fill["workloads"][:3] == SCAN_CELLS
    for suffix in ("drain", "arrive"):
        new = per[f"device.launch_ms.{suffix}"]
        old = per[f"device.program_ms_per_kpod.{suffix}"]
        assert (new["layer"], new["moves"], new["source"]) \
            == (old["layer"], old["moves"], "device_trace")
        assert (new["unit"], new["better"]) == ("ms", "lower")
        assert new["workloads"][:len(old["workloads"])] == old["workloads"]
    # a cheaper launch carries fewer pods at a fixed rate: where the device
    # sets the pace the count is no "higher is better" (PERF.md 7, fault 7)
    assert "anti-affinity-5k.required" \
        not in per["loop.pods_per_launch.arrive"]["workloads"]
