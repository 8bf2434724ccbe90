"""The reduction from a profiler trace to busy/idle, per-program times and
the breakdown, on a small recorded trace (two launches cut from a traced
slice of basic-5k.arrivals on a TPU v5 lite) and on a hand-written one."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_gives_known_busy_idle_and_program_times(recorded):
    t0, t1 = recorded["window_ns"]
    gap = 9426157.0            # the first launch's module event ends here
    spans = [("commit", gap + 1e5, gap + 6e5), ("pack", gap + 6e5, gap + 8e5)]
    r = trace_reduce.reduce_events(recorded, t0, t1, spans)
    # busy by an independent count: paint every operation on a 10 ns grid
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    grid = np.zeros(int((t1 - t0) / 10) + 2, bool)
    for _n, s, d in ops:
        grid[int(s / 10):int((s + d) / 10)] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 10 / 1e9, abs=2e-5)
    assert r["busy_s"] == pytest.approx(0.018806482, abs=1e-9)
    assert r["window_s"] == pytest.approx(0.072312464, abs=1e-9)
    assert r["program_s"]["schedule_batch_jit"] == pytest.approx(
        0.018815784, abs=1e-9)         # two launches of 9.4 ms
    assert set(r["program_s"]) == {"schedule_batch_jit",
                                   "convert_element_type"}
    assert r["device_ops"][0][0] == "schedule_batch_jit/while.5"
    assert r["device_ops"][0][1] == pytest.approx(0.016020747, abs=1e-9)
    assert len(r["device_ops"]) == trace_reduce.NAMES_KEPT
    launches = r["program_launch_s"]   # both launches lie whole inside
    assert [len(launches[p]) for p in sorted(launches)] == [2, 2]
    assert sum(launches["schedule_batch_jit"]) == pytest.approx(
        0.018815784, abs=1e-9)
    assert all(len(name) <= 120 for name, _s in r["device_ops"])
    idle = dict(r["idle_gaps"])
    assert idle["commit"] == pytest.approx(0.0005, abs=1e-9)
    assert idle["pack"] == pytest.approx(0.0002, abs=1e-9)
    # every idle second is attributed once: busy + idle = the window
    assert r["busy_s"] + sum(idle.values()) == pytest.approx(
        r["window_s"], abs=1e-9)
    assert trace_reduce.UNATTRIBUTED in idle


def test_a_slice_of_the_recorded_trace_clips_events_at_its_edges(recorded):
    r = trace_reduce.reduce_events(recorded, 5e6, 8e6, [])
    assert r["window_s"] == pytest.approx(0.003)
    assert r["program_s"] == {"schedule_batch_jit": pytest.approx(0.003)}
    assert 0.0025 < r["busy_s"] <= 0.003
    empty = trace_reduce.reduce_events(recorded, 2e7, 3e7, [])
    assert empty["busy_s"] == 0.0 and empty["device_ops"] == []
    assert dict(empty["idle_gaps"]) == {
        trace_reduce.UNATTRIBUTED: pytest.approx(0.01)}


XSPACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "jit_schedule_batch_jit(123)" } } }
planes { name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench_clock_sync" } } }
"""


def test_read_xplane_finds_device_lines_and_the_clock_marker(tmp_path):
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    path = trace_reduce.find_xplane(str(tmp_path))
    assert path and path.endswith("host.xplane.pb")
    tr = trace_reduce.read_xplane(path)
    assert tr["sync_ns"] == 1500.0
    dev = tr["devices"]["/device:TPU:0"]
    assert [(s, d) for _n, s, d in dev["ops"]] == [(2000.0, 2000.0),
                                                   (6000.0, 1000.0)]
    r = trace_reduce.reduce_events(tr, 1000.0, 11000.0,
                                   [("commit", 4000.0, 5000.0)])
    assert r["busy_s"] == pytest.approx(3e-6)
    assert r["program_s"] == {"schedule_batch_jit": pytest.approx(9e-6)}
    assert r["device_ops"] == [["schedule_batch_jit/fusion.1",
                                pytest.approx(3e-6)]]
    assert dict(r["idle_gaps"]) == {
        "commit": pytest.approx(1e-6),
        trace_reduce.UNATTRIBUTED: pytest.approx(6e-6)}
    assert trace_reduce.find_xplane(str(tmp_path / "nothing")) is None
