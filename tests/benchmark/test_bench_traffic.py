"""The traffic generator and the metric arithmetic. No JAX here."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import objects, stats, traffic  # noqa: E402


class _Hub:
    def __init__(self):
        self.created = []

    def create_pod(self, pod):
        self.created.append(pod)


def _generated(seed, seconds=2):
    mix = traffic.load_mix("arrivals", rehearse=True)
    schedule = traffic.arrival_schedule(mix, seconds)
    maker = objects.PodMaker(objects.load_template(mix["pod_template"]))
    token = f"{seed & 0xffffffff:08x}"
    pods = [maker.make(f"m-{token}-{i}")
            for i in range(sum(n for _o, n in schedule))]
    hub = _Hub()
    gen = traffic.ArrivalGenerator(hub, pods, schedule, 1000.0, lambda: 0,
                                   clock=lambda: 2000.0)   # all overdue
    gen.run()
    assert gen.error is None
    return schedule, gen, hub


def test_arrival_schedule_is_the_same_for_every_seed_and_pods_differ():
    s1, g1, h1 = _generated(1)
    s2, g2, h2 = _generated(3_000_000_001)
    assert s1 == s2
    assert sorted(g1.due.values()) == sorted(g2.due.values())
    assert len(h1.created) == len(h2.created) == sum(n for _o, n in s1)
    names1 = {p.metadata.name for p in h1.created}
    names2 = {p.metadata.name for p in h2.created}
    assert not names1 & names2
    assert [p.spec.containers[0].resources.requests for p in h1.created] \
        == [p.spec.containers[0].resources.requests for p in h2.created]


def test_arrival_schedule_counts_bursts_and_groups():
    mix = traffic.load_mix("arrivals")
    sched = traffic.arrival_schedule(mix, 30)
    in_window = [(o, n) for o, n in sched if o >= 0]
    per_group = mix["base_rate"] * mix["group_ms"] // 1000
    assert len(in_window) == 300
    assert sum(1 for _o, n in in_window if n > per_group) == 15
    assert sum(n for _o, n in in_window) == 30 * mix["base_rate"] \
        + 15 * mix["burst_pods"]
    assert sched[0][0] == -mix["warm_periods"] * mix["burst_period_s"]
    assert sched[0][1] == per_group + mix["burst_pods"]
    assert all(b[0] > a[0] for a, b in zip(sched, sched[1:]))
    with pytest.raises(ValueError):
        traffic.arrival_schedule({**mix, "base_rate": 1234, "group_ms": 7},
                                 30)


@pytest.mark.parametrize("rate,group_ms,seconds", [(50, 100, 30),
                                                   (400, 100, 30),
                                                   (1000, 50, 10)])
def test_no_burst_pods_is_a_steady_schedule(rate, group_ms, seconds):
    mix = {**traffic.load_mix("arrivals"), "base_rate": rate,
           "group_ms": group_ms, "burst_pods": 0}
    sched = traffic.arrival_schedule(mix, seconds)
    per_group = rate * group_ms // 1000
    assert {n for _o, n in sched} == {per_group}
    assert sum(n for o, n in sched if o >= 0) == rate * seconds
    gaps = {round(b[0] - a[0], 9) for a, b in zip(sched, sched[1:])}
    assert gaps == {group_ms / 1000.0}


def test_each_pod_is_timed_from_its_due_instant_not_from_its_creation():
    _s, gen, _h = _generated(5)
    # the generator ran 1,000 s late on its clock: lateness shows there,
    # and the due instants stay where the schedule put them
    assert min(gen.due.values()) == pytest.approx(1000.0 - 2.0)
    assert all(gen.sent[u] - d >= 990.0 for u, d in gen.due.items())


def test_rates_are_all_pods_over_the_whole_window_so_a_stall_moves_them():
    steady = [i / 100.0 for i in range(1000)]           # 100/s for 10 s
    stalled = [t for t in steady if not 4.0 <= t < 5.0]  # nothing for 1 s
    assert stats.rate_in_window(steady, 0.0, 10.0) == 100.0
    assert stats.rate_in_window(stalled, 0.0, 10.0) == 90.0
    assert stats.rate_in_window(steady, 2.0, 4.0) == 100.0


def test_a_one_second_stall_moves_the_95th_percentile():
    due = {f"p{i}": i / 100.0 for i in range(1000)}
    quick = {u: t + 0.05 for u, t in due.items()}
    stalled = dict(quick)
    for u, t in due.items():          # binds due in [4, 5) land at 5.0 + wait
        if 4.0 <= t < 5.0:
            stalled[u] = 5.05
    s0, f0 = stats.wait_samples_ms(due, quick, 0.0, 10.0, 12.0)
    s1, f1 = stats.wait_samples_ms(due, stalled, 0.0, 10.0, 12.0)
    assert f0 == f1 == 0 and len(s0) == len(s1) == 1000
    assert stats.percentile(s0, 95) == pytest.approx(50.0)
    assert stats.percentile(s1, 95) > 500.0
    assert stats.percentile(s1, 50) == pytest.approx(50.0)


def test_an_unbound_pod_counts_as_the_longest_wait_and_as_failed():
    due = {"a": 0.0, "b": 1.0, "c": 2.0, "late": 11.0}
    bound = {"a": 0.1, "b": 1.1}
    samples, failed = stats.wait_samples_ms(due, bound, 0.0, 10.0, 20.0)
    assert failed == 1 and len(samples) == 3      # "late" is outside
    assert samples[-1] == pytest.approx(18000.0)
    assert stats.percentile([], 95) is None


def test_backlog_feeder_keeps_depth_in_slabs():
    hub = _Hub()
    bound = [0]
    maker = objects.PodMaker(objects.load_template("pod-spread-required"))
    feeder = traffic.BacklogFeeder(hub, lambda i: maker.make(f"m-{i}"),
                                   lambda: bound[0], depth=100, slab=32)
    feeder.start()
    try:
        import time
        deadline = time.time() + 10
        while len(hub.created) < 100 and time.time() < deadline:
            time.sleep(0.01)
        assert len(hub.created) == 100
        bound[0] = 31                     # not yet a slab under the mark
        time.sleep(0.05)
        assert len(hub.created) == 100
        bound[0] = 40
        while len(hub.created) < 132 and time.time() < deadline:
            time.sleep(0.01)
        assert len(hub.created) == 132
    finally:
        feeder.stop()
    assert not feeder.is_alive() and feeder.error is None
    pod = hub.created[0]
    assert pod.metadata.labels == {"color": "blue"}
    tsc = pod.spec.topology_spread_constraints[0]
    assert (tsc.max_skew, tsc.when_unsatisfiable) == (5, "DoNotSchedule")
