"""The launch contract between the loop and the device: a dispatched
launch travels as one Wave, and what a launch returns and the loop pulls
is what the scheduler schedules with.

test_wave_matrix runs seven pod shapes, each on the path it exercises,
under both loop arms (pipelined waves, strict launch->commit alternation)
with the flight recorder's export on and off, all under a fixed tie seed:
every arm places every pod exactly as the shape's strict, export-off arm
does, an exported placement row names the bound node with a finite score
and nothing else of the launch, and the profiler's shape key keeps its
learned / feats / alts entries False."""

import json
import math
import time

import pytest

from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import PIPELINE_DEPTH, Scheduler, Wave
from kubernetes_tpu.testing import MakeNode, MakePod

pytestmark = pytest.mark.core

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
CAPS = Capacities(nodes=16, pods=256)


def mksched(hub, pipelined, export=None, batch=8):
    cfg = default_config()
    cfg.batch_size = batch
    cfg.pipelined_waves = pipelined
    cfg.tie_break_seed = 11
    cfg.trace_export_path = export
    cfg.pod_initial_backoff_seconds = 0.01
    cfg.pod_max_backoff_seconds = 0.05
    return Scheduler(hub, cfg, caps=CAPS)


def mkcluster(cpu="4"):
    """Eight nodes over four zones; the even ones carry disk=ssd."""
    hub = Hub()
    for i in range(8):
        node = (MakeNode().name(f"node-{i}").label(HOST, f"node-{i}")
                .label(ZONE, f"zone-{i % 4}")
                .capacity(cpu=cpu, memory="16Gi", pods="110"))
        if i % 2 == 0:
            node = node.label("disk", "ssd")
        hub.create_node(node.obj())
    return hub


def _pods(prefix, n, build):
    return [build(MakePod().name(f"{prefix}-{i}"), i).obj()
            for i in range(n)]


# shape -> (pods, (serial, soft) the launch runs on the CPU)
SHAPES = {
    "plain": (lambda: _pods("plain", 20, lambda p, i: p.req(
        cpu=f"{100 + 10 * i}m", memory="64Mi")), (False, False)),
    "spread_hard": (lambda: _pods("sh", 12, lambda p, i: p.label(
        "app", "sh").req(cpu="100m", memory="64Mi").spread_constraint(
        1, ZONE, "DoNotSchedule", {"app": "sh"})), (True, False)),
    "spread_soft": (lambda: _pods("ss", 12, lambda p, i: p.label(
        "app", "ss").req(cpu="100m", memory="64Mi").spread_constraint(
        1, ZONE, "ScheduleAnyway", {"app": "ss"})), (True, True)),
    "pod_affinity": (lambda: _pods("pa", 10, lambda p, i: p.label(
        "app", "pa").req(cpu="100m", memory="64Mi").pod_affinity(
        ZONE, {"app": "pa"})), (True, False)),
    "anti_affinity": (lambda: _pods("aa", 6, lambda p, i: p.label(
        "app", "aa").req(cpu="100m", memory="64Mi").pod_anti_affinity(
        HOST, {"app": "aa"})), (True, False)),
    "node_affinity": (lambda: _pods("na", 12, lambda p, i: p.req(
        cpu="200m", memory="64Mi").node_affinity_in("disk", ["ssd"])),
        (False, False)),
    # sixteen low pods fill the cluster; the preemptor needs one evicted
    "preemptor": (lambda: _pods("low", 16, lambda p, i: p.req(
        cpu="2", memory="256Mi").priority(0)), (False, False)),
}


def run_shape(shape, pipelined, export=None):
    """Drain the shape's pods; returns (name -> node, uid -> node, the
    profiler's shape keys, the scheduler's stats). The preemptor shape
    drains twice: the low pods, then the preemptor, which fails, evicts
    and binds."""
    hub = mkcluster()
    s = mksched(hub, pipelined, export)
    try:
        for p in SHAPES[shape][0]():
            hub.create_pod(p)
        s.run_until_idle()
        if shape == "preemptor":
            high = MakePod().name("high").req(
                cpu="1500m", memory="256Mi").priority(100).obj()
            hub.create_pod(high)
            s.run_until_idle()
            # the strict arm fires the eviction after its drain: the
            # preemptor then binds once its backoff has run out
            for _ in range(200):
                if hub.get_pod(high.metadata.uid).spec.node_name:
                    break
                time.sleep(0.01)
                s.run_until_idle()
        assert s.cache.compare_with_hub(hub) == []
        placed = {p.metadata.name: p.spec.node_name
                  for p in hub.list_pods()}
        uids = {p.metadata.uid: p.spec.node_name for p in hub.list_pods()}
        return placed, uids, list(s.profiler.shapes), dict(s.stats)
    finally:
        s.close()


_REFERENCE = {}


def reference(shape):
    """The shape's strict, export-off placements."""
    if shape not in _REFERENCE:
        _REFERENCE[shape] = run_shape(shape, pipelined=False)[0]
    return _REFERENCE[shape]


@pytest.mark.parametrize("export", [False, True], ids=["off", "export"])
@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "strict"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_wave_matrix(shape, pipelined, export, tmp_path):
    path = str(tmp_path / "cycles.jsonl") if export else None
    placed, uids, keys, stats = run_shape(shape, pipelined, path)
    if not pipelined and not export:
        _REFERENCE.setdefault(shape, placed)
    assert all(placed.values()), placed
    assert placed == reference(shape)
    if shape == "preemptor":
        assert stats["preemptions"] == 1 and "high" in placed
    assert keys
    for key in keys:
        d = dict(key)
        assert (d["learned"], d["feats"], d["alts"]) == (False,) * 3
    serial, soft = SHAPES[shape][1]
    assert any(dict(k)["serial"] == serial and dict(k)["soft"] == soft
               and not dict(k)["gang"] for k in keys), keys
    if not export:
        return
    rows = [r for line in open(path) if line.strip()
            for r in json.loads(line).get("placements", [])]
    for uid, node in uids.items():
        named = [r for r in rows if r["uid"] == uid and r["node"]]
        assert len(named) == 1 and named[0]["node"] == node, named
        assert math.isfinite(named[0]["score"])
        assert "feat" not in named[0] and "alt" not in named[0]


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "strict"])
def test_wave_depth_settles(pipelined):
    """trace.depth reads PIPELINE_DEPTH once the pipeline is full, and 1
    on every wave of the strict arm."""
    hub = mkcluster(cpu="32")
    s = mksched(hub, pipelined)
    try:
        for p in _pods("d", 48, lambda p, i: p.req(cpu="100m",
                                                   memory="64Mi")):
            hub.create_pod(p)
        s.run_until_idle()
        depths = [c["depth"] for c in s.flight.last(400) if c.get("depth")]
        assert len(depths) == 6
        if pipelined:
            assert depths == [1] + [PIPELINE_DEPTH] * 5
        else:
            assert depths == [1] * 6
    finally:
        s.close()


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "strict"])
def test_wave_whose_pull_raises_is_contained(pipelined):
    """A pull that raises (on the commit thread or inline) reaches the
    containment ladder with the wave's own runnable list, untouched, and
    every pod still binds through the host path."""
    hub = mkcluster(cpu="32")
    s = mksched(hub, pipelined)
    waves, contained = [], []
    dispatch, contain = s._dispatch, s._contain_batch_fault

    def spy_dispatch(*a, **kw):
        wave = dispatch(*a, **kw)
        if wave is not None:
            assert isinstance(wave, Wave)
            waves.append((wave, [qp.uid for qp in wave.runnable]))
        return wave

    def failing_pull(out, exporting):
        raise RuntimeError("pull failed")

    def spy_contain(runnable, exc):
        contained.append((runnable, exc))
        return contain(runnable, exc)

    s._dispatch = spy_dispatch
    s._pull_verdicts = failing_pull
    s._contain_batch_fault = spy_contain
    try:
        for p in _pods("f", 8, lambda p, i: p.req(cpu="100m",
                                                  memory="64Mi")):
            hub.create_pod(p)
        s.run_until_idle()
        assert len(waves) == 1 and len(contained) == 1
        (wave, uids), (runnable, exc) = waves[0], contained[0]
        assert runnable is wave.runnable
        assert [qp.uid for qp in runnable] == uids and len(uids) == 8
        assert str(exc) == "pull failed"
        assert all(p.spec.node_name for p in hub.list_pods())
        assert s.stats["device_fallbacks"] == 1
    finally:
        s.close()
