"""Batched pipeline: as-if-serial commit semantics + driver entry points.

The key property (SURVEY.md §7.2 hard part 2): scheduling a batch in one
launch must produce the same placements as running the serial loop pod by
pod with an assume between pods (schedule_one.go:65 comment)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.models.pipeline import (
    FILTER_PLUGINS,
    default_weights,
    schedule_batch_jit,
)
from kubernetes_tpu.models.testbed import build_cluster, make_pod
from kubernetes_tpu.ops.features import Capacities

CAPS = Capacities(nodes=16, pods=64)


def _run(mirror, pods, batch=8):
    return schedule_batch_jit(mirror.to_blobs(),
                              mirror.pack_batch_blobs(pods, batch),
                              mirror.well_known(), default_weights(), CAPS)


def test_batch_places_all_when_space():
    _, snap, mirror = build_cluster(4, caps=CAPS)
    pods = [make_pod(i) for i in range(6)]
    out = _run(mirror, pods)
    rows = np.asarray(out.node_row)
    assert (rows[:6] >= 0).all()
    assert (rows[6:] == -1).all()  # padding rows stay unscheduled
    assert (np.asarray(out.feasible_count)[:6] == 4).all()


def test_in_batch_resource_exhaustion():
    """Nodes fit exactly one big pod each: the batch must spread, and the
    (n+1)th big pod must be unschedulable — proves pod b sees pod b-1's
    commit inside one launch."""
    _, snap, mirror = build_cluster(3, caps=CAPS)
    pods = [make_pod(i, cpu="20", mem="100Gi") for i in range(4)]  # node: 32 cpu
    out = _run(mirror, pods)
    rows = np.asarray(out.node_row)[:4]
    assert (rows[:3] >= 0).all()
    assert len(set(rows[:3].tolist())) == 3, "one big pod per node"
    assert rows[3] == -1, "fourth big pod must not fit anywhere"
    # first-fail attribution: rejected by NodeResourcesFit
    fit_idx = FILTER_PLUGINS.index("NodeResourcesFit")
    assert np.asarray(out.reject_counts)[3, fit_idx] == 3


def test_in_batch_host_port_conflict():
    """Two pods with the same hostPort in one batch must not co-locate
    (as-if-serial NodePorts, types.go:1291)."""
    from kubernetes_tpu.api.objects import Container, ContainerPort

    _, snap, mirror = build_cluster(2, caps=CAPS)
    pods = []
    for i in range(3):
        p = make_pod(i)
        p.spec.containers[0].ports = [ContainerPort(host_port=8080)]
        pods.append(p)
    out = _run(mirror, pods)
    rows = np.asarray(out.node_row)[:3]
    assert rows[0] >= 0 and rows[1] >= 0
    assert rows[0] != rows[1], "same hostPort pods must spread"
    assert rows[2] == -1, "third pod: both nodes' port taken in-batch"
    ports_idx = FILTER_PLUGINS.index("NodePorts")
    assert np.asarray(out.reject_counts)[2, ports_idx] == 2


def test_matches_serial_oracle():
    """One launch over B pods == B launches of batch-size-1 with host-side
    re-sync between them."""
    pods = [make_pod(i, cpu="3", mem="1Gi") for i in range(10)]

    _, _, mirror = build_cluster(5, caps=CAPS)
    batched = np.asarray(_run(mirror, pods, batch=16).node_row)[:10]

    cache2, snap2, mirror2 = build_cluster(5, caps=CAPS)
    serial = []
    for p in pods:
        out = _run(mirror2, [p], batch=1)
        row = int(out.node_row[0])
        serial.append(row)
        if row >= 0:
            name = mirror2.name_of_row(row)
            p2 = p.clone()
            p2.spec.node_name = name
            cache2.assume_pod(p2)
            cache2.update_snapshot(snap2)
            mirror2.sync(snap2)
    assert batched.tolist() == serial


def test_graft_entry_compiles():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    assert (np.asarray(out.node_row) >= 0).all()


def test_dryrun_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(min(8, len(jax.devices())))


def test_pct_nodes_to_score_knob():
    """percentageOfNodesToScore (schedule_one.go:668-694): with the knob
    set, selection happens among a rotating feasible subset; with it unset
    (or >=100) all nodes are scored. At small clusters the
    minFeasibleNodesToFind=100 floor keeps the knob a no-op."""
    caps = Capacities(nodes=256, pods=64)
    _, snap, mirror = build_cluster(200, caps=caps)
    pods = [make_pod(i) for i in range(8)]
    cb = mirror.to_blobs()
    pb = mirror.pack_batch_blobs(pods, 8)
    wk = mirror.well_known()
    w = default_weights()
    full = schedule_batch_jit(cb, pb, wk, w, caps)
    # floor: 200 * 50% = 100 = minFeasibleNodesToFind, but all 200 nodes
    # are feasible so the window truncates to the first 100 visited
    capped = schedule_batch_jit(cb, pb, wk, w, caps, pct_nodes=50)
    rows_f = np.asarray(full.node_row)
    rows_c = np.asarray(capped.node_row)
    assert (rows_c >= 0).all(), "capped run must still place every pod"
    # the capped run only ever reports <= k feasible nodes
    assert (np.asarray(capped.feasible_count) <= 100).all()
    assert (np.asarray(full.feasible_count) == 200).all()
    # explicit 0 = the reference's ADAPTIVE percentage (49% at 200 nodes
    # -> k=max(100, 98)=100): truncates exactly like pct=50 here
    from kubernetes_tpu.models.pipeline import ADAPTIVE_PCT
    adaptive = schedule_batch_jit(cb, pb, wk, w, caps,
                                  pct_nodes=ADAPTIVE_PCT)
    assert (np.asarray(adaptive.feasible_count) <= 100).all()
    # pct=100 never truncates: byte-identical placements to the default
    same = schedule_batch_jit(cb, pb, wk, w, caps, pct_nodes=100)
    np.testing.assert_array_equal(rows_f, np.asarray(same.node_row))
    np.testing.assert_array_equal(np.asarray(full.feasible_count),
                                  np.asarray(same.feasible_count))


def test_pct_nodes_rotates_start_index():
    """The visit window advances between pods (nextStartNodeIndex,
    schedule_one.go:620): with k=100 over 200 identical feasible nodes,
    consecutive pods must not all pick from the same leading window."""
    caps = Capacities(nodes=256, pods=64)
    _, snap, mirror = build_cluster(200, caps=caps)
    pods = [make_pod(i) for i in range(8)]
    out = schedule_batch_jit(mirror.to_blobs(),
                            mirror.pack_batch_blobs(pods, 8),
                            mirror.well_known(), default_weights(), caps,
                            pct_nodes=50)
    rows = np.asarray(out.node_row)
    # pod 0 picks inside nodes [0,100); pod 1's window starts at 100
    assert rows[0] < 100
    assert rows[1] >= 100
    # windows alternate [0,100) / [100,200) for the whole batch, and the
    # rotation wraps over the 200 REAL nodes (not the 256-row padded
    # bucket): 8 pods x 100 processed -> nextStartNodeIndex back at 0
    assert all(r < 100 for r in rows[0::2])
    assert all(r >= 100 for r in rows[1::2])
    assert int(out.pct_start) == 0


def test_pct_nodes_start_carries_across_launches():
    """The rotation survives ACROSS launches via BatchResult.pct_start (the
    Scheduler's persistent nextStartNodeIndex, schedule_one.go:620): a
    launch seeded with a prior launch's final offset opens its first
    window there, not at node 0. 150 valid nodes / k=100 makes the seeded
    window [start, start+100) unambiguous."""
    caps = Capacities(nodes=256, pods=64)
    _, snap, mirror = build_cluster(150, caps=caps)
    pods = [make_pod(i) for i in range(8)]
    cb = mirror.to_blobs()
    pb = mirror.pack_batch_blobs(pods, 8)
    wk = mirror.well_known()
    w = default_weights()
    out = schedule_batch_jit(cb, pb, wk, w, caps, pct_nodes=50)
    start1 = int(out.pct_start)
    assert start1 > 0
    out2 = schedule_batch_jit(cb, pb, wk, w, caps, pct_nodes=50,
                              pct_start=out.pct_start)
    rows2 = np.asarray(out2.node_row)
    # pod 0's window is the 100 feasible nodes visited from start1; when
    # that window doesn't wrap (start1 <= 50) every candidate is >= start1
    if start1 <= 50:
        assert rows2[0] >= start1, (start1, rows2[0])
    # and the seeded trajectory ends at a different offset
    assert int(out2.pct_start) != start1


# ---- the scan's length follows the batch (PR 35) ----
#
# The commit scan runs whole blocks of scan_unroll() steps up to the last
# row that carries a pod and stops. A step on a padding row changes no
# carry, so the same pods must land the same way whatever the width of the
# program they ride in, and BatchResult.scan_steps must read what
# scan_steps_for() computes on the host.

import pytest  # noqa: E402

from kubernetes_tpu.api.objects import (  # noqa: E402
    LABEL_HOSTNAME,
    LABEL_ZONE,
    Affinity,
    ContainerPort,
    LabelSelector,
    PodAffinityTerm,
    PodAntiAffinity,
    TopologySpreadConstraint,
)
from kubernetes_tpu.backend.mirror import Mirror  # noqa: E402
from kubernetes_tpu.backend.snapshot import Snapshot  # noqa: E402
from kubernetes_tpu.models.pipeline import (  # noqa: E402
    extract_state_jit,
    launch_batch,
    scan_steps_for,
    scan_unroll,
    table_blocks_for,
)
from kubernetes_tpu.ops.features import PodBlobs  # noqa: E402

WIDTHS = (16, 64, 1024)
SCAN_CAPS = Capacities(nodes=16, pods=64, domains=16)
PCT_CAPS = Capacities(nodes=256, pods=64)


def _same_pod(i):
    """Pods of one Deployment: they differ in name and uid alone, so a
    launch of any size has the same topology groups (and one program a
    width serves every size)."""
    p = make_pod(i)
    p.metadata.uid = p.metadata.name
    p.metadata.labels = {"app": "s"}
    p.spec.containers[0].image = "img-0"
    return p


def _green(i, ns="sched-1"):
    """The anti-affinity cell's pod: green, and no green pod of either
    namespace on its node (benchmark/templates/pod-with-pod-anti-affinity)."""
    p = _same_pod(i)
    p.metadata.name = p.metadata.uid = f"green-{ns}-{i}"
    p.metadata.namespace = ns
    p.metadata.labels = {"color": "green"}
    p.spec.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(required=[
        PodAffinityTerm(topology_key=LABEL_HOSTNAME,
                        namespaces=["sched-1", "sched-0"],
                        label_selector=LabelSelector(
                            match_labels={"color": "green"}))]))
    return p


def _anti_affinity_case(n, caps=SCAN_CAPS):
    """16 nodes, six of them already hold a green pod: ten are left, so of
    13 pods three find every node forbidden, by the table or by an earlier
    pod of their own launch."""
    cache, snap, mirror = build_cluster(16, caps=caps)
    for i in range(6):
        init = _green(i, ns="sched-0")
        init.spec.node_name = f"node-{2 * i}"
        cache.add_pod(init)
    cache.update_snapshot(snap)
    mirror.sync(snap)
    return mirror, [_green(i) for i in range(n)], caps, {}


def _zone_spread_case(n, caps=SCAN_CAPS, when="DoNotSchedule"):
    """12 nodes in 3 zones, maxSkew 1 over the zone, and the third zone's
    nodes are full: its count stays 0, so the first two pods take a zone
    each and every later one is held off by the commits before it."""
    cache, snap, mirror = build_cluster(12, caps=caps, zones=3)
    for i in range(2, 12, 3):
        full = make_pod(100 + i, cpu="32", mem="1Gi")
        full.metadata.uid = full.metadata.name
        full.spec.node_name = f"node-{i}"
        cache.add_pod(full)
    cache.update_snapshot(snap)
    mirror.sync(snap)
    pods = []
    for i in range(n):
        p = _same_pod(i)
        p.spec.topology_spread_constraints = [TopologySpreadConstraint(
            max_skew=1, topology_key=LABEL_ZONE, when_unsatisfiable=when,
            label_selector=LabelSelector(match_labels={"app": "s"}))]
        pods.append(p)
    return mirror, pods, caps, {}


def _soft_spread_case(n, caps=SCAN_CAPS):
    """The zone spread as ScheduleAnyway: a score, not a filter, so the
    launch takes the soft-topology auction (topology-5k.preferred's)."""
    mirror, pods, caps, _ = _zone_spread_case(n, caps, "ScheduleAnyway")
    return mirror, pods, caps, {"serial_scan": False}


def _host_ports_case(n):
    """No topology, three host ports over four nodes: the serial scan is
    what keeps two pods of one port apart, and the 13th pod has no node."""
    _, _, mirror = build_cluster(4, caps=SCAN_CAPS)
    pods = []
    for i in range(n):
        p = _same_pod(i)
        p.spec.containers[0].ports = [ContainerPort(host_port=8080 + i % 3)]
        pods.append(p)
    return mirror, pods, SCAN_CAPS, {}


def _pct_nodes_case(n):
    """percentageOfNodesToScore 50 over 200 nodes: the rotating start is a
    carry of its own, and a padding step must leave it where it was."""
    _, _, mirror = build_cluster(200, caps=PCT_CAPS)
    return mirror, [_same_pod(i) for i in range(n)], PCT_CAPS, \
        {"pct_nodes": 50}


# the case's builder, and how many pods find a node there
SCAN_CASES = {"anti_affinity_hostname": (_anti_affinity_case, 10),
              "zone_spread": (_zone_spread_case, 2),
              "host_ports": (_host_ports_case, 12),
              "pct_nodes": (_pct_nodes_case, 13)}


def _launch(mirror, pods, width, caps, **kw):
    return launch_batch(mirror.prepare_launch(pods, width),
                        mirror.well_known(), default_weights(), caps, **kw)


@pytest.mark.parametrize("n", [1, scan_unroll() - 1, scan_unroll(),
                               scan_unroll() + 1, 13])
@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_placements_do_not_depend_on_the_launch_width(case, n):
    build, room = SCAN_CASES[case]
    mirror, pods, caps, kw = build(n)
    outs = [_launch(mirror, pods, w, caps, **kw) for w in WIDTHS]
    u = scan_unroll()
    for w, out in zip(WIDTHS, outs):
        assert int(out.scan_steps) == min(-(-n // u) * u, w) \
            == scan_steps_for(n, w), (w, int(out.scan_steps))
        assert (np.asarray(out.node_row)[n:] == -1).all()
    ref = outs[0]
    rows = np.asarray(ref.node_row)[:n]
    placed = rows[rows >= 0]
    assert len(placed) == min(n, room), rows
    if case == "anti_affinity_hostname":
        assert len(set(placed.tolist())) == len(placed)
        assert not set(placed.tolist()) & {
            mirror.row_of(f"node-{2 * i}") for i in range(6)}
    for out in outs[1:]:
        for field in ("node_row", "score", "feasible_count",
                      "reject_counts", "unresolvable_count"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, field))[:n],
                np.asarray(getattr(out, field))[:n], err_msg=field)
        for field in ("free", "nzr", "pct_start", "guard"):
            np.testing.assert_array_equal(np.asarray(getattr(ref, field)),
                                          np.asarray(getattr(out, field)),
                                          err_msg=field)


def test_scan_steps_of_a_full_batch_is_its_width():
    mirror, pods, caps, _ = _anti_affinity_case(16)
    out = _launch(mirror, pods, 16, caps)
    assert int(out.scan_steps) == 16 == scan_steps_for(16, 16)
    rows = np.asarray(out.node_row)
    assert (rows[:10] >= 0).all() and (rows[10:] == -1).all()


def test_an_empty_batch_runs_no_scan_step():
    """No row carries a pod (the mirror refuses to pack such a batch; a
    direct caller can hand one in): the loop's trip count is 0 and every
    row reads what a padding step writes."""
    _, _, mirror = build_cluster(3, caps=CAPS)
    pb = mirror.pack_batch_blobs([make_pod(0)], 8)
    pb = PodBlobs(f32=pb.f32.at[:].set(0), i32=pb.i32.at[:].set(0))
    cb = mirror.to_blobs()
    out = schedule_batch_jit(cb, pb, mirror.well_known(), default_weights(),
                             CAPS)
    assert int(out.scan_steps) == 0 == scan_steps_for(0, 8)
    assert (np.asarray(out.node_row) == -1).all()
    # the four plugins the scan attributes to: no step ran, none counted
    assert not np.asarray(out.reject_counts)[
        :, FILTER_PLUGINS.index("NodePorts"):].any()
    assert not np.asarray(out.feasible_count).any()
    free0, nzr0 = extract_state_jit(cb, CAPS)
    np.testing.assert_array_equal(np.asarray(out.free), np.asarray(free0))
    np.testing.assert_array_equal(np.asarray(out.nzr), np.asarray(nzr0))


def test_an_auction_launch_runs_no_scan_step():
    _, _, mirror = build_cluster(4, caps=SCAN_CAPS)
    out = _launch(mirror, [make_pod(i) for i in range(5)], 16, SCAN_CAPS,
                  serial_scan=False)
    assert int(out.scan_steps) == 0
    assert (np.asarray(out.node_row)[:5] >= 0).all()


def test_a_hole_in_the_valid_rows_is_stepped_over_not_cut_off():
    """Rows 0 and 5 carry a pod, 1 to 4 are padding: the scan runs to the
    last row that carries one (two blocks at unroll 4) and places both."""
    _, _, mirror = build_cluster(3, caps=CAPS)
    pods = [make_pod(i, cpu="20", mem="100Gi") for i in range(6)]
    pb = mirror.pack_batch_blobs(pods, 16)
    pb = PodBlobs(f32=pb.f32.at[1:5].set(0), i32=pb.i32.at[1:5].set(0))
    out = schedule_batch_jit(mirror.to_blobs(), pb, mirror.well_known(),
                             default_weights(), CAPS)
    rows = np.asarray(out.node_row)
    assert rows[0] >= 0 and rows[5] >= 0 and rows[0] != rows[5]
    assert (np.delete(rows, [0, 5]) == -1).all()
    assert int(out.scan_steps) == scan_steps_for(6, 16)


@pytest.mark.parametrize("width, n, steps", [
    (6, 6, 6),      # a full batch of one whole block and a part of one
    (6, 3, 4),      # one block
    (6, 5, 6),      # the second block ends with the batch, not past it
    (2, 2, 2),      # a batch shorter than a block
    (2, 1, 2),
])
def test_a_width_that_is_no_multiple_of_the_unroll(width, n, steps):
    """Direct callers only: the host's buckets are powers of two. One big
    pod a node, so every pod's node shows that it saw the commits before."""
    assert scan_unroll() == 4
    _, _, mirror = build_cluster(5, caps=CAPS)
    pods = [make_pod(i, cpu="20", mem="100Gi") for i in range(n)]
    out = _run(mirror, pods, batch=width)
    rows = np.asarray(out.node_row)
    assert rows.shape == (width,)
    assert len(set(rows[:min(n, 5)].tolist())) == min(n, 5)
    assert (rows[:min(n, 5)] >= 0).all() and (rows[5:] == -1).all()
    assert (rows[n:] == -1).all()
    assert int(out.scan_steps) == steps == scan_steps_for(n, width)
    wide = np.asarray(_run(mirror, pods, batch=16).node_row)
    np.testing.assert_array_equal(rows[:n], wide[:n])


# suite-tier discipline (tests/test_markers.py): area marker
pytestmark = pytest.mark.core


# ---- phase 1b reads the pod table up to its last live slot (PR 38) ----
#
# The table passes run over blocks of the table and stop after the last
# block that holds a live slot: the same cluster and pods in a table of 64
# and of 256 slots (blocks of 4 and of 16) land the same way, and
# BatchResult.table_blocks reads what table_blocks_for() computes on the
# host from the mirror's highest slot in use.

TABLE_CASES = {"anti_affinity_hostname": _anti_affinity_case,
               "zone_spread": _zone_spread_case,
               "soft_spread": _soft_spread_case}


@pytest.mark.parametrize("case", sorted(TABLE_CASES))
def test_placements_do_not_depend_on_the_table_size(case):
    outs = []
    for pt in (64, 256):
        mirror, pods, caps, kw = TABLE_CASES[case](
            13, dataclasses.replace(SCAN_CAPS, pods=pt))
        spec = mirror.prepare_launch(pods, 16)
        assert spec.enable_topology and spec.table_hi == mirror.slots_hi
        assert 0 < spec.table_hi <= 6        # the init pods' slots
        out = launch_batch(spec, mirror.well_known(), default_weights(),
                           caps, **kw)
        assert int(out.table_blocks) == table_blocks_for(spec.table_hi, pt) \
            == -(-spec.table_hi // (pt // 16)), (pt, int(out.table_blocks))
        outs.append(out)
    ref, big = outs
    assert (np.asarray(ref.node_row)[:13] >= 0).any()
    for field in ("node_row", "score", "feasible_count", "reject_counts",
                  "unresolvable_count", "free", "nzr", "guard"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, field)),
                                      np.asarray(getattr(big, field)),
                                      err_msg=field)


@pytest.mark.parametrize("serial_scan", [True, False])
def test_a_launch_without_topology_reads_no_table_block(serial_scan):
    """Plain pods over a table that holds plain pods: no topology, so
    phase 1b and its table passes compile out."""
    cache, snap, mirror = build_cluster(4, caps=SCAN_CAPS)
    for i in range(3):
        bound = _same_pod(100 + i)
        bound.spec.node_name = f"node-{i}"
        cache.add_pod(bound)
    cache.update_snapshot(snap)
    mirror.sync(snap)
    assert mirror.slots_hi == 3
    pods = [_same_pod(i) for i in range(5)]
    out = _launch(mirror, pods, 16, SCAN_CAPS, serial_scan=serial_scan)
    assert int(out.table_blocks) == 0
    assert (np.asarray(out.node_row)[:5] >= 0).all()
