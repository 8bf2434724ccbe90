"""Pipelined scheduling waves (ISSUE 19): A/B parity of the pipelined
arm against strict launch->commit alternation, chain-surviving churn,
off-thread commit containment, fused auction rounds, preemptor
next-wave activation, and the zero-recompile gate."""

import numpy as np

from kubernetes_tpu.chaos import DeviceChaos, DeviceChaosConfig
from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.models.pipeline import launch_cache_size
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import PIPELINE_DEPTH, Scheduler
from kubernetes_tpu.testing import MakeNode, MakePod


def mksched(hub, pipelined=True, batch=16, nodes=16, pods=256, seed=7):
    cfg = default_config()
    cfg.batch_size = batch
    cfg.pipelined_waves = pipelined
    cfg.tie_break_seed = seed
    return Scheduler(hub, cfg, caps=Capacities(nodes=nodes, pods=pods))


def mkcluster(n=8, cpu="32"):
    hub = Hub()
    for i in range(n):
        hub.create_node(MakeNode().name(f"node-{i}")
                        .capacity(cpu=cpu, memory="64Gi", pods="110").obj())
    return hub


def placements(hub):
    return {p.metadata.name: p.spec.node_name for p in hub.list_pods()}


# ---------------- A/B parity (satellite 4) ----------------


def test_pipelined_ab_parity_churn_free():
    """Identical placements on a churn-free workload under a fixed tie
    seed: the chain is the same state either way, only its lifetime
    differs between the pipelined and strict-alternation arms."""
    outs = []
    for pipelined in (True, False):
        hub = mkcluster()
        s = mksched(hub, pipelined=pipelined)
        try:
            for i in range(60):
                hub.create_pod(MakePod().name(f"p-{i}")
                               .req(cpu=f"{100 + i}m", memory="64Mi").obj())
            s.run_until_idle()
            outs.append(placements(hub))
        finally:
            s.close()
    assert outs[0] == outs[1]
    assert all(n is not None for n in outs[0].values())


def test_pipelined_ab_parity_under_churn():
    """Same churn sequence (foreign deletes + late arrivals between
    drains) lands identical placements whether the churn is folded into
    the live chain (patches) or invalidates it wholesale."""
    outs, stats = [], []
    for pipelined in (True, False):
        hub = mkcluster()
        s = mksched(hub, pipelined=pipelined)
        try:
            for i in range(40):
                hub.create_pod(MakePod().name(f"p-{i}")
                               .req(cpu="100m", memory="64Mi").obj())
            s.run_until_idle()
            victims = sorted((p for p in hub.list_pods()
                              if p.spec.node_name),
                             key=lambda p: p.metadata.name)[:6]
            for v in victims:
                hub.delete_pod(v.metadata.uid)
            for i in range(40, 72):
                hub.create_pod(MakePod().name(f"p-{i}")
                               .req(cpu="150m", memory="64Mi").obj())
            s.run_until_idle()
            outs.append(placements(hub))
            stats.append(dict(s.stats))
            assert s.cache.compare_with_hub(hub) == []
        finally:
            s.close()
    assert outs[0] == outs[1]
    # the pipelined arm actually exercised the patch path (the deletes
    # between drains are foreign-pod deltas scattered into the chain)
    assert stats[0]["chain_patches"] > 0
    assert stats[0]["chain_patch_rows"] > 0
    assert stats[1]["chain_patches"] == 0


# ---------------- pipeline depth (satellite 1) ----------------


def test_pipeline_depth_recovers_after_host_batch():
    """A non-chainable (host-port) batch mid-drain must not strand the
    pipeline shallow: depth returns to PIPELINE_DEPTH afterwards."""
    hub = mkcluster()
    s = mksched(hub, batch=8)
    try:
        for i in range(40):
            hub.create_pod(MakePod().name(f"a-{i}")
                           .req(cpu="100m", memory="64Mi").obj())
        # the host-port pod forces its batch through the snapshot-sync
        # (unchained) path
        hub.create_pod(MakePod().name("hp").req(cpu="100m", memory="64Mi")
                       .host_port(8080).obj())
        for i in range(40):
            hub.create_pod(MakePod().name(f"b-{i}")
                           .req(cpu="100m", memory="64Mi").obj())
        s.run_until_idle()
        depths = [c["depth"] for c in s.flight.last(400) if c.get("depth")]
        assert max(depths) == PIPELINE_DEPTH
        # find the stall (a dispatch that found the pipeline drained) and
        # demand full depth again afterwards
        shallow = [i for i, d in enumerate(depths) if d == 1]
        assert shallow, "expected at least the first dispatch at depth 1"
        assert any(d == PIPELINE_DEPTH
                   for d in depths[shallow[-1]:]), \
            "pipeline never refilled after the last shallow dispatch"
        assert all(p.spec.node_name for p in hub.list_pods())
    finally:
        s.close()


def test_off_arm_strict_alternation():
    """pipelined_waves=False commits every wave before the next
    dispatch: recorded depth never exceeds 1."""
    hub = mkcluster()
    s = mksched(hub, pipelined=False, batch=8)
    try:
        for i in range(40):
            hub.create_pod(MakePod().name(f"p-{i}")
                           .req(cpu="100m", memory="64Mi").obj())
        s.run_until_idle()
        depths = [c["depth"] for c in s.flight.last(400) if c.get("depth")]
        assert depths and max(depths) == 1
    finally:
        s.close()


def test_pipelined_commit_pull_attribution():
    """Host-tail attribution under pipelined waves (ISSUE 20 satellite):
    pipelined cycles book the commit thread's device pull as the
    overlapped "commit_pull" phase, device_launch carries only the loop
    thread's blocked wait, and the cycle total does not double-count
    the pull. The strict-alternation arm books no
    commit_pull at all (the pull runs inline inside device_launch)."""
    for pipelined in (True, False):
        hub = mkcluster()
        s = mksched(hub, pipelined=pipelined, batch=8)
        try:
            for i in range(48):
                hub.create_pod(MakePod().name(f"p-{i}")
                               .req(cpu="100m", memory="64Mi").obj())
            s.run_until_idle()
            cycles = [c for c in s.flight.last(400) if c.get("pods")]
            assert cycles
            pulled = [c for c in cycles
                      if "commit_pull" in c.get("phases_ms", {})]
            if not pipelined:
                assert not pulled
                continue
            # pipelined cycles past the first dispatch ride the chain
            assert pulled, "no pipelined cycle booked a commit_pull"
            for c in pulled:
                ph = c["phases_ms"]
                # the exported total sums the booked phases WITHOUT the
                # overlap (and without the dra_*/compile views)
                from kubernetes_tpu.utils.tracing import EXCLUDED_PHASES
                booked = sum(v for k, v in ph.items()
                             if k not in EXCLUDED_PHASES)
                # phases_ms round per-phase to 3 decimals, total_ms
                # rounds once — allow half-ulp per booked phase
                assert abs(c["total_ms"] - booked) < 0.0005 * (len(ph) + 1)
                assert ph["commit_pull"] >= 0.0
        finally:
            s.close()


# ---------------- zero-recompile gate (satellite 3) ----------------


def test_no_recompiles_in_steady_churn():
    """After a first drain warmed every bucket (including the chain-patch
    kernels), steady churn at the same batch buckets compiles nothing."""
    hub = mkcluster()
    s = mksched(hub, batch=16)
    try:
        for i in range(48):        # buckets: 16, 16, 16
            hub.create_pod(MakePod().name(f"w-{i}")
                           .req(cpu="100m", memory="64Mi").obj())
        s.run_until_idle()
        before = launch_cache_size()
        for rnd in range(3):
            victims = [p for p in hub.list_pods() if p.spec.node_name][:4]
            for v in victims:
                hub.delete_pod(v.metadata.uid)
            for i in range(16):    # one full bucket per round
                hub.create_pod(MakePod().name(f"c-{rnd}-{i}")
                               .req(cpu="100m", memory="64Mi").obj())
            s.run_until_idle()
        assert s.stats["chain_patches"] > 0
        assert launch_cache_size() == before, \
            "steady-state churn triggered a recompile"
    finally:
        s.close()


# ---------------- fused auction rounds (tentpole front 1) -------------


def test_auction_unroll_bit_identical():
    """The cond-gated unrolled auction body is bit-identical to the
    one-round-per-iteration loop (the body is idempotent at its fixed
    point, so over-stepping past convergence is a no-op)."""
    from kubernetes_tpu.models.pipeline import (
        extract_state_jit,
        schedule_batch_jit,
    )

    hub = mkcluster(n=6, cpu="8")
    s = mksched(hub, nodes=8, pods=64, batch=32)
    try:
        pods = [MakePod().name(f"p-{i}").req(cpu="900m", memory="64Mi")
                .obj() for i in range(30)]
        for p in pods:
            hub.create_pod(p)
        s.cache.update_snapshot(s.snapshot)
        s.mirror.sync(s.snapshot)
        spec = s.mirror.prepare_launch(pods, 32)
        pcfg = s._profile_cfg["default-scheduler"]
        state = extract_state_jit(spec.cblobs, s.caps)

        def run(unroll):
            return schedule_batch_jit(
                spec.cblobs, spec.pblobs, s.mirror.well_known(),
                pcfg["weights"], s.caps, spec.enable_topology, spec.d_cap,
                pcfg["filters"], serial_scan=False, state=state,
                active=spec.active, pfields=spec.pfields, ptmpl=spec.ptmpl,
                auction_unroll=unroll)

        o1, o4 = run(1), run(4)
        assert np.array_equal(np.asarray(o1.node_row),
                              np.asarray(o4.node_row))
        assert np.array_equal(np.asarray(o1.free), np.asarray(o4.free))
        assert np.array_equal(np.asarray(o1.nzr), np.asarray(o4.nzr))
        assert (np.asarray(o1.node_row) >= 0).sum() == len(pods)
    finally:
        s.close()


# ---------------- commit-thread containment (satellite 5) -------------


def test_commit_pull_fault_contained():
    """A commit-thread exception surfaces through the wave's future and
    takes the SAME _finish_contained ladder as an inline launch fault:
    every pod still binds exactly once, nothing is lost."""
    hub = mkcluster()
    s = mksched(hub)
    chaos = DeviceChaos(DeviceChaosConfig(seed=3,
                                          commit_pull_error_rate=0.5))
    s.fault_injector = chaos
    try:
        for i in range(48):
            hub.create_pod(MakePod().name(f"p-{i}")
                           .req(cpu="100m", memory="64Mi").obj())
        s.run_until_idle()
        assert chaos.stats["injected_pull_errors"] > 0
        assert s.stats["device_fallbacks"] > 0
        pods = hub.list_pods()
        assert len(pods) == 48
        assert all(p.spec.node_name for p in pods)
        assert s.cache.compare_with_hub(hub) == []
    finally:
        s.close()


# ---------------- preemptor next-wave activation (front 4) ------------


def test_preemptor_rides_next_wave():
    """After the eviction flush fires, the preemptor is activated and
    binds within the SAME drain — no backoff wait into a later one."""
    hub = Hub()
    for i in range(2):
        hub.create_node(MakeNode().name(f"node-{i}")
                        .capacity(cpu="2", memory="32Gi", pods="110").obj())
    s = mksched(hub, nodes=16, pods=64)
    try:
        for i in range(4):
            hub.create_pod(MakePod().name(f"low-{i}")
                           .req(cpu="1", memory="256Mi").priority(0).obj())
        s.run_until_idle()
        assert s.stats["scheduled"] == 4
        high = MakePod().name("high").req(cpu="1500m", memory="256Mi") \
            .priority(100).obj()
        hub.create_pod(high)
        s.run_until_idle()
        hp = hub.get_pod(high.metadata.uid)
        assert hp.spec.node_name in ("node-0", "node-1")
        assert s.stats["preemptions"] == 1
    finally:
        s.close()


# suite-tier discipline (tests/test_markers.py): area marker
import pytest  # noqa: E402
pytestmark = pytest.mark.core
