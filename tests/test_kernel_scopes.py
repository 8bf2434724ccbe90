"""jax.named_scope on the device kernels (PERF.md §7): each name reaches
the lowered HLO of the program it belongs to, so a profiler trace's
operation details can be grouped by kernel. Lowering only: nothing runs."""

import random
import re

import jax.numpy as jnp
import pytest

from kubernetes_tpu.api.objects import (
    LABEL_ZONE,
    Affinity,
    LabelSelector,
    NodeAffinity,
    NodeSelector,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    PodAffinity,
    PodAffinityTerm,
    PreferredSchedulingTerm,
)

from kubernetes_tpu.backend.mirror import _scatter_rows_jit
from kubernetes_tpu.models.pipeline import (
    KERNEL_SCOPES,
    _chain_add_rows_jit,
    _chain_set_rows_jit,
    default_weights,
    schedule_batch_jit,
)
from kubernetes_tpu.models.testbed import build_cluster, make_pod
from kubernetes_tpu.ops.features import Capacities

from tests.test_soft_auction import CAPS as SOFT_CAPS
from tests.test_soft_auction import build as build_soft
from tests.test_soft_auction import soft_pod

pytestmark = pytest.mark.core

CAPS = Capacities(nodes=64, pods=256)
BODY_SCOPES = ("scan_queries", "scan_map_updates")


def _scoped(name, text):
    """The scope is part of an operation's name stack below the jitted
    program: "jit(f)/name/op", or "jit(f)/vmap(name)/op" under a vmap. A
    scope inside the commit scan's body heads a name stack of its own
    there ("name/op"): the body is lowered as its own function, called
    from "jit(f)/commit_scan/while/body/jit(body)"."""
    if name in BODY_SCOPES:
        return re.search(rf'loc\("{name}/', text) is not None
    return re.search(rf'"jit\([^"]*[/(]{name}[/)]', text) is not None


def _lowered_text(spec, wk, caps, serial_scan):
    """schedule_batch_jit lowered with the arguments launch_batch gives
    it, locations (where the scope names live) included."""
    return schedule_batch_jit.lower(
        spec.cblobs, spec.pblobs, wk, default_weights(), caps,
        spec.enable_topology, spec.d_cap, None,
        serial_scan=serial_scan, state=None, active=spec.active,
        pfields=spec.pfields, ptmpl=spec.ptmpl, gid=spec.gid, rep=spec.rep,
        g_cap=spec.g_cap, dra=spec.dra,
        topo_soft=spec.topo_soft).as_text(debug_info=True)


def _plain_spec():
    _cache, _snap, mirror = build_cluster(16, caps=CAPS)
    return mirror.prepare_launch([make_pod(i) for i in range(8)], 8), \
        mirror.well_known(), CAPS


def _affinity_spec():
    """Pods with a required zone affinity term: the launch takes the
    topology programs, inter-pod affinity among them."""
    _cache, _snap, mirror = build_cluster(16, caps=CAPS)
    pods = [make_pod(i) for i in range(8)]
    for p in pods:
        p.metadata.uid = p.metadata.name
        p.spec.affinity = Affinity(pod_affinity=PodAffinity(required=[
            PodAffinityTerm(topology_key=LABEL_ZONE,
                            label_selector=LabelSelector(
                                match_labels={"app": "app-0"}))]))
    spec = mirror.prepare_launch(pods, 8)
    assert spec.enable_topology
    return spec, mirror.well_known(), CAPS


def _node_affinity_spec():
    """Pods with a required zone nodeAffinity term: the launch compiles the
    full node-affinity kernels (the `nodeaffinity` launch feature)."""
    _cache, _snap, mirror = build_cluster(16, caps=CAPS)
    pods = [make_pod(i) for i in range(8)]
    for p in pods:
        p.spec.affinity = Affinity(node_affinity=NodeAffinity(
            required=NodeSelector(node_selector_terms=[NodeSelectorTerm(
                match_expressions=[NodeSelectorRequirement(
                    key=LABEL_ZONE, operator="In",
                    values=["zone-0", "zone-1"])])]),
            preferred=[PreferredSchedulingTerm(
                weight=10, preference=NodeSelectorTerm(match_expressions=[
                    NodeSelectorRequirement(key=LABEL_ZONE, operator="In",
                                            values=["zone-1"])]))]))
    spec = mirror.prepare_launch(pods, 8)
    assert "nodeaffinity" in spec.active and not spec.enable_topology
    return spec, mirror.well_known(), CAPS


def _soft_spec():
    rng = random.Random(7)
    _table, _snap, mirror = build_soft(rng)
    pods = []
    for i in range(4):
        p = soft_pod(f"s{i}", rng)
        p.metadata.uid = f"s{i}"
        pods.append(p)
    spec = mirror.prepare_launch(pods, 4)
    assert spec.topo_soft
    return spec, mirror.well_known(), SOFT_CAPS


@pytest.mark.parametrize("make, serial_scan, scopes, absent", [
    (_plain_spec, False, ("static_filters", "auction_rounds"),
     ("commit_scan", "soft_topology_auction", "table_block",
      "node_affinity")),
    (_plain_spec, True, ("static_filters", "commit_scan"),
     ("auction_rounds", "scan_queries", "scan_map_updates", "table_block")),
    (_soft_spec, False, ("static_filters", "soft_topology_auction",
                         "table_block"),
     ("auction_rounds", "commit_scan")),
    (_affinity_spec, True, ("static_filters", "inter_pod_affinity",
                            "table_block", "commit_scan", "scan_queries",
                            "scan_map_updates"), ("auction_rounds",)),
    (_node_affinity_spec, False, ("static_filters", "node_affinity",
                                  "auction_rounds"),
     ("commit_scan", "soft_topology_auction", "table_block")),
])
def test_schedule_batch_kernels_carry_their_scope(make, serial_scan, scopes,
                                                  absent):
    spec, wk, caps = make()
    text = _lowered_text(spec, wk, caps, serial_scan)
    for name in scopes:
        assert _scoped(name, text), name
    for name in absent:
        assert not _scoped(name, text), name


def test_chain_and_mirror_scatters_carry_their_scope():
    free, nzr = jnp.zeros((8, 4)), jnp.zeros((8, 2))
    idx = jnp.zeros((2,), jnp.int32)
    rows = (jnp.zeros((2, 4)), jnp.zeros((2, 2)))
    for fn in (_chain_set_rows_jit, _chain_add_rows_jit):
        assert _scoped("patch_chain", fn.lower(
            free, nzr, idx, *rows).as_text(debug_info=True))
    assert _scoped("scatter_rows", _scatter_rows_jit.lower(
        free, idx, rows[0]).as_text(debug_info=True))
    assert set(KERNEL_SCOPES) == {
        "static_filters", "auction_rounds", "soft_topology_auction",
        "commit_scan", "patch_chain", "scatter_rows", "inter_pod_affinity",
        "scan_queries", "scan_map_updates", "table_block", "node_affinity"}
