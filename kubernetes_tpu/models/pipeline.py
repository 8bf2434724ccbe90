"""The flagship model: one XLA launch schedules a whole batch of pods.

This replaces the reference's serial per-pod hot path — ``schedulingCycle`` →
``findNodesThatPassFilters`` (goroutine fan-out over nodes,
schedule_one.go:583-650) → ``prioritizeNodes`` (3-stage score pipeline,
runtime/framework.go:1117-1194) → ``selectHost`` (schedule_one.go:865) →
``assume`` (schedule_one.go:938) — with a single jitted program in two
phases:

1. **Parallel phase** (vmap over the pod batch): every Filter and raw Score
   whose result cannot be changed by in-batch placements — taints, node
   affinity/selectors, host ports, unschedulable, image locality — is
   evaluated for ALL (pod, node) pairs at once. This is where the FLOPs
   are, and it is embarrassingly parallel over both axes.
2. **Commit scan** (a loop over the batch's rows): a deliberately tiny
   sequential pass that re-evaluates only what a previous pod's commit can
   invalidate — the resource fit predicate and the utilization scores —
   then normalizes, aggregates, argmaxes, and commits the winner's
   resources to the scan carry. Pod b+1 therefore sees pod b's placement
   exactly as the serial loop's assume step would provide ("as-if-serial").
   The scan's length follows the batch, not the bucket it was compiled at:
   it runs blocks of ``scan_unroll()`` steps up to the last row that
   carries a pod and stops (``BatchResult.scan_steps``), so a launch of 13
   pods in a 1,024-row program pays for 16 steps. A padding step changes
   no carry, so the placements are those of the full-length scan.

Between the two, phase 1b reads the pod table once a topology group: the
table's passes run over blocks of it up to the last block that holds a
live slot (``ops.topology.fold_table``, ``BatchResult.table_blocks``), so
their cost follows the pods in the table, not its capacity.

The node axis is the sharding axis: under a ``jax.sharding.Mesh`` the
per-node work is data-parallel; argmax and normalization reductions become
XLA collectives over ICI (SURVEY.md §5.8).

Filter order follows the reference's default plugin order
(apis/config/v1/default_plugins.go:30-58); a node's rejection is attributed
to its FIRST failing plugin, mirroring RunFilterPlugins' short-circuit
(runtime/framework.go:877-922) so Diagnosis/FitError parity holds.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import common as C
from kubernetes_tpu.ops import filters as FL
from kubernetes_tpu.ops import scores as SC
from kubernetes_tpu.ops import topology as T
from kubernetes_tpu.ops.topology import table_blocks_for  # noqa: F401
from kubernetes_tpu.utils.interner import NONE
from kubernetes_tpu.ops.features import (
    Capacities,
    ClusterBlobs,
    ClusterTensors,
    PodBlobs,
    PodFeatures,
    unpack_cluster,
    unpack_pods,
)

# --- filter plugin order (first-fail attribution; default_plugins.go) ---

FILTER_PLUGINS = (
    "NodeUnschedulable",
    "NodeName",
    "TaintToleration",
    "NodeAffinity",
    "NodePorts",
    "NodeResourcesFit",
    "PodTopologySpread",
    "InterPodAffinity",
)
NUM_FILTER_PLUGINS = len(FILTER_PLUGINS)

# --- score plugin set with default weights (default_plugins.go:30-58) ---

SCORE_PLUGINS = (
    "TaintToleration",            # w=3, inverse-normalized
    "NodeAffinity",               # w=2, max-normalized
    "NodeResourcesFit",           # w=1, least-allocated 0..100
    "NodeResourcesBalancedAllocation",  # w=1, 0..100
    "ImageLocality",              # w=1, 0..100
    "PodTopologySpread",          # w=2, spread-normalized
    "InterPodAffinity",           # w=2, max-min-normalized
)

# default HardPodAffinityWeight (apis/config/v1/defaults.go)
HARD_POD_AFFINITY_WEIGHT = 1.0

# phase-1 (parallel Filter/Score) sub-batch size: bounds the transient
# [chunk, selector-capacity, N] gather footprint for giant drain batches
PHASE1_CHUNK = 1024

# commit-scan unroll factor (the steps in one block of the scan's loop, see
# schedule_batch): amortizes per-iteration dispatch overhead, which
# dominates the topology scan at these shapes.
# 16 on TPU (+15-25% on the topology workloads on the round-5 rig; the
# full-width [2048 x 8192] program compiles and runs at 16 on a v5e —
# chip_smoke.py leg B); 4 on CPU, where the only effect of a bigger body
# is slower XLA:CPU compiles. Resolved LAZILY at first trace via the real
# backend (no JAX init at import).
_SCAN_UNROLL = None


def scan_unroll() -> int:
    global _SCAN_UNROLL
    if _SCAN_UNROLL is None:
        _SCAN_UNROLL = 4 if jax.default_backend() == "cpu" else 16
    return _SCAN_UNROLL


def _scan_block(b: int) -> int:
    """Steps in one block of the commit scan for a batch bucket of ``b``
    rows: scan_unroll(), or the whole batch where that is shorter."""
    return min(scan_unroll(), b)


def scan_steps_for(n_rows: int, b: int) -> int:
    """The commit-scan steps a serial launch runs (BatchResult.scan_steps,
    as host arithmetic): whole blocks up to row ``n_rows`` (1 + the index
    of the last row that carries a pod; pods are packed as a prefix, so
    the number of pods), never past the batch bucket ``b``."""
    u = _scan_block(b)
    return min(-(-n_rows // u) * u, b)


# auction-round unroll factor (see _rounds_commit): how many K-accept
# rounds one while_loop iteration fuses. The loop condition is
# data-dependent, so every iteration costs a device round trip on the
# progress flag; fusing U rounds into the body cuts that U-fold while
# lax.cond skips the work of rounds past convergence (the body is
# idempotent at its fixed point, so an extra executed round is a no-op).
# Auctions converge in a handful of rounds, so a small U covers most
# drains in ONE iteration.
AUCTION_UNROLL = 4

# minFeasibleNodesToFind (schedule_one.go:39-45): below this cluster-wide
# feasible count the percentageOfNodesToScore early-exit never truncates
MIN_FEASIBLE_NODES_TO_FIND = 100

# pct_nodes sentinel: config percentageOfNodesToScore == 0, meaning the
# reference's ADAPTIVE percentage (50 - nodes/125, min 5) rather than a
# fixed one. Unset (None) stays "score everything" — the TPU-native default.
ADAPTIVE_PCT = -1


@jax.tree_util.register_dataclass
@dataclass
class ScoreWeights:
    """Per-plugin score weights (scorePluginWeight, runtime/framework.go:57).
    A dynamic arg — changing weights does not recompile."""

    taint_toleration: jax.Array
    node_affinity: jax.Array
    resources_fit: jax.Array
    balanced_allocation: jax.Array
    image_locality: jax.Array
    pod_topology_spread: jax.Array
    inter_pod_affinity: jax.Array


def default_weights() -> ScoreWeights:
    return ScoreWeights(
        taint_toleration=jnp.float32(3.0),
        node_affinity=jnp.float32(2.0),
        resources_fit=jnp.float32(1.0),
        balanced_allocation=jnp.float32(1.0),
        image_locality=jnp.float32(1.0),
        pod_topology_spread=jnp.float32(2.0),
        inter_pod_affinity=jnp.float32(2.0),
    )


DEFAULT_WEIGHTS = default_weights


@jax.tree_util.register_dataclass
@dataclass
class BatchResult:
    """Per-pod outcome of one batched launch.

    ``free``/``nzr`` are the post-batch cluster usage state ([N, R] and
    [N, 2]): the device-resident "assume" ledger. Feeding them to the next
    launch's ``state`` arg chains batches without a host->device mirror
    re-sync round trip in between (the batched analog of the assume step
    keeping the cache hot between cycles, cache.go:361)."""

    node_row: jax.Array        # [B] i32: chosen node row, -1 = unschedulable
    score: jax.Array           # [B] f32: winning aggregate score
    feasible_count: jax.Array  # [B] i32: nodes passing all filters
    reject_counts: jax.Array   # [B, P] i32: nodes rejected per plugin (first-fail)
    unresolvable_count: jax.Array  # [B] i32: nodes where fit can never succeed
    free: jax.Array            # [N, R] f32: post-batch free resources
    nzr: jax.Array             # [N, 2] f32: post-batch nonzero-requested
    # [] i32: post-batch rotating visit offset (nextStartNodeIndex,
    # schedule_one.go:620). Feed to the next launch's ``pct_start`` so the
    # percentageOfNodesToScore window keeps rotating ACROSS batches, not
    # just within one. Always a concrete scalar (0 when the knob is off) so
    # the pytree structure is launch-config independent.
    pct_start: jax.Array
    # [] i32 guard bitmask, the device-side poison detector: bit 0 = NaN
    # in the winning scores, bit 1 = NaN in the post-batch free state
    # (which would poison the usage chain and every chained launch after
    # it). A cheap reduction computed on device; the scheduler pulls it
    # with node_row and degrades the batch to the host path when set.
    guard: jax.Array
    # [B] i32: nodes rejected by the fused DRA device allocator (first-
    # fail after the static filters; zeros when the launch carried no
    # DraBatch). Pulled only on failure — the scheduler folds it into
    # the pod's host_reject_counts under "DynamicResources" so diagnosis
    # and requeue hints match the host filter path exactly.
    dra_reject: jax.Array
    # [] i32: commit-scan steps this launch ran (whole blocks of
    # scan_unroll() up to the last row that carries a pod; 0 on the
    # auction path, which has no scan). scan_steps_for() is the same
    # number on the host; nobody pulls this one.
    scan_steps: jax.Array
    # [] i32: pod-table blocks phase 1b's passes ran (ops.topology
    # .fold_table: whole blocks up to the last live slot; 0 on a launch
    # without topology). table_blocks_for() is the same number on the
    # host; nobody pulls this one either.
    table_blocks: jax.Array


# workload-activity flags (STATIC, host-derived per launch by
# Mirror.launch_features): a feature absent from both the batch and the
# cluster mirror compiles to an all-pass mask / zero score — XLA dead-code-
# eliminates the whole kernel. The device analog of PreFilter returning
# Skip for a pod that doesn't use the plugin (framework/interface.go:518).
ALL_FEATURES = ("nodeaffinity", "taints", "ports", "images")
# "nodeaffinity_pin" is the cheap sibling of "nodeaffinity": every
# affinity-bearing pod in the batch reduced to a matchFields
# metadata.name In [v] pin (the daemonset-controller shape), so only the
# [N] pin compare compiles — never the [N, T, E, V] selector kernels or
# the preferred-term scorer (pins carry no preferred terms).


def _guard_reduction(scores: jnp.ndarray, free: jnp.ndarray) -> jnp.ndarray:
    """BatchResult.guard: NaN poison detector, fused into the launch.
    Bit 0 = NaN in the winning scores (placements untrustworthy), bit 1 =
    NaN in the post-batch free state (the usage chain is poisoned)."""
    return (jnp.any(jnp.isnan(scores)).astype(jnp.int32)
            | (jnp.any(jnp.isnan(free)).astype(jnp.int32) << 1))


# jax.named_scope on the device kernels: the names reach the HLO metadata
# (and from there a profiler trace's operation details); they change no
# program and no cache key. KERNEL_SCOPES lists them for whoever reads
# per-kernel device time.
KERNEL_SCOPES = ("static_filters", "auction_rounds", "soft_topology_auction",
                 "commit_scan", "patch_chain", "scatter_rows",
                 "inter_pod_affinity", "scan_queries", "scan_map_updates",
                 "table_block", "node_affinity")


@jax.named_scope("static_filters")
def static_filters(ct: ClusterTensors, pod: PodFeatures,
                   wk: dict[str, jnp.ndarray],
                   enabled: tuple[bool, ...],
                   active: frozenset[str]) -> jnp.ndarray:
    """Commit-invariant Filter plugins for one pod over all nodes: [5, N]
    masks in FILTER_PLUGINS order (the rest run in the commit scan).
    ``enabled`` (static, from the framework's resolved config) replaces a
    disabled plugin's mask with all-True — XLA dead-code-eliminates it;
    ``active`` does the same for features the workload doesn't use."""
    fns = (
        lambda: FL.node_unschedulable(ct, pod, wk["unschedulable_taint_key"]),
        lambda: FL.node_name(ct, pod),
        lambda: (FL.taint_toleration(ct, pod)
                 if "taints" in active else None),
        lambda: (FL.node_affinity(ct, pod, full="nodeaffinity" in active)
                 if ("nodeaffinity" in active
                     or "nodeaffinity_pin" in active) else None),
        lambda: (FL.node_ports(ct, pod, wk["wildcard_ip"])
                 if "ports" in active else None),
    )
    n = ct.node_valid.shape[0]
    masks = []
    for i, fn in enumerate(fns):
        m = fn() if enabled[i] else None
        masks.append(m if m is not None else jnp.ones((n,), bool))
    return jnp.stack(masks)


def tie_perturb(b, n: int, seed=None) -> jnp.ndarray:
    """[n] pseudo-random f32 in [0,1) keyed by (pod index b, node index):
    the stateless device analog of selectHost's reservoir sampling
    (schedule_one.go:865) — equal-score nodes pick uniformly instead of
    hotspotting the lowest row. Cheap integer hash; fuses, no RNG state.

    ``seed`` (config tie_break_seed, a DYNAMIC scalar — changing it never
    recompiles) mixes an explicit stream into the hash so paired A/B runs
    are tie-break-deterministic and score diffs attribute to the scorer,
    not the coin. Seed 0 (and None) is the identity xor: the default
    launch stays bit-identical to the historical unseeded hash."""
    x = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)
    x = x ^ (jnp.asarray(b).astype(jnp.uint32) * jnp.uint32(40503))
    if seed is not None:
        x = x ^ (jnp.asarray(seed).astype(jnp.uint32)
                 * jnp.uint32(2654435761))
    x = (x ^ (x >> 15)) * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    return (x >> 8).astype(jnp.float32) / jnp.float32(1 << 24)


@dataclass
class _SoftTopo:
    """Everything the auction needs to score SOFT topology terms (preferred
    pod (anti)affinity + ScheduleAnyway spread) without the serial scan.

    Soft terms never change FEASIBILITY, so a batch whose only topology
    work is soft keeps the auction's round structure: the static (table)
    part of each score is per-GROUP phase-1 work, and the in-batch part is
    recomputed per round from the placed set with dense domain
    scatters/gathers — "the same gathers with a weight multiply" as the
    hard-constraint machinery, fused into the same launch."""

    gid: jax.Array          # [B] group id per pod
    ipa_ok_g: jax.Array     # [G, N] static InterPodAffinity mask (the
                            # table's required anti-affinity vs each group;
                            # all-True when the ipa filter is disabled)
    ipa_raw_g: jax.Array    # [G, N] static ipa score (table terms both
                            # directions incl. hardPodAffinityWeight)
    match_static_g: jax.Array  # [G, N, C] static soft-spread match counts
    tpw_g: jax.Array        # [G, C] topology normalizing weight log(size+2)
    used_soft_g: jax.Array  # [G, C] soft (ScheduleAnyway) constraint slots
    dom_ok_g: jax.Array     # [G, N, C] node carries the constraint's key
    ign_g: jax.Array        # [G, N] node ignored for spread scoring
    has_soft_g: jax.Array   # [G] any soft constraint
    skew_g: jax.Array       # [G, C] maxSkew per constraint
    el_node_g: jax.Array    # [G, N, C] in-batch eligibility of a node as a
                            # commit target for the group's constraints
    # per-own-term domain columns: node n's domain under term (g, a)'s key
    nd_paff: jax.Array      # [N, G, A] i32 (NONE = key absent)
    nd_panti: jax.Array     # [N, G, A]
    nd_tsc: jax.Array       # [N, G, C]
    paff_tk_g: jax.Array    # [G, A]
    panti_tk_g: jax.Array   # [G, A]
    tsc_tk_g: jax.Array     # [G, C]
    paff_w_g: jax.Array     # [G, A] f32
    panti_w_g: jax.Array    # [G, A] f32
    M_paff_gg: jax.Array    # [G, A, G] pairwise group term matches
    M_panti_gg: jax.Array   # [G, A, G]
    M_tsc_gg: jax.Array     # [G, C, G]
    topo_dom: jax.Array     # [N, TK]
    d_cap: int = 0


def _soft_statics(ct, table, caps, pods, pods_rep, gid, g_cap, d_cap, wk,
                  enabled_filters, act, ipa_on, chunked_vmap):
    """Per-GROUP static halves of the soft topology scores (the auction's
    phase-1b): the table's contribution to each group's ipa mask/score and
    soft-spread counts — placement-independent, computed once per launch
    over the table's live blocks (``table`` is ClusterBlobs.pods_i32)."""
    valid = ct.node_valid

    def per_group_soft(pod: PodFeatures):
        masks = static_filters(ct, pod, wk, enabled_filters, act)
        g_static_ok = jnp.all(masks, axis=0) & valid & pod.valid
        taint_ok, nodeaff_ok = masks[2], masks[3]
        used_c = pod.tsc_tk != jnp.int32(-1)
        used_soft = used_c & ~pod.tsc_hard
        el_soft = T.spread_eligible(ct, pod, nodeaff_ok, taint_ok,
                                    used_soft)
        ts = T.table_statics(
            ct, table, caps, pod, d_cap, forbid=ipa_on,
            hard_weight=jnp.float32(HARD_POD_AFFINITY_WEIGHT),
            spread_el=el_soft)
        cnt = ts.cnt                                             # [C, D]
        node_dom = T.take_cols(ct.topo_dom, pod.tsc_tk, jnp.int32(-1))
        ign = jnp.any((node_dom == jnp.int32(-1))
                      & used_soft[None], axis=1)                 # [N]
        exists_score = T.spread_exists(
            ct, pod, (g_static_ok & ~ign)[:, None] & used_soft[None],
            d_cap)
        tpw = jnp.log(jnp.sum(exists_score, axis=1)
                      .astype(jnp.float32) + 2.0)                # [C]
        match_static = T.gather_rows(cnt, node_dom)              # [N, C]
        # in-batch commit-target eligibility (policies + key presence);
        # soft-only batches have no hard constraints to honor
        pol = (jnp.where(pod.tsc_honor_affinity[None],
                         (nodeaff_ok & valid)[:, None], True)
               & jnp.where(pod.tsc_honor_taints[None],
                           (taint_ok & valid)[:, None], True))   # [N, C]
        dom_ok = node_dom != jnp.int32(-1)                       # [N, C]
        all_s = jnp.all(dom_ok | ~used_soft[None], axis=1)       # [N]
        el_node = pol & all_s[:, None] & dom_ok & used_soft[None]
        # the ipa filter disabled: its static mask all-True
        anti_ok = ts.anti_ok if ipa_on else jnp.ones_like(valid)
        return (anti_ok, ts.ipa_raw, match_static, tpw, used_soft,
                dom_ok, ign, jnp.any(used_soft), el_node)

    (anti_g, ipa_raw_g, match_g, tpw_g, soft_g, dom_ok_g, ign_g,
     has_soft_g, el_node_g) = chunked_vmap(per_group_soft, pods_rep, g_cap)
    tk_cap = ct.topo_dom.shape[1]

    def nd_of(tk_g):
        # [N, G, A]: node n's domain under term (g, a)'s topology key
        nd = ct.topo_dom[:, jnp.clip(tk_g, 0, tk_cap - 1)]
        return jnp.where(tk_g[None] != NONE, nd, NONE)

    M_paff_gg = T.pair_term_match(
        pods_rep.paff_tk, pods_rep.paff_ns, pods_rep.paff_ns_all,
        pods_rep.paff_sel_cols, pods_rep.paff_sel_ops,
        pods_rep.paff_sel_vals, pods_rep.plabel_vals, pods_rep.ns,
        pods_rep.valid)
    M_panti_gg = T.pair_term_match(
        pods_rep.panti_tk, pods_rep.panti_ns, pods_rep.panti_ns_all,
        pods_rep.panti_sel_cols, pods_rep.panti_sel_ops,
        pods_rep.panti_sel_vals, pods_rep.plabel_vals, pods_rep.ns,
        pods_rep.valid)
    M_tsc_gg = T.pair_tsc_match(pods_rep)
    return _SoftTopo(
        gid=gid, ipa_ok_g=anti_g, ipa_raw_g=ipa_raw_g,
        match_static_g=match_g, tpw_g=tpw_g, used_soft_g=soft_g,
        dom_ok_g=dom_ok_g, ign_g=ign_g, has_soft_g=has_soft_g,
        skew_g=pods_rep.tsc_max_skew.astype(jnp.float32),
        el_node_g=el_node_g,
        nd_paff=nd_of(pods_rep.paff_tk), nd_panti=nd_of(pods_rep.panti_tk),
        nd_tsc=nd_of(pods_rep.tsc_tk),
        paff_tk_g=pods_rep.paff_tk, panti_tk_g=pods_rep.panti_tk,
        tsc_tk_g=pods_rep.tsc_tk,
        paff_w_g=pods_rep.paff_weight.astype(jnp.float32),
        panti_w_g=pods_rep.panti_weight.astype(jnp.float32),
        M_paff_gg=M_paff_gg, M_panti_gg=M_panti_gg, M_tsc_gg=M_tsc_gg,
        topo_dom=ct.topo_dom, d_cap=d_cap)


def _soft_scores(soft: _SoftTopo, placed, gid_oh):
    """[G, N] live soft scores (static + in-batch halves) for the current
    placed set: the auction-round analog of the scan's map_updates +
    queries, recomputed from scratch each round via domain scatter/gather
    (placed sets are small and rounds are few — no carry maps needed)."""
    d_cap = soft.d_cap
    n_cap = soft.topo_dom.shape[0]
    ok = placed >= 0                                             # [B]
    r = jnp.clip(placed, 0, n_cap - 1)
    dom_rows = jnp.where(ok[:, None], soft.topo_dom[r], NONE)    # [B, TK]
    tk_cap = soft.topo_dom.shape[1]

    def committed_dom(tk_g):
        # [B, G, A]: committed pod y's domain under term (g, a)'s key
        dy = dom_rows[:, jnp.clip(tk_g, 0, tk_cap - 1)]
        return jnp.where(tk_g[None] != NONE, dy, NONE)

    def pair_delta(tk_g, nd, M_gg, w_g):
        """[G, N] weighted same-domain score mass from placed pods, both
        directions of the preferred terms (scoring.go processExistingPod's
        incoming-vs-existing and existing-vs-incoming soft halves).

        Domain ids are validity-checked against d_cap: the padding group's
        zeroed term rows reference arbitrary topology keys whose domain
        space can exceed the launch's bucket, and an out-of-range gather
        index fills NaN — which a zero weight does NOT neutralize."""
        G, A = tk_g.shape
        dy = committed_dom(tk_g)                                 # [B, G, A]
        dy_t = jnp.moveaxis(dy, 0, -1)                           # [G, A, B]
        dv = (dy_t >= 0) & (dy_t < d_cap) & ok[None, None, :]
        flat = (jnp.arange(G)[:, None, None] * (A * d_cap)
                + jnp.arange(A)[None, :, None] * d_cap
                + jnp.clip(dy_t, 0, d_cap - 1))
        # b-side: x's own term a matches committed pod y
        Mg = M_gg[:, :, :] @ gid_oh.T                            # [G, A, B]
        P_b = jnp.zeros((G * A * d_cap,), jnp.float32).at[
            flat.reshape(-1)].add(
                jnp.where(dv, Mg, 0.0).reshape(-1))
        P_b = P_b.reshape(G, A, d_cap)
        # j-side: committed pod y's own term a matches group g2
        own = jnp.moveaxis(gid_oh, 0, -1)                        # [G, B]
        P_j = jnp.zeros((G * A * d_cap,), jnp.float32).at[
            flat.reshape(-1)].add(
                jnp.where(dv, own[:, None, :], 0.0).reshape(-1))
        P_j = P_j.reshape(G, A, d_cap)
        nd_g = jnp.moveaxis(nd, 0, -1)                           # [G, A, N]
        nd_ok = (nd_g >= 0) & (nd_g < d_cap)
        idx = jnp.clip(nd_g, 0, d_cap - 1)
        gath_b = jnp.take_along_axis(P_b, idx.reshape(G, A, -1),
                                     axis=2).reshape(nd_g.shape)
        gath_j = jnp.take_along_axis(P_j, idx.reshape(G, A, -1),
                                     axis=2).reshape(nd_g.shape)
        delta_b = jnp.sum(jnp.where(nd_ok, gath_b, 0.0)
                          * w_g[:, :, None], axis=1)             # [G, N]
        delta_j = jnp.einsum("gah,gan->hn", soft_mul(M_gg, w_g),
                             jnp.where(nd_ok, gath_j, 0.0))
        return delta_b + delta_j

    def soft_mul(M_gg, w_g):
        return M_gg.astype(jnp.float32) * w_g[:, :, None]

    ipa_delta = (pair_delta(soft.paff_tk_g, soft.nd_paff,
                            soft.M_paff_gg.astype(jnp.float32),
                            soft.paff_w_g)
                 - pair_delta(soft.panti_tk_g, soft.nd_panti,
                              soft.M_panti_gg.astype(jnp.float32),
                              soft.panti_w_g))
    ipa_live = soft.ipa_raw_g + ipa_delta                        # [G, N]

    # soft spread: in-batch match-count deltas per (group, constraint)
    G, C = soft.tsc_tk_g.shape
    dy = committed_dom(soft.tsc_tk_g)                            # [B, G, C]
    dy_t = jnp.moveaxis(dy, 0, -1)                               # [G, C, B]
    el_y = jnp.moveaxis(soft.el_node_g[:, r, :], 1, -1)          # [G, C, B]
    Mg = soft.M_tsc_gg.astype(jnp.float32) @ gid_oh.T            # [G, C, B]
    val = jnp.where((dy_t >= 0) & (dy_t < d_cap) & ok[None, None, :],
                    Mg * el_y.astype(jnp.float32), 0.0)
    flat = (jnp.arange(G)[:, None, None] * (C * d_cap)
            + jnp.arange(C)[None, :, None] * d_cap
            + jnp.clip(dy_t, 0, d_cap - 1))
    P_t = jnp.zeros((G * C * d_cap,), jnp.float32).at[
        flat.reshape(-1)].add(val.reshape(-1)).reshape(G, C, d_cap)
    nd_t = jnp.moveaxis(soft.nd_tsc, 0, -1)                      # [G, C, N]
    gath_t = jnp.take_along_axis(
        P_t, jnp.clip(nd_t, 0, d_cap - 1).reshape(G, C, -1),
        axis=2).reshape(nd_t.shape)
    match = (jnp.moveaxis(soft.match_static_g, 1, -1)
             + jnp.where((nd_t >= 0) & (nd_t < d_cap), gath_t, 0.0))
    per_c = match * soft.tpw_g[:, :, None] \
        + (soft.skew_g[:, :, None] - 1.0)
    per_c = jnp.where(soft.used_soft_g[:, :, None]
                      & jnp.moveaxis(soft.dom_ok_g, 1, -1), per_c, 0.0)
    sp_r = jnp.where(soft.ign_g, 0.0, jnp.sum(per_c, axis=1))    # [G, N]
    return ipa_live, sp_r


def _rounds_commit(ct, pods, static_ok, static_rejects, taint_raw, aff_raw,
                   img, unres, weights, free0, nzr0, host_score=None,
                   fit_strategy="LeastAllocated", fit_shape=None,
                   dra_reject=None, tie_seed=None, soft=None,
                   unroll=None):
    """Parallel auction replacing the per-pod commit scan when the batch has
    no topology constraints and no host ports: every round, all unplaced
    pods score+argmax in parallel; per node, up to K pods are accepted in
    BATCH INDEX order while their cumulative requests fit (the
    as-if-serial feasibility invariant — no node is ever overcommitted
    relative to the serial order); losers re-score against the updated
    cluster next round. K = ceil(B / valid nodes): 1 on clusters at least
    batch-sized (the historical one-accept-per-node behavior, bit
    identical), proportionally higher when the batch outnumbers the
    nodes — a 1024-pod batch over 200 nodes converges in ~2 rounds
    instead of the ~B/N rounds one-accept-per-node starves through,
    while ties still spread (K tracks the per-node share a balanced
    placement would take anyway).

    Placement CHOICES may differ from the serial scan (a pod scores against
    round-start state, not the exact post-predecessor state) but every
    placement satisfies the same constraints the serial loop enforces. The
    scan path remains the exact-parity mode for topology/port batches.

    Wall-clock: O(rounds) of [B, N] work instead of B sequential steps —
    rounds ≈ a few with random tie-breaking. This is what makes the batched
    design faster than the reference's per-pod loop on TPU: the MXU-friendly
    [B, N] score matrix replaces B round trips through tiny kernels."""
    B, N = static_ok.shape
    alloc2 = SC.alloc_cpu_mem(ct)
    own = jnp.arange(N)[None, :] == pods.nominated_row[:, None]    # [B, N]
    perturb = jax.vmap(lambda u: tie_perturb(u, N, tie_seed))(pods.uid_id)
    idx_b = jnp.arange(B)
    # soft-topology mode: the static ipa mask (the table's required
    # anti-affinity vs each group) joins the feasible set; the soft score
    # halves join the round totals below. Soft terms never constrain, so
    # the auction's round structure is unchanged.
    if soft is not None:
        ipa_mask = soft.ipa_ok_g[soft.gid]                         # [B, N]
        gid_oh = (soft.gid[:, None]
                  == jnp.arange(soft.ipa_ok_g.shape[0])[None, :]
                  ).astype(jnp.float32) * pods.valid[:, None]      # [B, G]
        ign_b = soft.ign_g[soft.gid]                               # [B, N]
        soft_b = soft.has_soft_g[soft.gid]                         # [B]
    else:
        ipa_mask = None
    # STATIC gate for the K-accept rounds: only a batch that outnumbers
    # the node bucket can need K > 1, and the cumulative-fit cumsums are
    # [B, N]-sized work the big-cluster shapes must not pay — at B <= N
    # the historical one-accept-per-node program compiles, bit identical
    multi_accept = B > N
    # per-node acceptance budget per round (see docstring): the share a
    # balanced placement would put on one node anyway (valid pods over
    # valid nodes — padding rows place nothing)
    k_accept = jnp.ceil(
        jnp.sum(pods.valid).astype(jnp.float32) / jnp.maximum(
            jnp.sum(ct.node_valid).astype(jnp.float32), 1.0)
    ).astype(jnp.int32) if multi_accept else None

    def eff_all(free):
        """[B, N, R] per-pod effective free rows (nominated reservations
        subtracted, the pod's OWN nomination handed back)."""
        return (free[None] - ct.nominated_req[None]
                + jnp.where(own[..., None], pods.req[:, None, :], 0.0))

    def fit_all(free):
        return jnp.all(pods.req[:, None, :] <= eff_all(free), axis=-1)

    def totals(nzr, feasible, sp_b=None, ipa_b=None):
        def per_pod(nzreq, t_raw, a_raw, im, feas, *topo):
            frac = SC.utilization_fractions(alloc2, nzr, nzreq)
            least = SC.fit_score_from_fractions(frac, fit_strategy,
                                                fit_shape)
            bal = SC.balanced_allocation_from_fractions(frac)
            taint = SC.normalize_inverse(t_raw, feas)
            aff = SC.normalize_max(a_raw, feas)
            total = (weights.taint_toleration * taint
                     + weights.node_affinity * aff
                     + weights.resources_fit * least
                     + weights.balanced_allocation * bal
                     + weights.image_locality * im)
            if topo:
                # soft-topology mode: normalize + weight the live soft
                # halves per pod, exactly like the serial scan's step
                sp_row, ipa_row, ign_row, softp = topo
                ipa_n = SC.normalize_maxmin(ipa_row, feas)
                sp_n = jnp.where(softp,
                                 SC.normalize_spread(sp_row, feas,
                                                     ign_row), 0.0)
                total = (total + weights.pod_topology_spread * sp_n
                         + weights.inter_pod_affinity * ipa_n)
            return total
        args = (pods.nonzero_req, taint_raw, aff_raw, img, feasible)
        if sp_b is not None:
            args = args + (sp_b, ipa_b, ign_b, soft_b)
        out = jax.vmap(per_pod)(*args)
        return out if host_score is None else out + host_score

    def cond(state):
        _free, _nzr, _placed, _win, progress = state
        return progress

    def body(state):
        free, nzr, placed, win, _ = state
        eff = eff_all(free)                                        # [B, N, R]
        fit = jnp.all(pods.req[:, None, :] <= eff, axis=-1)
        feasible = static_ok & fit & (placed < 0)[:, None]
        if ipa_mask is not None:
            feasible = feasible & ipa_mask
        if soft is not None:
            # live soft topology scores against the ROUND-START placed
            # set (the auction's state discipline, same as utilization)
            ipa_live_g, sp_r_g = _soft_scores(soft, placed, gid_oh)
            total = totals(nzr, feasible, sp_b=sp_r_g[soft.gid],
                           ipa_b=ipa_live_g[soft.gid])
        else:
            total = totals(nzr, feasible)
        choice = jax.vmap(C.masked_argmax_random)(total, feasible, perturb)
        # per-node acceptance: up to k_accept pods per node per round,
        # in batch index order, while their CUMULATIVE requests keep
        # fitting the pod's own effective free row (exact as-if-serial
        # feasibility); colliding losers re-score against the updated
        # cluster next round, so utilization scores steer them away from
        # just-filled nodes and the final balance tracks the serial
        # loop's. Everything is dense [B, N] reductions / cumsums /
        # one-hot matmuls — no scatters, which TPU would serialize per
        # update.
        chosen = choice[:, None] == jnp.arange(N)[None, :]         # [B, N]
        if multi_accept:
            rank = jnp.cumsum(chosen.astype(jnp.int32), axis=0) - 1
            take = chosen & (rank < k_accept)
            cum_ok = jnp.ones((B, N), bool)
            for r in range(pods.req.shape[1]):     # static R unroll
                cr = jnp.cumsum(jnp.where(take, pods.req[:, r:r + 1],
                                          0.0), axis=0)
                cum_ok &= cr <= eff[:, :, r]
            acc_cell = take & cum_ok
            accept = (choice >= 0) & jnp.take_along_axis(
                acc_cell, jnp.clip(choice, 0, N - 1)[:, None],
                axis=1)[:, 0]
        else:
            # one accept per node per round: first chooser in batch
            # index order (the historical program; K would be 1 anyway)
            cand_idx = jnp.where(chosen, idx_b[:, None], B)
            first_idx = jnp.min(cand_idx, axis=0)                  # [N]
            accept = ((choice >= 0)
                      & (jnp.take(first_idx, jnp.clip(choice, 0, N - 1))
                         == idx_b))                                # [B]
        onehot = (accept[:, None] & chosen).astype(free.dtype)     # [B, N]
        free = free - onehot.T @ pods.req                          # [N, R]
        nzr = nzr + onehot.T @ pods.nonzero_req                    # [N, 2]
        placed = jnp.where(accept, choice, placed)
        win_now = jnp.take_along_axis(
            total, jnp.clip(choice, 0, N - 1)[:, None], axis=1)[:, 0]
        win = jnp.where(accept, win_now, win)
        return free, nzr, placed, win, jnp.any(accept)

    init = (free0, nzr0, jnp.full((B,), -1, jnp.int32),
            jnp.zeros((B,), jnp.float32), jnp.bool_(True))
    # fused multi-round body: the while condition is data-dependent, so
    # every loop iteration costs a host<->device round trip on the
    # progress flag. Running `unroll` rounds per iteration cuts that
    # U-fold with fixed shapes (no recompiles). Rounds past convergence
    # are skipped by lax.cond on the progress flag — and even an executed
    # extra round is a no-op, because at the fixed point the feasible set
    # admits no accept (the body is idempotent), so the final state is
    # bit-identical to the one-round-per-iteration program.
    unroll = AUCTION_UNROLL if unroll is None else max(1, int(unroll))
    if unroll == 1:
        fused = body
    else:
        def fused(state):
            state = body(state)
            for _ in range(unroll - 1):
                state = jax.lax.cond(state[4], body, lambda s: s, state)
            return state
    with jax.named_scope("auction_rounds" if soft is None
                         else "soft_topology_auction"):
        free, nzr, placed, win, _ = jax.lax.while_loop(cond, fused, init)

    # diagnostics from the final state (unplaced pods' reject attribution)
    fit = fit_all(free)
    zeros = jnp.zeros((B,), jnp.int32)
    if ipa_mask is not None:
        feas = jnp.sum(static_ok & fit & ipa_mask,
                       axis=1).astype(jnp.int32)
        ipa_rejects = jnp.sum(static_ok & fit & ~ipa_mask,
                              axis=1).astype(jnp.int32)
    else:
        feas = jnp.sum(static_ok & fit, axis=1).astype(jnp.int32)
        ipa_rejects = zeros
    fit_rejects = jnp.sum(static_ok & ~fit, axis=1).astype(jnp.int32)
    reject_counts = jnp.concatenate(
        [static_rejects, fit_rejects[:, None], zeros[:, None],
         ipa_rejects[:, None]], axis=1)
    return BatchResult(node_row=placed, score=win, feasible_count=feas,
                       reject_counts=reject_counts,
                       unresolvable_count=unres, free=free, nzr=nzr,
                       pct_start=jnp.int32(0),
                       guard=_guard_reduction(win, free),
                       dra_reject=(jnp.zeros((B,), jnp.int32)
                                   if dra_reject is None else dra_reject),
                       scan_steps=jnp.int32(0),
                       table_blocks=(T.table_blocks(ct) if soft is not None
                                     else jnp.int32(0)))


def schedule_batch(cblobs: ClusterBlobs, pblobs: PodBlobs,
                   wk: dict[str, jnp.ndarray], weights: ScoreWeights,
                   caps: Capacities, enable_topology: bool = True,
                   d_cap: int | None = None,
                   enabled_filters: tuple[bool, ...] | None = None,
                   serial_scan: bool = True,
                   state: tuple[jnp.ndarray, jnp.ndarray] | None = None,
                   active: tuple[str, ...] | None = None,
                   pfields: tuple[str, ...] | None = None,
                   ptmpl: PodBlobs | None = None,
                   gid: jnp.ndarray | None = None,
                   rep: jnp.ndarray | None = None,
                   g_cap: int = 0,
                   host_ok: jnp.ndarray | None = None,
                   host_score: jnp.ndarray | None = None,
                   fit_strategy: str = "LeastAllocated",
                   fit_shape=None,
                   pct_nodes: int = 0,
                   pct_start: jnp.ndarray | None = None,
                   dra=None,
                   tie_seed=None,
                   topo_soft: bool = False,
                   auction_unroll: int | None = None,
                   ) -> BatchResult:
    """Schedule a whole pod batch in one launch, as-if-serial (see module
    docstring for the two-phase structure).

    ``topo_soft`` (STATIC): the batch's topology work is SOFT-only (no
    required terms, no DoNotSchedule spread — LaunchSpec.topo_soft). The
    serial scan then compiles the reduced soft program: only the
    weighted-score carries (wscore_n + node-space spread counts) survive
    — the hard-constraint carry maps (forbid/presence/domain-count
    tensors, the ones that scale with d_cap) are provably neutral for a
    soft-only batch and compile out. Same placements, bit-identical
    scores, a fraction of the per-step kernels. The auction path uses it
    to fuse the soft-score terms (_soft_statics/_soft_scores).

    ``enable_topology`` and ``d_cap`` are STATIC, host-derived launch args —
    the device analog of PreFilter returning Skip (framework/interface.go):
    a batch with no (anti)affinity terms or spread constraints compiles to a
    program with the topology kernels dead-code-eliminated, and ``d_cap``
    bounds the domain scatter space to the batch's actually-used topology
    keys (Mirror.domain_bucket) instead of the worst-case node count.

    ``serial_scan=False`` (STATIC) selects the parallel-rounds auction
    (_rounds_commit) instead of the exact-parity commit scan. Only valid
    when the launch has no topology work and no batch pod carries host
    ports — the host gates this (Scheduler/bench), mirroring the
    reference's own "skip what the pod doesn't use" PreFilter returns.

    ``state`` optionally overrides the cluster's (free, nonzero_requested)
    usage tensors with the previous launch's BatchResult.free/.nzr — the
    device-resident chain that lets a multi-batch drain run without host
    mirror re-syncs in between.

    ``gid``/``rep``/``g_cap`` (Mirror._batch_groups) dedup the batch into
    TOPOLOGY GROUPS: pods whose packed rows differ only in identity fields
    compute identical topology statics and pairwise term matches, so both
    the phase-1 statics and the commit scan's in-batch maps are computed per
    GROUP, not per pod. Real workloads are deployment-shaped (few distinct
    specs per batch), which turns the former per-pod scatter storm — TPU
    scatters run ~100x below bandwidth — into a handful of small dense
    updates. g_cap is a static pow2 bucket; a fully heterogeneous batch
    (g_cap == B) is still exact, just back to per-pod cost.

    ``host_ok``/``host_score`` ([B, N] bool / f32) carry HOST plugin
    verdicts (volume family, custom plugins): the host filter mask is ANDed
    into every pod's feasible set, the host score added to the aggregate —
    the mixed host/device framework's seam (runtime.run_host_filters).

    ``dra`` (an ops.dra.DraBatch, or None for launches without device-
    routed claim pods) fuses the batched DRA allocator into this same
    program: claim feasibility for every (pod, node) pair is one more
    vmapped predicate ANDed into the feasible mask, and the per-pod
    rejected-node count lands in BatchResult.dra_reject.

    ``tie_seed`` (dynamic scalar) keys the tie-break hash for
    A/B-deterministic paired runs; seed 0/None is the historical hash."""
    ct = unpack_cluster(cblobs, caps)
    pods = unpack_pods(pblobs, caps, pfields, ptmpl)  # leaves [B, ...]
    free0 = ct.free if state is None else state[0]
    nzr0 = ct.nonzero_requested if state is None else state[1]
    act = frozenset(ALL_FEATURES if active is None else active)
    num_valid = jnp.sum(ct.node_valid)
    valid = ct.node_valid
    if d_cap is None:
        d_cap = caps.domain_cap
    if enabled_filters is None:
        enabled_filters = (True,) * NUM_FILTER_PLUGINS
    fit_on = enabled_filters[FILTER_PLUGINS.index("NodeResourcesFit")]
    spread_on = (enable_topology
                 and enabled_filters[FILTER_PLUGINS.index("PodTopologySpread")])
    ipa_on = (enable_topology
              and enabled_filters[FILTER_PLUGINS.index("InterPodAffinity")])
    if enable_topology and gid is None:
        # direct callers without host grouping: every pod its own group.
        # NOTE: at large B this materializes O(B*N)-sized scan-carry maps —
        # production callers go through Mirror.prepare_launch, whose host
        # dedup keeps g_cap at the number of DISTINCT pod specs
        nb = pblobs.f32.shape[0]
        gid = jnp.arange(nb, dtype=jnp.int32)
        rep = jnp.arange(nb, dtype=jnp.int32)
        g_cap = nb

    # ---- phase 1: parallel over the batch (per-pod base statics) ----
    def per_pod(pod: PodFeatures):
        masks = static_filters(ct, pod, wk, enabled_filters, act)  # [5, N]
        static_ok = jnp.all(masks, axis=0) & valid & pod.valid  # [N]
        # first-fail attribution among the static plugins
        prev_ok = jnp.cumprod(
            jnp.concatenate([jnp.ones((1, masks.shape[1]), masks.dtype),
                             masks[:-1]], axis=0), axis=0).astype(bool)
        first_fail = prev_ok & ~masks & valid[None]
        static_rejects = jnp.sum(first_fail, axis=1).astype(jnp.int32)  # [P-1]
        # raw commit-invariant scores (inactive feature -> zero, DCE'd)
        n = valid.shape[0]
        zeros_n = jnp.zeros((n,), jnp.float32)
        taint_raw = (SC.taint_toleration_score(ct, pod)
                     if "taints" in act else zeros_n)           # [N]
        aff_raw = (SC.node_affinity_score(ct, pod)
                   if "nodeaffinity" in act else zeros_n)       # [N]
        img = (SC.image_locality(ct, pod, num_valid)
               if "images" in act else zeros_n)                 # [N]
        # fit can never succeed: request exceeds allocatable (Unresolvable)
        unresolvable = jnp.any(pod.req[None] > ct.allocatable, axis=-1)
        unres_count = jnp.sum(unresolvable & valid).astype(jnp.int32)
        return (static_ok, static_rejects, taint_raw, aff_raw, img,
                unres_count)

    def chunked_vmap(fn, tree, n_rows):
        """vmap chunked through lax.map so giant batches stay inside HBM —
        per-chunk peak is what a PHASE1_CHUNK-sized batch needs."""
        if n_rows <= PHASE1_CHUNK:
            return jax.vmap(fn)(tree)
        pad = (-n_rows) % PHASE1_CHUNK
        tree_p = tree if pad == 0 else jax.tree.map(
            lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)), tree)
        groups = (n_rows + pad) // PHASE1_CHUNK
        tree_g = jax.tree.map(
            lambda x: x.reshape((groups, PHASE1_CHUNK) + x.shape[1:]), tree_p)
        outs = jax.lax.map(lambda p: jax.vmap(fn)(p), tree_g)
        return jax.tree.map(
            lambda x: x.reshape((groups * PHASE1_CHUNK,)
                                + x.shape[2:])[:n_rows], outs)

    B_all = pblobs.f32.shape[0]
    if gid is not None and rep.shape[0] < B_all:
        # phase-1 dedup: statics are identity-free, so compute them per
        # GROUP representative and gather back to pods — deployment-shaped
        # batches (few distinct specs) collapse the [B, N] phase-1 work to
        # [G, N] (Mirror.prepare_launch attaches groups for no-topology
        # launches too when the batch is homogeneous enough). Degenerate
        # per-pod groupings (rep as wide as the batch) skip the detour —
        # the two full-batch gathers would only add HBM traffic.
        pods_rep_p1 = jax.tree.map(lambda x: x[rep], pods)
        outs_g = chunked_vmap(per_pod, pods_rep_p1, rep.shape[0])
        outs = jax.tree.map(lambda x: x[gid], outs_g)
    else:
        outs = chunked_vmap(per_pod, pods, B_all)
    (static_ok, static_rejects, taint_raw, aff_raw, img, unres) = outs
    if dra is not None:
        # fused batched DRA allocator (ops/dra.py): claim feasibility
        # for all (pod, node) pairs in this same launch. First-fail
        # attribution after the static filters; host_ok rejects stay
        # host-attributed like before.
        from kubernetes_tpu.ops.dra import batch_feasible

        dra_ok = batch_feasible(dra)                            # [B, N]
        dra_reject = jnp.sum(static_ok & ~dra_ok, axis=1).astype(jnp.int32)
        static_ok = static_ok & dra_ok
    else:
        dra_reject = jnp.zeros((B_all,), jnp.int32)
    if host_ok is not None:
        # host Filter verdicts AND in here; host rejects are attributed by
        # the Scheduler from its own counts (they never reach reject_counts)
        static_ok = static_ok & host_ok
    if not serial_scan:
        if pct_nodes:
            raise ValueError(
                "percentageOfNodesToScore truncation only exists in the "
                "serial scan; gate the auction off when the knob is set")
        soft = None
        if enable_topology:
            if not topo_soft:
                raise ValueError(
                    "auction commit requires a no-topology or soft-only "
                    "topology launch; required terms / DoNotSchedule "
                    "spread need the serial as-if-serial commit scan")
            # SOFT-ONLY topology launch (the caller gates this on the
            # batch carrying no required terms and no DoNotSchedule
            # spread): preferred (anti)affinity weights and ScheduleAnyway
            # spread are SCORES, not constraints, so the auction's round
            # structure holds — the table halves are per-group statics,
            # the in-batch halves recompute per round (_soft_scores)
            pods_rep = jax.tree.map(lambda x: x[rep], pods)
            soft = _soft_statics(ct, cblobs.pods_i32, caps, pods, pods_rep,
                                 gid, g_cap, d_cap, wk, enabled_filters,
                                 act, ipa_on, chunked_vmap)
        return _rounds_commit(ct, pods, static_ok, static_rejects, taint_raw,
                              aff_raw, img, unres, weights, free0, nzr0,
                              host_score, fit_strategy, fit_shape,
                              dra_reject, tie_seed, soft=soft,
                              unroll=auction_unroll)
    soft_st = None
    if enable_topology and topo_soft:
        # ---- phase 1b (SOFT): the reduced per-group statics — exactly
        # what the soft scores need; none of the hard-constraint maps
        pods_rep = jax.tree.map(lambda x: x[rep], pods)
        soft_st = _soft_statics(ct, cblobs.pods_i32, caps, pods, pods_rep,
                                gid, g_cap, d_cap, wk, enabled_filters, act,
                                ipa_on, chunked_vmap)
    if enable_topology and not topo_soft:
        # ---- phase 1b: topology statics per GROUP (representatives) ----
        pods_rep = jax.tree.map(lambda x: x[rep], pods)  # leaves [G, ...]

        def per_group(pod: PodFeatures):
            masks = static_filters(ct, pod, wk, enabled_filters, act)
            g_static_ok = jnp.all(masks, axis=0) & valid & pod.valid
            taint_ok, nodeaff_ok = masks[2], masks[3]
            used_c = pod.tsc_tk != jnp.int32(-1)
            used_hard = used_c & pod.tsc_hard
            used_soft = used_c & ~pod.tsc_hard
            el_hard = T.spread_eligible(ct, pod, nodeaff_ok, taint_ok,
                                        used_hard)
            el_soft = T.spread_eligible(ct, pod, nodeaff_ok, taint_ok,
                                        used_soft)
            el_mixed = jnp.where(pod.tsc_hard[None], el_hard, el_soft)
            # every read of the pod table, in one fold over its live blocks
            ts = T.table_statics(
                ct, cblobs.pods_i32, caps, pod, d_cap, forbid=True,
                presence=True,
                hard_weight=jnp.float32(HARD_POD_AFFINITY_WEIGHT),
                spread_el=el_mixed)
            cnt = ts.cnt                                            # [C, D]
            exists_hard = T.spread_exists(ct, pod, el_hard, d_cap)  # [C, D]
            node_dom = T.take_cols(ct.topo_dom, pod.tsc_tk, jnp.int32(-1))
            spread_ignored = jnp.any((node_dom == jnp.int32(-1))
                                     & used_soft[None], axis=1)     # [N]
            # topoSize over (approximately) filtered nodes: static filters
            # only, matching PreScore's filteredNodes modulo in-batch effects
            exists_score = T.spread_exists(
                ct, pod,
                (g_static_ok & ~spread_ignored)[:, None] & used_soft[None],
                d_cap)
            tp_weight = jnp.log(jnp.sum(exists_score, axis=1)
                                .astype(jnp.float32) + 2.0)         # [C]
            tsc_self = T._tsc_self_match(pod).astype(jnp.float32)   # [C]
            has_soft = jnp.any(used_soft)
            # in-batch spread eligibility of ANY node as a commit target for
            # this group's constraints (policies + topology-label presence;
            # the commit scan gathers it at each committed node)
            pol = (jnp.where(pod.tsc_honor_affinity[None],
                             (nodeaff_ok & valid)[:, None], True)
                   & jnp.where(pod.tsc_honor_taints[None],
                               (taint_ok & valid)[:, None], True))  # [N, C]
            dom_ok = node_dom != jnp.int32(-1)                      # [N, C]
            all_h = jnp.all(dom_ok | ~used_hard[None], axis=1)      # [N]
            all_s = jnp.all(dom_ok | ~used_soft[None], axis=1)      # [N]
            el_node = (pol & jnp.where(used_hard[None], all_h[:, None],
                                       all_s[:, None]) & used_c[None])
            # node-space statics so the commit scan never gathers by domain:
            # required-affinity term satisfaction from the PRE-batch table,
            # spread match counts at each node's domain, domain presence
            aff_node_dom = T.take_cols(ct.topo_dom, pod.aff_tk, NONE)  # [N, A]
            has_lbl = aff_node_dom != NONE
            term_static = has_lbl & T.gather_rows(ts.present, aff_node_dom)
            match_static = T.gather_rows(cnt, node_dom)              # [N, C]
            num_domains = jnp.sum(exists_hard, axis=1)               # [C]
            return (cnt, exists_hard, spread_ignored, tp_weight, tsc_self,
                    ts.anti_ok, ts.any_match, ts.ipa_raw, has_soft,
                    el_node, term_static, has_lbl, match_static, dom_ok,
                    num_domains)

        (cnt_g, exists_hard_g, ign_g, tpw_g, self_g, ipa_anti_g,
         aff_any_g, ipa_raw_g, has_soft_g, el_node_g, term_static_g,
         has_lbl_g, match_static_g, dom_ok_g,
         num_domains_g) = chunked_vmap(per_group, pods_rep, g_cap)
        # [N, G, C] so the scan dynamic-slices a committed node's row
        el_node_nr = jnp.transpose(el_node_g, (1, 0, 2))
        # group-level term tables (the scan indexes these by group id)
        anti_tk_g = pods_rep.anti_tk                        # [G, A]
        aff_tk_g = pods_rep.aff_tk
        paff_tk_g = pods_rep.paff_tk
        panti_tk_g = pods_rep.panti_tk
        paff_w_g = pods_rep.paff_weight.astype(jnp.float32)
        panti_w_g = pods_rep.panti_weight.astype(jnp.float32)
        tsc_tk_g = pods_rep.tsc_tk                          # [G, C]
        tsc_hard_g = pods_rep.tsc_hard
        tsc_skew_g = pods_rep.tsc_max_skew
        tsc_mind_g = pods_rep.tsc_min_domains
        aff_self_g = pods_rep.aff_self_match                # [G]
        # pairwise GROUP<->GROUP term matches (placement-independent)
        M_anti_gg = T.pair_term_match(
            pods_rep.anti_tk, pods_rep.anti_ns, pods_rep.anti_ns_all,
            pods_rep.anti_sel_cols, pods_rep.anti_sel_ops,
            pods_rep.anti_sel_vals, pods_rep.plabel_vals, pods_rep.ns,
            pods_rep.valid)                                 # [G, A, G]
        M_aff_gg = T.pair_term_match(
            pods_rep.aff_tk, pods_rep.aff_ns, pods_rep.aff_ns_all,
            pods_rep.aff_sel_cols, pods_rep.aff_sel_ops,
            pods_rep.aff_sel_vals, pods_rep.plabel_vals, pods_rep.ns,
            pods_rep.valid)
        M_paff_gg = T.pair_term_match(
            pods_rep.paff_tk, pods_rep.paff_ns, pods_rep.paff_ns_all,
            pods_rep.paff_sel_cols, pods_rep.paff_sel_ops,
            pods_rep.paff_sel_vals, pods_rep.plabel_vals, pods_rep.ns,
            pods_rep.valid)
        M_panti_gg = T.pair_term_match(
            pods_rep.panti_tk, pods_rep.panti_ns, pods_rep.panti_ns_all,
            pods_rep.panti_sel_cols, pods_rep.panti_sel_ops,
            pods_rep.panti_sel_vals, pods_rep.plabel_vals, pods_rep.ns,
            pods_rep.valid)
        M_tsc_gg = T.pair_tsc_match(pods_rep)               # [G, C, G]

    # ---- phase 2: sequential commit scan (tiny per-step work) ----
    alloc2 = SC.alloc_cpu_mem(ct)                               # [N, 2]
    B = pblobs.f32.shape[0]
    # per-pod tie perturbation keyed by uid: equal-score nodes pick
    # uniformly instead of hotspotting the lowest row (selectHost's
    # reservoir sample, schedule_one.go:865)
    perturb_rows = jax.vmap(
        lambda u: tie_perturb(u, cblobs.node_f32.shape[0],
                              tie_seed))(pods.uid_id)
    # pairwise hostPort conflicts: pod j can't join a node where an earlier
    # conflicting batch pod was committed (as-if-serial NodePorts)
    port_conf = (FL.pod_pair_port_conflict(pods, wk["wildcard_ip"])
                 if "ports" in act
                 else jnp.zeros((B, B), bool))                  # [B, B]

    topo_dom = ct.topo_dom
    tk_cap = topo_dom.shape[1]

    @jax.named_scope("scan_queries")
    def queries(g, forbid1_n, map2_n, pres_n, any3, wscore_n, cntmap,
                cnt_match_n):
        """Per-step topology verdicts for a group-g pod from the carry maps
        (committed pods 0..b-1 already folded in). Node-space maps make
        every query a dynamic-slice by group id — no device gathers."""
        fail1 = forbid1_n[g]                                      # [N]
        fail2 = map2_n[g]                                         # [N]
        # required affinity incl. committed pods (step_affinity_ok)
        term_used = aff_tk_g[g] != NONE                           # [A]
        term_ok = term_static_g[g] | pres_n[g].T                  # [N, A]
        pods_exist = jnp.all(term_ok | ~term_used[None], axis=1)
        all_lbl = jnp.all(has_lbl_g[g] | ~term_used[None], axis=1)
        any_match = aff_any_g[g] | any3[g]
        self_ok = aff_self_g[g] & ~any_match & all_lbl
        aff_ok = jnp.where(jnp.any(term_used), pods_exist | self_ok, True)
        ipa_ok = ipa_anti_g[g] & ~fail1 & ~fail2 & aff_ok
        # spread with live counts (step_spread semantics, gather-free:
        # domain-space counts feed the min, node-space counts the match)
        used = tsc_tk_g[g] != NONE                                # [C]
        used_hard = used & tsc_hard_g[g]
        used_soft = used & ~tsc_hard_g[g]
        cnt_live = cnt_g[g] + cntmap[g]                           # [C, D]
        exists = exists_hard_g[g]
        min_cnt = jnp.min(jnp.where(exists, cnt_live, jnp.inf), axis=1)
        min_cnt = jnp.where(jnp.isfinite(min_cnt), min_cnt, 0.0)
        min_cnt = jnp.where((tsc_mind_g[g] > 0)
                            & (num_domains_g[g] < tsc_mind_g[g]),
                            0.0, min_cnt)                         # [C]
        match_num = match_static_g[g] + cnt_match_n[g].T          # [N, C]
        skew = match_num + self_g[g][None] - min_cnt[None]
        ok_c = dom_ok_g[g] & (skew <= tsc_skew_g[g][None])
        sp_ok = jnp.all(ok_c | ~used_hard[None], axis=1)          # [N]
        per_c = match_num * tpw_g[g][None] \
            + (tsc_skew_g[g][None].astype(jnp.float32) - 1.0)
        per_c = jnp.where(used_soft[None] & dom_ok_g[g], per_c, 0.0)
        sp_r = jnp.where(ign_g[g], 0.0, jnp.sum(per_c, axis=1))
        # ipa score with committed-pod weighted deltas
        ipa_live = ipa_raw_g[g] + wscore_n[g]
        return ipa_ok, sp_ok, sp_r, ipa_live

    arange_tk_f = jnp.arange(tk_cap)
    arange_d = jnp.arange(d_cap)

    def tk_onehot(tk):
        """[..., TK] f32 one-hot of term keys (NONE -> zero row): turns every
        per-step key lookup into a tiny matmul instead of a device gather."""
        return ((tk[..., None] == arange_tk_f) & (tk[..., None] != NONE)
                ).astype(jnp.float32)

    if enable_topology and topo_soft:
        # soft-scan one-hots + the per-commit update (the soft subset of
        # map_updates: weighted paff/panti score deltas + node-space
        # spread match counts; everything else is neutral for a
        # soft-only batch and never compiles)
        oh_paff_soft = tk_onehot(soft_st.paff_tk_g)
        oh_panti_soft = tk_onehot(soft_st.panti_tk_g)
        oh_tsc_soft = tk_onehot(soft_st.tsc_tk_g)
        el_node_soft_nr = jnp.transpose(soft_st.el_node_g, (1, 0, 2))
        M_paff_soft = soft_st.M_paff_gg.astype(jnp.float32)
        M_panti_soft = soft_st.M_panti_gg.astype(jnp.float32)
        paff_w_soft = soft_st.paff_w_g
        panti_w_soft = soft_st.panti_w_g

        @jax.named_scope("scan_map_updates")
        def soft_map_updates(g, r, do, wscore_n, cnt_match_n):
            dom_row = topo_dom[r]                              # [TK]
            same_dom = ((topo_dom == dom_row[None])
                        & (dom_row[None] != NONE)
                        & do).astype(jnp.float32)              # [N, TK]
            j_side = ((same_dom @ oh_paff_soft[g].T)
                      @ (M_paff_soft[g] * paff_w_soft[g][:, None])
                      - (same_dom @ oh_panti_soft[g].T)
                      @ (M_panti_soft[g]
                         * panti_w_soft[g][:, None]))          # [N, G]
            nd_gb_paff = jnp.einsum("nt,gat->nga", same_dom,
                                    oh_paff_soft)
            nd_gb_panti = jnp.einsum("nt,gat->nga", same_dom,
                                     oh_panti_soft)
            b_side = (jnp.einsum("nga,ga->gn", nd_gb_paff,
                                 M_paff_soft[:, :, g] * paff_w_soft)
                      - jnp.einsum("nga,ga->gn", nd_gb_panti,
                                   M_panti_soft[:, :, g]
                                   * panti_w_soft))
            wscore_n = wscore_n + j_side.T + b_side
            el_r = el_node_soft_nr[r]                          # [G, C]
            hits_c = soft_st.M_tsc_gg[:, :, g] & el_r
            nd_gb_tsc = jnp.einsum("nt,gct->ngc", same_dom,
                                   oh_tsc_soft)
            cnt_match_n = cnt_match_n + jnp.einsum(
                "ngc,gc->gcn", nd_gb_tsc,
                hits_c.astype(jnp.float32))
            return wscore_n, cnt_match_n
    if enable_topology and not topo_soft:
        oh_anti_own = tk_onehot(anti_tk_g)  # [G, A, TK] (each group's terms)
        oh_aff_own = tk_onehot(aff_tk_g)
        oh_paff_own = tk_onehot(paff_tk_g)
        oh_panti_own = tk_onehot(panti_tk_g)
        oh_tsc_own = tk_onehot(tsc_tk_g)    # [G, C, TK]

    @jax.named_scope("scan_map_updates")
    def map_updates(g, r, do, forbid1_n, map2_n, pres_n, any3, wscore_n,
                    cntmap, cnt_match_n):
        """Fold ONE commit (group-g pod on node row r) into the carry maps.
        Everything is dense compares / tiny matmuls against the committed
        node's domain row — no scatters, no gathers (TPU runs both ~100x
        below bandwidth)."""
        dom_row = topo_dom[r]                                     # [TK]
        # same_dom[n, t]: node n shares the committed node's domain under
        # topology key t (the ONE [N, TK] compare all updates contract with)
        same_dom = ((topo_dom == dom_row[None]) & (dom_row[None] != NONE)
                    & do).astype(jnp.float32)                     # [N, TK]
        dom_row_f = dom_row.astype(jnp.float32)
        nonef = jnp.float32(NONE)

        # j-side (committed pod's own terms, keys [A]): [N, A] same-domain
        oh_j_anti = oh_anti_own[g]                                # [A, TK]
        oh_j_aff = oh_aff_own[g]
        oh_j_paff = oh_paff_own[g]
        oh_j_panti = oh_panti_own[g]
        nd_j_anti = same_dom @ oh_j_anti.T                        # [N, A]
        nd_j_aff = same_dom @ oh_j_aff.T
        # forbid1_n: j's anti terms forbid same-domain nodes for groups they
        # match
        m1 = M_anti_gg[g].astype(jnp.float32)                     # [A, G]
        forbid1_n = forbid1_n | ((nd_j_anti @ m1).T > 0)          # [G, N]
        # b-side (each group's own terms vs the committed pod)
        nd_gb_anti = jnp.einsum("nt,gat->nga", same_dom, oh_anti_own)
        m2 = M_anti_gg[:, :, g].astype(jnp.float32)               # [G, A]
        map2_n = map2_n | (jnp.einsum("nga,ga->gn", nd_gb_anti, m2) > 0)
        nd_gb_aff = jnp.einsum("nt,gat->nga", same_dom, oh_aff_own)
        m3 = M_aff_gg[:, :, g]                                    # [G, A]
        pres_n = pres_n | (jnp.einsum("nga,ga->gan", nd_gb_aff,
                                      m3.astype(jnp.float32)) > 0)
        d3 = oh_aff_own @ dom_row_f                               # [G, A]
        dv3 = (d3 != nonef) & (jnp.sum(oh_aff_own, -1) > 0)
        any3 = any3 | (jnp.any(m3 & dv3, axis=1) & do)
        # weighted ipa score deltas (scoring.go processExistingPod, all five
        # directions of the old per-step scatter groups)
        hw = jnp.full(aff_tk_g.shape[1], HARD_POD_AFFINITY_WEIGHT,
                      jnp.float32)
        j_side = (nd_j_aff @ (M_aff_gg[g].astype(jnp.float32) * hw[:, None])
                  + (same_dom @ oh_j_paff.T)
                  @ (M_paff_gg[g].astype(jnp.float32)
                     * paff_w_g[g][:, None])
                  - (same_dom @ oh_j_panti.T)
                  @ (M_panti_gg[g].astype(jnp.float32)
                     * panti_w_g[g][:, None]))                    # [N, G]
        nd_gb_paff = jnp.einsum("nt,gat->nga", same_dom, oh_paff_own)
        nd_gb_panti = jnp.einsum("nt,gat->nga", same_dom, oh_panti_own)
        b_side = (jnp.einsum("nga,ga->gn", nd_gb_paff,
                             M_paff_gg[:, :, g] * paff_w_g)
                  - jnp.einsum("nga,ga->gn", nd_gb_panti,
                               M_panti_gg[:, :, g] * panti_w_g))
        wscore_n = wscore_n + j_side.T + b_side
        # spread counts: domain-space (for the min) + node-space (for match)
        el_r = el_node_nr[r]                                      # [G, C]
        hits_c = M_tsc_gg[:, :, g] & el_r                         # [G, C]
        d_c = oh_tsc_own @ dom_row_f                              # [G, C]
        dv_c = hits_c & (d_c != nonef) & (jnp.sum(oh_tsc_own, -1) > 0) & do
        cntmap = cntmap + (dv_c[..., None]
                           & (d_c[..., None] == arange_d)
                           ).astype(jnp.float32)                  # [G, C, D]
        nd_gb_tsc = jnp.einsum("nt,gct->ngc", same_dom, oh_tsc_own)
        cnt_match_n = cnt_match_n + jnp.einsum(
            "ngc,gc->gcn", nd_gb_tsc, hits_c.astype(jnp.float32))
        return forbid1_n, map2_n, pres_n, any3, wscore_n, cntmap, cnt_match_n

    def body(carry, xs):
        if pct_nodes:
            carry, start = carry[:-1], carry[-1]
        if enable_topology and topo_soft:
            # soft scan: the only live topology state is the weighted
            # score carry + node-space spread counts; feasibility is the
            # STATIC table mask (in-batch commits cannot constrain)
            (free, nzr, committed_rows, wscore_n, cnt_match_n) = carry
            (b, ok_s, t_raw, a_raw, im, req, nzreq, ptb, g) = xs
            ipa_ok = soft_st.ipa_ok_g[g]
            sp_ok = jnp.ones_like(ok_s)
            used_soft = soft_st.used_soft_g[g]
            match_num = (soft_st.match_static_g[g]
                         + cnt_match_n[g].T)                   # [N, C]
            per_c = (match_num * soft_st.tpw_g[g][None]
                     + (soft_st.skew_g[g][None] - 1.0))
            per_c = jnp.where(used_soft[None] & soft_st.dom_ok_g[g],
                              per_c, 0.0)
            sp_r = jnp.where(soft_st.ign_g[g], 0.0,
                             jnp.sum(per_c, axis=1))
            ipa_live = soft_st.ipa_raw_g[g] + wscore_n[g]
            ign_b = soft_st.ign_g[g]
            soft_b = soft_st.has_soft_g[g]
        elif enable_topology:
            (free, nzr, committed_rows, forbid1_n, map2_n, pres_n, any3,
             wscore_n, cntmap, cnt_match_n) = carry
            (b, ok_s, t_raw, a_raw, im, req, nzreq, ptb, g) = xs
            ipa_ok, sp_ok, sp_r, ipa_live = queries(
                g, forbid1_n, map2_n, pres_n, any3, wscore_n, cntmap,
                cnt_match_n)
            if not spread_on:   # filter disabled by config (score may stay)
                sp_ok = jnp.ones_like(sp_ok)
            if not ipa_on:
                ipa_ok = jnp.ones_like(sp_ok)
            ign_b = ign_g[g]
            soft_b = has_soft_g[g]
        else:
            (free, nzr, committed_rows) = carry
            (b, ok_s, t_raw, a_raw, im, req, nzreq, ptb) = xs
            ones = jnp.ones_like(ok_s)
            sp_ok = ipa_ok = ones
            sp_r = ipa_live = jnp.zeros_like(t_raw)
            ign_b = ~ones
            soft_b = jnp.bool_(False)
        if fit_on:
            # nominated preemptors reserve their requests on their nominated
            # node (framework.go:989 AddPod pass); a pod's OWN nomination is
            # handed back so it can claim the room its victims vacated
            own = (jnp.arange(free.shape[0]) == pods.nominated_row[b])
            eff = free - ct.nominated_req + jnp.where(own[:, None], req[None],
                                                      0.0)
            fit_ok = jnp.all(req[None] <= eff, axis=-1)         # [N]
        else:
            fit_ok = jnp.ones(free.shape[0], bool)
        # nodes holding an earlier batch commit that clashes on hostPort
        clash = port_conf[b] & (committed_rows >= 0)            # [B]
        forbidden = jnp.zeros_like(fit_ok).at[
            jnp.maximum(committed_rows, 0)].max(clash)          # [N]
        ports_ok = ~forbidden
        feasible = ok_s & ports_ok & fit_ok & sp_ok & ipa_ok
        if pct_nodes:
            # percentageOfNodesToScore early-exit parity
            # (schedule_one.go:668-694): visit nodes in rotating order from
            # `start`, stop once k feasible are found, score only those.
            # Unnecessary for TPU throughput (all nodes are scored in one
            # launch regardless) but preserves the reference's node-subset
            # SELECTION semantics when the knob is set. reject_counts stay
            # full-cluster (better diagnostics than the reference's
            # partial-visit counts; documented divergence). Padding rows are
            # never feasible, so they only inflate `processed` bookkeeping.
            n_total = feasible.shape[0]
            nv = num_valid.astype(jnp.int32)
            if pct_nodes == ADAPTIVE_PCT:
                # explicit 0 in config = the reference's adaptive formula
                # (numFeasibleNodesToFind, schedule_one.go:668-694):
                # pct = 50 - nodes/125, floored at 5
                eff = jnp.maximum(jnp.int32(5), 50 - nv // 125)
            else:
                eff = jnp.int32(pct_nodes)
            k_find = jnp.maximum(
                jnp.int32(MIN_FEASIBLE_NODES_TO_FIND), (nv * eff) // 100)
            rolled = jnp.roll(feasible, -start)
            csum = jnp.cumsum(rolled.astype(jnp.int32))
            feasible = jnp.roll(rolled & (csum <= k_find), start)
            found_k = csum[-1] >= k_find
            kth = jnp.argmax(csum >= k_find).astype(jnp.int32)
            processed = jnp.where(found_k, kth + 1, n_total)
            # Advance in row space, then SNAP to the next valid row so
            # nextStartNodeIndex never dwells on padding/hole regions —
            # matching the reference's rotation cadence over real nodes
            # (schedule_one.go:620) while row layout may have holes.
            start = (start + processed) % n_total
            start = (start + jnp.argmax(jnp.roll(valid, -start))) % n_total
        frac = SC.utilization_fractions(alloc2, nzr, nzreq)
        least = SC.fit_score_from_fractions(frac, fit_strategy, fit_shape)
        bal = SC.balanced_allocation_from_fractions(frac)
        taint = SC.normalize_inverse(t_raw, feasible)
        aff = SC.normalize_max(a_raw, feasible)
        ipa = SC.normalize_maxmin(ipa_live, feasible)
        spread = jnp.where(soft_b,
                           SC.normalize_spread(sp_r, feasible, ign_b), 0.0)
        total = (weights.taint_toleration * taint
                 + weights.node_affinity * aff
                 + weights.resources_fit * least
                 + weights.balanced_allocation * bal
                 + weights.image_locality * im
                 + weights.pod_topology_spread * spread
                 + weights.inter_pod_affinity * ipa)
        if host_score is not None:
            total = total + host_score[b]
        row = C.masked_argmax_random(total, feasible, ptb)
        # commit the winner (the "assume"): free -= request, nonzero += request
        do = row >= 0
        r = jnp.maximum(row, 0)
        free = free.at[r].add(jnp.where(do, -req, 0.0))
        nzr = nzr.at[r].add(jnp.where(do, nzreq, 0.0))
        committed_rows = committed_rows.at[b].set(row)
        # first-fail order: NodePorts (in-batch), Fit, Spread, InterPod
        ok_ports = ok_s & ports_ok
        ok_fit = ok_ports & fit_ok
        ok_sp = ok_fit & sp_ok
        port_rejects = jnp.sum(ok_s & ~ports_ok).astype(jnp.int32)
        fit_rejects = jnp.sum(ok_ports & ~fit_ok).astype(jnp.int32)
        sp_rejects = jnp.sum(ok_fit & ~sp_ok).astype(jnp.int32)
        ipa_rejects = jnp.sum(ok_sp & ~ipa_ok).astype(jnp.int32)
        win = jnp.where(do, total[r], 0.0)
        if enable_topology and topo_soft:
            wscore_n, cnt_match_n = soft_map_updates(
                g, r, do, wscore_n, cnt_match_n)
            out_carry = (free, nzr, committed_rows, wscore_n,
                         cnt_match_n)
        elif enable_topology:
            (forbid1_n, map2_n, pres_n, any3, wscore_n, cntmap,
             cnt_match_n) = map_updates(
                g, r, do, forbid1_n, map2_n, pres_n, any3, wscore_n,
                cntmap, cnt_match_n)
            out_carry = (free, nzr, committed_rows, forbid1_n, map2_n,
                         pres_n, any3, wscore_n, cntmap, cnt_match_n)
        else:
            out_carry = (free, nzr, committed_rows)
        if pct_nodes:
            out_carry = out_carry + (start,)
        ys = (row, win, jnp.sum(feasible).astype(jnp.int32),
              port_rejects, fit_rejects, sp_rejects, ipa_rejects)
        return out_carry, ys

    xs = (jnp.arange(B), static_ok, taint_raw, aff_raw, img,
          pods.req, pods.nonzero_req, perturb_rows)
    init = (free0, nzr0, jnp.full((B,), -1, jnp.int32))
    if enable_topology and topo_soft:
        xs = xs + (gid,)
        n_cap = free0.shape[0]
        C_cap = soft_st.tsc_tk_g.shape[1]
        init = init + (
            jnp.zeros((g_cap, n_cap), jnp.float32),       # wscore_n
            jnp.zeros((g_cap, C_cap, n_cap), jnp.float32),   # cnt_match_n
        )
    elif enable_topology:
        xs = xs + (gid,)
        A_cap = anti_tk_g.shape[1]
        C_cap = tsc_tk_g.shape[1]
        n_cap = free0.shape[0]
        init = init + (
            jnp.zeros((g_cap, n_cap), bool),              # forbid1_n
            jnp.zeros((g_cap, n_cap), bool),              # map2_n (own anti)
            jnp.zeros((g_cap, A_cap, n_cap), bool),       # pres_n (affinity)
            jnp.zeros((g_cap,), bool),                    # any3
            jnp.zeros((g_cap, n_cap), jnp.float32),       # wscore_n
            jnp.zeros((g_cap, C_cap, d_cap), jnp.float32),   # cntmap
            jnp.zeros((g_cap, C_cap, n_cap), jnp.float32),   # cnt_match_n
        )
    if pct_nodes:
        # rotating nextStartNodeIndex, seeded from the previous launch's
        # BatchResult.pct_start so rotation persists ACROSS batches
        init = init + (jnp.int32(0) if pct_start is None
                       else jnp.asarray(pct_start, jnp.int32),)
    # The scan's length follows the batch, not the bucket: blocks of u
    # steps (the body is many small fused kernels, so per-iteration
    # dispatch overhead is a real cost at these shapes and a block
    # amortizes it), as many as reach the last row that carries a pod. A
    # step on a padding row changes no carry (static_ok all false, row -1,
    # do false; under pct_nodes `start` comes back where it was), so the
    # rows past the last block keep what such a step writes.
    u = _scan_block(B)
    n_live = jnp.max(jnp.where(pods.valid,
                               jnp.arange(1, B + 1, dtype=jnp.int32), 0))
    n_blocks = (n_live + (u - 1)) // u
    pad = (-B) % u
    if pad:
        # direct callers only (the host's buckets are multiples of u): the
        # last block's steps past B see rows no node accepts
        xs = (jnp.arange(B + pad),) + jax.tree.map(
            lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)),
            xs[1:])
    ys_fill = [-1, 0.0, 0, 0, 0, 0, 0]       # row, win, feas, 4 rejects
    ys_sds = jax.eval_shape(lambda c, x: body(c, x)[1], init,
                            jax.tree.map(lambda x: x[0], xs))
    ys_init = tuple(jnp.full((B + pad,) + sd.shape, fill, sd.dtype)
                    for sd, fill in zip(ys_sds, ys_fill, strict=True))

    # traced once and called u times a block, as lax.scan's unroll did
    step = jax.jit(body)

    def block(k, state):
        carry, ys_buf = state
        for j in range(u):
            i = k * u + j
            carry, ys = step(carry, jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, False), xs))
            ys_buf = tuple(jax.lax.dynamic_update_index_in_dim(buf, y, i, 0)
                           for buf, y in zip(ys_buf, ys))
        return carry, ys_buf

    with jax.named_scope("commit_scan"):
        (carry_out, ys_out) = jax.lax.fori_loop(0, n_blocks, block,
                                                (init, ys_init))
    if pad:
        ys_out = tuple(y[:B] for y in ys_out)
    (rows, win_scores, feas, port_rejects, fit_rejects, sp_rejects,
     ipa_rejects) = ys_out
    free_out, nzr_out = carry_out[0], carry_out[1]
    start_out = carry_out[-1] if pct_nodes else jnp.int32(0)

    ports_idx = FILTER_PLUGINS.index("NodePorts")
    static_rejects = static_rejects.at[:, ports_idx].add(port_rejects)
    reject_counts = jnp.concatenate(
        [static_rejects, fit_rejects[:, None], sp_rejects[:, None],
         ipa_rejects[:, None]], axis=1)
    return BatchResult(node_row=rows, score=win_scores, feasible_count=feas,
                       reject_counts=reject_counts, unresolvable_count=unres,
                       free=free_out, nzr=nzr_out, pct_start=start_out,
                       guard=_guard_reduction(win_scores, free_out),
                       dra_reject=dra_reject,
                       scan_steps=jnp.minimum(n_blocks * u, B),
                       table_blocks=(T.table_blocks(ct) if enable_topology
                                     else jnp.int32(0)))


@partial(jax.jit, static_argnames=("caps", "enable_topology", "d_cap",
                                   "enabled_filters", "serial_scan",
                                   "active", "pfields", "g_cap",
                                   "fit_strategy", "pct_nodes",
                                   "topo_soft", "auction_unroll"))
def schedule_batch_jit(cblobs, pblobs, wk, weights, caps,
                       enable_topology=True, d_cap=None,
                       enabled_filters=None, serial_scan=True, state=None,
                       active=None, pfields=None, ptmpl=None,
                       gid=None, rep=None, g_cap=0, host_ok=None,
                       host_score=None, fit_strategy="LeastAllocated",
                       fit_shape=None, pct_nodes=0, pct_start=None,
                       dra=None, tie_seed=None,
                       topo_soft=False, auction_unroll=None):
    return schedule_batch(cblobs, pblobs, wk, weights, caps,
                          enable_topology, d_cap, enabled_filters,
                          serial_scan, state, active, pfields, ptmpl,
                          gid, rep, g_cap, host_ok, host_score,
                          fit_strategy, fit_shape, pct_nodes, pct_start,
                          dra, tie_seed, topo_soft, auction_unroll)


@partial(jax.jit, static_argnames=("caps",))
def extract_state_jit(cblobs, caps):
    """(free, nonzero_requested) of a cluster blob — the seed for the
    device-resident usage chain. The Scheduler feeds this to every
    UNCHAINED launch so chained and unchained dispatches share one
    schedule_batch_jit signature (state always present): the warmup pass
    then compiles the exact program the full-scale drain runs, instead of
    a fresh multi-second XLA compile appearing mid-phase the first time a
    drain chains two batches."""
    ct = unpack_cluster(cblobs, caps)
    return ct.free, ct.nonzero_requested


@jax.jit
@jax.named_scope("patch_chain")
def _chain_set_rows_jit(free, nzr, idx, free_rows, nzr_rows):
    return free.at[idx].set(free_rows), nzr.at[idx].set(nzr_rows)


@jax.jit
@jax.named_scope("patch_chain")
def _chain_add_rows_jit(free, nzr, idx, free_rows, nzr_rows):
    return free.at[idx].add(free_rows), nzr.at[idx].add(nzr_rows)


def patch_chain(free, nzr, set_rows=(), add_rows=()):
    """Scatter node-row patches into the device-resident (free, nzr) usage
    chain IN PLACE of a full snapshot resync — the device half of
    chain-surviving churn. This generalizes the gang packer's free/nzr
    chunk-chaining protocol (ops.gang.pack_gangs ``state=``): the chain is
    the single mutable device truth between launches, and everyone who
    learns something about a node — a committed chunk, an informer event —
    folds it in rather than rebuilding the world.

    ``set_rows`` carries absolute repacks (node add/update/remove):
    ``(row, free_row [R], nzr_row [2])`` tuples whose rows REPLACE the
    chain's. ``add_rows`` carries commutative usage deltas (foreign pod
    bind/delete): ``(row, dfree [R], dnzr [2])`` tuples ADDED to the
    chain's rows, so they compose with in-flight waves' device commits in
    either order. Row lists are padded host-side to the next power of two
    (sets duplicate their last entry — idempotent; adds pad zero rows —
    identity) so launch shapes stay in a tiny bucket family and a drain
    never recompiles on patch count. Donation is deliberately off: the
    input chain may still be referenced by an in-flight wave's pending
    tuple. Returns the patched (free, nzr)."""
    import numpy as _np

    def _pad(rows, dup):
        k = len(rows)
        cap = 1
        while cap < k:
            cap *= 2
        idx = _np.empty((cap,), _np.int32)
        fr = _np.zeros((cap, free.shape[1]), _np.float32)
        nz = _np.zeros((cap, nzr.shape[1]), _np.float32)
        for i, (r, f, n) in enumerate(rows):
            idx[i] = r
            fr[i] = f
            nz[i] = n
        for i in range(k, cap):
            idx[i] = rows[-1][0]
            if dup:
                fr[i] = rows[-1][1]
                nz[i] = rows[-1][2]
        return idx, fr, nz
    if set_rows:
        free, nzr = _chain_set_rows_jit(free, nzr, *_pad(set_rows, True))
    if add_rows:
        free, nzr = _chain_add_rows_jit(free, nzr, *_pad(add_rows, False))
    return free, nzr


def warm_patch_chain(free, nzr, max_bucket: int = 256) -> None:
    """Pre-compile every patch-scatter bucket the scheduler can ever
    launch against this chain shape (pow2 buckets up to the scheduler's
    patch cap, beyond which it falls back to a full resync). Called once
    per chain shape at first install so churn patches never trigger an
    XLA compile mid-drain — the patch kernels ride launch_cache_size, so
    the bench's flat-cache assertion would catch a miss here."""
    import numpy as _np

    cap = 1
    while cap <= max_bucket:
        idx = _np.zeros((cap,), _np.int32)
        fr = _np.zeros((cap, free.shape[1]), _np.float32)
        nz = _np.zeros((cap, nzr.shape[1]), _np.float32)
        a = _chain_set_rows_jit(free, nzr, idx, fr, nz)
        b = _chain_add_rows_jit(free, nzr, idx, fr, nz)
        jax.block_until_ready((a, b))
        cap *= 2


def launch_programs() -> tuple:
    """The jitted programs behind every scheduling launch:
    schedule_batch_jit, the state-extraction seed, the chain patch
    scatters, and the gang packer (so a gang-shape recompile is
    attributed to its launch)."""
    # imported lazily: ops.gang traces against this module's
    # static_filters
    from kubernetes_tpu.ops.gang import pack_gangs_jit

    return (schedule_batch_jit, extract_state_jit, pack_gangs_jit,
            _chain_set_rows_jit, _chain_add_rows_jit)


def launch_cache_size() -> int:
    """Executable-cache entries behind the launch programs: the
    DeviceProfiler reads this after each dispatch — growth means a real
    XLA compile happened while tracing that launch."""
    return sum(fn._cache_size() for fn in launch_programs())


def launch_batch(spec, wk, weights, caps, enabled_filters=None,
                 serial_scan=True, state=None, host_ok=None,
                 host_score=None, fit_strategy="LeastAllocated",
                 fit_shape=None, pct_nodes=0, pct_start=None,
                 tie_seed=None) -> BatchResult:
    """schedule_batch_jit driven by a Mirror.prepare_launch LaunchSpec."""
    return schedule_batch_jit(
        spec.cblobs, spec.pblobs, wk, weights, caps,
        spec.enable_topology, spec.d_cap, enabled_filters,
        serial_scan=serial_scan, state=state, active=spec.active,
        pfields=spec.pfields, ptmpl=spec.ptmpl,
        gid=spec.gid, rep=spec.rep, g_cap=spec.g_cap,
        host_ok=host_ok, host_score=host_score,
        fit_strategy=fit_strategy, fit_shape=fit_shape,
        pct_nodes=pct_nodes, pct_start=pct_start, dra=spec.dra,
        tie_seed=tie_seed, topo_soft=spec.topo_soft)
