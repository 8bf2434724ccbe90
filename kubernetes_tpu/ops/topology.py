"""InterPodAffinity + PodTopologySpread as topology-domain tensor kernels.

The reference computes per-pod PreFilter state by scanning all pods on all
nodes into `(topologyKey, topologyValue) -> count` hash maps
(interpodaffinity/filtering.go:204-272, podtopologyspread/filtering.go:235+)
and then does per-node map lookups. The TPU-native formulation replaces the
hash maps with dense per-topology-key domain arrays:

- every registered topology key tk has a compact domain-id space [0, D);
  a node's domain under tk is ``ct.topo_dom[n, tk]`` (NONE = label absent);
- "existing pod p affects all nodes in its domain" becomes a scatter of
  per-(pod-slot, term) matches into a ``[TK or A or C, D]`` map;
- "node n looks up its (key, value) pair" becomes a gather of that map at
  ``topo_dom[n, tk]``.

Scatter + gather over dense domain ids is exactly the XLA-friendly shape of
the reference's two-phase build/lookup — one launch, no hashing, vmappable
over the pod batch.

Reference semantics implemented here:
- interpodaffinity/filtering.go: satisfyExistingPodsAntiAffinity (:352),
  satisfyPodAntiAffinity (:367), satisfyPodAffinity (:382) including the
  first-pod-of-a-group rule.
- interpodaffinity/scoring.go: processExistingPod (:81-123) — incoming
  preferred terms both directions, existing pods' required terms at
  hardPodAffinityWeight, existing pods' preferred terms.
- podtopologyspread/filtering.go: skew = matchNum + selfMatchNum -
  minMatchNum > maxSkew (:311), minDomains (:300), node-inclusion policies.
- podtopologyspread/scoring.go: scoreForCount (:300) with
  topologyNormalizingWeight = log(size + 2) (:292).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import common as C
from kubernetes_tpu.ops.features import (  # noqa: F401  (IMPOSSIBLE re-export)
    IMPOSSIBLE,
    Capacities,
    ClusterTensors,
    PodFeatures,
    codecs,
)
from kubernetes_tpu.ops.blobs import Blobs
from kubernetes_tpu.utils.interner import NONE


def take_cols(table: jnp.ndarray, cols: jnp.ndarray, fill) -> jnp.ndarray:
    """table: [R, K]; cols: [...] i32 (NONE allowed). -> [R, *cols.shape]."""
    k = table.shape[1]
    safe = jnp.clip(cols, 0, k - 1)
    out = jnp.take(table, safe.reshape(-1), axis=1)
    out = out.reshape((table.shape[0],) + cols.shape)
    return jnp.where(cols[None] >= 0, out, fill)


def slot_topo_dom(ct: ClusterTensors) -> jnp.ndarray:
    """[PT, TK]: topology domain of each table pod's node per topo key.
    Shared across the whole batch — compute once per launch."""
    tds = ct.topo_dom[jnp.maximum(ct.pod_node, 0)]
    return jnp.where(ct.pod_valid[:, None], tds, NONE)


def sel_match(ops: jnp.ndarray, vals: jnp.ndarray,
              tgt_vals: jnp.ndarray) -> jnp.ndarray:
    """Full LabelSelector match over op-coded expressions.

    ops: [..., MS] (NONE = unused slot); vals: [..., MS, V]; tgt_vals:
    [..., MS] = target's label value gathered at each expression's column
    (NONE = label absent). Semantics follow apimachinery labels.Requirement:
    In = present & value in set; NotIn = !present | value not in set;
    Exists = present; DoesNotExist = !present; unknown op matches nothing.
    Returns [...] bool: AND over used expressions."""
    from kubernetes_tpu.ops.features import (
        OP_DOES_NOT_EXIST, OP_EXISTS, OP_IN, OP_NOT_IN)

    present = tgt_vals != NONE
    inin = present & C.isin(tgt_vals, vals)
    m = jnp.where(ops == OP_IN, inin,
        jnp.where(ops == OP_NOT_IN, ~inin,
        jnp.where(ops == OP_EXISTS, present,
        jnp.where(ops == OP_DOES_NOT_EXIST, ~present, False))))
    return jnp.all(m | (ops == NONE), axis=-1)


def table_mask(ct: ClusterTensors, pod: PodFeatures,
               include_nominated: bool) -> jnp.ndarray:
    """[PT]: which table pods count for this incoming pod. Always excludes
    the pod's own entry (incl. its own nomination); nominated pods count
    only for anti-affinity constraints, not for required-affinity presence,
    scoring, or spread counts (the dual-pass rule of
    RunFilterPluginsWithNominatedPods, runtime/framework.go:989)."""
    m = ct.pod_valid & (ct.pod_uid != pod.uid_id)
    if not include_nominated:
        m = m & ~ct.pod_nominated
    return m


def incoming_terms_vs_table(ct: ClusterTensors, tbl_ok: jnp.ndarray,
                            tk: jnp.ndarray,
                            ns: jnp.ndarray, ns_all: jnp.ndarray,
                            sel_cols: jnp.ndarray, sel_ops: jnp.ndarray,
                            sel_vals: jnp.ndarray) -> jnp.ndarray:
    """[PT, A]: does table pod s satisfy the incoming pod's term a?
    (AffinityTerm.Matches: s.ns in term.namespaces (or all-ns) and the
    selector expressions match s's labels). tbl_ok: [PT] from table_mask."""
    ns_ok = C.isin(ct.pod_ns[:, None], ns[None]) | ns_all[None]  # [PT, A]
    tv = take_cols(ct.pt_label_vals, sel_cols, NONE)           # [PT, A, MS]
    sel_ok = sel_match(sel_ops[None], sel_vals[None], tv)      # [PT, A]
    return ns_ok & sel_ok & tbl_ok[:, None] & (tk[None] != NONE)


def table_terms_vs_incoming(ct: ClusterTensors, tbl_ok: jnp.ndarray,
                            grp_tk: jnp.ndarray,
                            grp_ns: jnp.ndarray, grp_ns_all: jnp.ndarray,
                            grp_cols: jnp.ndarray, grp_ops: jnp.ndarray,
                            grp_vals: jnp.ndarray,
                            pod: PodFeatures) -> jnp.ndarray:
    """[PT, A]: does the incoming pod satisfy table pod s's term a?"""
    ns_ok = (jnp.any((grp_ns == pod.ns) & (grp_ns != NONE), axis=-1)
             | grp_ns_all)                                     # [PT, A]
    kp = pod.plabel_vals.shape[0]
    pv = pod.plabel_vals[jnp.clip(grp_cols, 0, kp - 1)]        # [PT, A, MS]
    pv = jnp.where(grp_cols >= 0, pv, NONE)
    sel_ok = sel_match(grp_ops, grp_vals, pv)                  # [PT, A]
    return ns_ok & sel_ok & (grp_tk != NONE) & tbl_ok[:, None]


def scatter_or(tk2d: jnp.ndarray, dom2d: jnp.ndarray, hit2d: jnp.ndarray,
               num_rows: int, d_cap: int) -> jnp.ndarray:
    """[num_rows, d_cap] bool: OR of hits at (row=tk2d, col=dom2d)."""
    ok = hit2d & (tk2d != NONE) & (dom2d != NONE)
    flat = jnp.clip(tk2d, 0) * d_cap + jnp.clip(dom2d, 0)
    m = jnp.zeros((num_rows * d_cap,), bool)
    m = m.at[flat.reshape(-1)].max(ok.reshape(-1))
    return m.reshape(num_rows, d_cap)


def gather_rows(m: jnp.ndarray, dom: jnp.ndarray):
    """m: [R, D]; dom: [N, R] domain per node per row -> m[r, dom[n, r]]
    masked where dom is NONE (False/0)."""
    r = m.shape[0]
    vals = m[jnp.arange(r)[None, :], jnp.clip(dom, 0)]
    zero = jnp.zeros((), m.dtype)
    return jnp.where(dom != NONE, vals, zero)


# ----------------- in-batch (committed pods) machinery -----------------
#
# The batched commit scan must preserve as-if-serial semantics: pod b has to
# see pods 0..b-1's placements exactly as the serial loop's assume step
# would provide (schedule_one.go:938). For the topology plugins that means
# pairwise GROUP<->GROUP term matches are precomputed OUTSIDE the scan
# (labels and terms don't depend on placement; pods dedup into groups,
# Mirror._batch_groups), and the scan folds each commit into small node-
# space carry maps with dense compares — see pipeline.map_updates. TPU
# scatters/gathers run ~100x below bandwidth, so nothing in the per-step
# path scatters or gathers by domain.


def pair_term_match(tk: jnp.ndarray, ns: jnp.ndarray, ns_all: jnp.ndarray,
                    cols: jnp.ndarray, ops: jnp.ndarray, vals: jnp.ndarray,
                    tgt_labels: jnp.ndarray, tgt_ns: jnp.ndarray,
                    tgt_valid: jnp.ndarray) -> jnp.ndarray:
    """[Bx, A, By]: does batch pod y satisfy batch pod x's term a?

    tk [Bx, A]; ns [Bx, A, NS]; ns_all [Bx, A]; cols/ops [Bx, A, MS];
    vals [Bx, A, MS, V]; tgt_labels [By, Kp]; tgt_ns/tgt_valid [By]."""
    kp = tgt_labels.shape[1]
    pv = tgt_labels.T[jnp.clip(cols, 0, kp - 1)]       # [Bx, A, MS, By]
    pv = jnp.where(cols[..., None] >= 0, pv, NONE)
    # move By before MS so sel_match reduces over its last-but-one layout:
    # [Bx, A, By, MS] vs vals broadcast [Bx, A, 1, MS, V]
    pv = jnp.moveaxis(pv, -1, -2)                       # [Bx, A, By, MS]
    sel_ok = sel_match(ops[..., None, :], vals[..., None, :, :], pv)
    ns_ok = (jnp.any((ns[..., :, None] == tgt_ns[None, None, None, :])
                     & (ns[..., :, None] != NONE), axis=2)
             | ns_all[..., None])                       # [Bx, A, By]
    return (ns_ok & sel_ok & (tk[..., None] != NONE)
            & tgt_valid[None, None, :])


def pair_tsc_match(pods: PodFeatures) -> jnp.ndarray:
    """[Bx, C, By]: does batch pod y match batch pod x's spread constraint c?
    (same namespace + selector expressions over y's labels)"""
    kp = pods.plabel_vals.shape[1]
    pv = pods.plabel_vals.T[jnp.clip(pods.tsc_sel_cols, 0, kp - 1)]
    pv = jnp.where(pods.tsc_sel_cols[..., None] >= 0, pv, NONE)
    pv = jnp.moveaxis(pv, -1, -2)                       # [Bx, C, By, MS]
    sel_ok = sel_match(pods.tsc_sel_ops[..., None, :],
                       pods.tsc_sel_vals[..., None, :, :], pv)
    ns_ok = pods.ns[:, None, None] == pods.ns[None, None, :]
    return (sel_ok & ns_ok & (pods.tsc_tk[..., None] != NONE)
            & pods.valid[None, None, :])






# --------------------------- InterPodAffinity ---------------------------


@jax.named_scope("inter_pod_affinity")
def ipa_forbid_map(ct: ClusterTensors, pod: PodFeatures, tds: jnp.ndarray,
                   d_cap: int) -> jnp.ndarray:
    """[TK, D] bool: the domains the table forbids the incoming pod, rules
    1 and 2 of the Filter (filtering.go satisfyExistingPodsAntiAffinity,
    satisfyPodAntiAffinity). forbid_ok takes it to node space."""
    tk_cap = ct.topo_dom.shape[1]
    anti_ok_tbl = table_mask(ct, pod, include_nominated=True)

    # 1. existing pods' required anti-affinity vs incoming pod
    m1 = table_terms_vs_incoming(ct, anti_ok_tbl, ct.pod_anti_tk,
                                 ct.pod_anti_ns,
                                 ct.pod_anti_ns_all, ct.pod_anti_sel_cols,
                                 ct.pod_anti_sel_ops, ct.pod_anti_sel_vals,
                                 pod)                              # [PT, A]
    dom1 = jnp.take_along_axis(tds, jnp.clip(ct.pod_anti_tk, 0, tk_cap - 1),
                               axis=1)
    dom1 = jnp.where(ct.pod_anti_tk != NONE, dom1, NONE)
    f1 = scatter_or(ct.pod_anti_tk, dom1, m1, tk_cap, d_cap)       # [TK, D]

    # 2. incoming pod's required anti-affinity vs existing pods
    m2 = incoming_terms_vs_table(ct, anti_ok_tbl, pod.anti_tk, pod.anti_ns,
                                 pod.anti_ns_all, pod.anti_sel_cols,
                                 pod.anti_sel_ops, pod.anti_sel_vals)
    dom2 = tds[:, jnp.clip(pod.anti_tk, 0, tk_cap - 1)]            # [PT, A]
    dom2 = jnp.where(pod.anti_tk[None] != NONE, dom2, NONE)
    tk2 = jnp.broadcast_to(pod.anti_tk[None], m2.shape)
    f2 = scatter_or(tk2, dom2, m2, tk_cap, d_cap)
    # a node fails if either map holds its domain under some key: the OR
    # of the maps gathers to the OR of the two node verdicts
    return f1 | f2


def forbid_ok(ct: ClusterTensors, forbid: jnp.ndarray) -> jnp.ndarray:
    """[N]: no domain of the node is in the forbid map."""
    return ~jnp.any(gather_rows(forbid, ct.topo_dom), axis=1)


@jax.named_scope("inter_pod_affinity")
def ipa_presence_map(ct: ClusterTensors, pod: PodFeatures, tds: jnp.ndarray,
                     d_cap: int):
    """Rule 3, the incoming pod's required affinity: (present [A, D] — the
    domains holding a table pod that term a selects, any_match — some
    table pod matches some term; the first-pod-of-a-group rule)."""
    tk_cap = ct.topo_dom.shape[1]
    pres_tbl = table_mask(ct, pod, include_nominated=False)
    a_cap = pod.aff_tk.shape[0]
    m3 = incoming_terms_vs_table(ct, pres_tbl, pod.aff_tk, pod.aff_ns,
                                 pod.aff_ns_all, pod.aff_sel_cols,
                                 pod.aff_sel_ops, pod.aff_sel_vals)
    dom3 = tds[:, jnp.clip(pod.aff_tk, 0, tk_cap - 1)]             # [PT, A]
    dom3 = jnp.where(pod.aff_tk[None] != NONE, dom3, NONE)
    rows3 = jnp.broadcast_to(jnp.arange(a_cap)[None], m3.shape)
    present = scatter_or(rows3, dom3, m3, a_cap, d_cap)            # [A, D]
    term_used = pod.aff_tk != NONE                                 # [A]
    any_match = jnp.any(m3 & (dom3 != NONE) & term_used[None])
    return present, any_match


def inter_pod_affinity_static(ct: ClusterTensors, pod: PodFeatures,
                              tds: jnp.ndarray, d_cap: int):
    """Pre-batch-table part of the Filter (filtering.go) in one pass over
    the whole table: returns (anti_ok [N] — rules 1+2 vs the table,
    present [A, D] — affinity presence map from the table, any_match —
    scalar). The launch runs the same maps over the table's live blocks
    (table_statics); this form is the reference its tests hold it to. The
    commit scan layers in-batch deltas on top."""
    present, any_match = ipa_presence_map(ct, pod, tds, d_cap)
    return (forbid_ok(ct, ipa_forbid_map(ct, pod, tds, d_cap)), present,
            any_match)


@jax.named_scope("inter_pod_affinity")
def ipa_score_map(ct: ClusterTensors, pod: PodFeatures, tds: jnp.ndarray,
                  d_cap: int, hard_weight: jnp.ndarray) -> jnp.ndarray:
    """[TK, D] f32: the raw score the table gives each domain under each
    key (scoring.go processExistingPod); score_nodes takes it to node
    space. Whole-number terms (weights 1-100), so a sum in any order is
    the same f32 while it stays under 2**24."""
    tk_cap = ct.topo_dom.shape[1]
    score = jnp.zeros((tk_cap * d_cap,), jnp.float32)
    tbl_ok = table_mask(ct, pod, include_nominated=False)

    def add_incoming(score, tk, ns, ns_all, cols, ops, vals, w, sign):
        m = incoming_terms_vs_table(ct, tbl_ok, tk, ns, ns_all, cols, ops,
                                    vals)
        dom = tds[:, jnp.clip(tk, 0, tk_cap - 1)]
        ok = m & (dom != NONE) & (tk[None] != NONE)
        flat = jnp.clip(tk[None], 0) * d_cap + jnp.clip(dom, 0)
        upd = jnp.where(ok, sign * w[None].astype(jnp.float32), 0.0)
        return score.at[flat.reshape(-1)].add(upd.reshape(-1))

    def add_table(score, tk, ns, ns_all, cols, ops, vals, w, sign):
        m = table_terms_vs_incoming(ct, tbl_ok, tk, ns, ns_all, cols, ops,
                                    vals, pod)
        dom = jnp.take_along_axis(tds, jnp.clip(tk, 0, tk_cap - 1), axis=1)
        ok = m & (dom != NONE) & (tk != NONE)
        flat = jnp.clip(tk, 0) * d_cap + jnp.clip(dom, 0)
        upd = jnp.where(ok, sign * w.astype(jnp.float32), 0.0)
        return score.at[flat.reshape(-1)].add(upd.reshape(-1))

    score = add_incoming(score, pod.paff_tk, pod.paff_ns, pod.paff_ns_all,
                         pod.paff_sel_cols, pod.paff_sel_ops,
                         pod.paff_sel_vals, pod.paff_weight, 1.0)
    score = add_incoming(score, pod.panti_tk, pod.panti_ns, pod.panti_ns_all,
                         pod.panti_sel_cols, pod.panti_sel_ops,
                         pod.panti_sel_vals, pod.panti_weight, -1.0)
    hw = jnp.broadcast_to(hard_weight, ct.pod_aff_tk.shape)
    score = add_table(score, ct.pod_aff_tk, ct.pod_aff_ns, ct.pod_aff_ns_all,
                      ct.pod_aff_sel_cols, ct.pod_aff_sel_ops,
                      ct.pod_aff_sel_vals, hw, 1.0)
    score = add_table(score, ct.pod_paff_tk, ct.pod_paff_ns,
                      ct.pod_paff_ns_all, ct.pod_paff_sel_cols,
                      ct.pod_paff_sel_ops, ct.pod_paff_sel_vals,
                      ct.pod_paff_weight, 1.0)
    score = add_table(score, ct.pod_panti_tk, ct.pod_panti_ns,
                      ct.pod_panti_ns_all, ct.pod_panti_sel_cols,
                      ct.pod_panti_sel_ops, ct.pod_panti_sel_vals,
                      ct.pod_panti_weight, -1.0)
    return score.reshape(tk_cap, d_cap)


def score_nodes(ct: ClusterTensors, score: jnp.ndarray) -> jnp.ndarray:
    """[N]: a [TK, D] score map summed over each node's domains."""
    return jnp.sum(gather_rows(score, ct.topo_dom), axis=1)


def inter_pod_affinity_score(ct: ClusterTensors, pod: PodFeatures,
                             tds: jnp.ndarray, d_cap: int,
                             hard_weight: jnp.ndarray) -> jnp.ndarray:
    """[N] raw score (scoring.go processExistingPod) in one pass over the
    whole table, the reference of table_statics; normalized max-min at
    aggregation (NormalizeScore :258)."""
    return score_nodes(ct, ipa_score_map(ct, pod, tds, d_cap, hard_weight))


# --------------------------- PodTopologySpread ---------------------------


def _tsc_self_match(pod: PodFeatures) -> jnp.ndarray:
    """[C]: does the pod match its own constraint selector? (selfMatchNum)"""
    kp = pod.plabel_vals.shape[0]
    pv = pod.plabel_vals[jnp.clip(pod.tsc_sel_cols, 0, kp - 1)]    # [C, MS]
    pv = jnp.where(pod.tsc_sel_cols >= 0, pv, NONE)
    return sel_match(pod.tsc_sel_ops, pod.tsc_sel_vals, pv)


def _tsc_matches(ct: ClusterTensors, pod: PodFeatures) -> jnp.ndarray:
    """[PT, C]: table pod s matches constraint c's selector in pod's ns.
    Nominated pods and the pod's own entry are excluded from spread counts
    (shouldn't double-count itself; nominated pods may never run)."""
    ns_ok = ct.pod_ns[:, None] == pod.ns                           # [PT, 1]
    tv = take_cols(ct.pt_label_vals, pod.tsc_sel_cols, NONE)       # [PT, C, MS]
    sel_ok = sel_match(pod.tsc_sel_ops[None], pod.tsc_sel_vals[None], tv)
    tbl = table_mask(ct, pod, include_nominated=False)
    return sel_ok & ns_ok & tbl[:, None] & (pod.tsc_tk[None] != NONE)


def spread_eligible(ct: ClusterTensors, pod: PodFeatures,
                    nodeaff_ok: jnp.ndarray, taint_ok: jnp.ndarray,
                    consider: jnp.ndarray) -> jnp.ndarray:
    """[N, C] node-inclusion eligibility per constraint
    (matchNodeInclusionPolicies, common.go:33-127), plus the
    requireAllTopologies rule: a node missing ANY considered constraint's
    topology label is ignored entirely (filtering.go calPreFilterState).

    ``consider`` [C] selects the constraint set: the Filter path evaluates
    only DoNotSchedule constraints, the Score path only ScheduleAnyway —
    mixing them would let a soft constraint on an unlabeled key disable
    hard filtering."""
    node_dom = take_cols(ct.topo_dom, pod.tsc_tk, NONE)            # [N, C]
    all_topo = jnp.all((node_dom != NONE) | ~consider[None], axis=1)  # [N]
    base = ct.node_valid & all_topo                                # [N]
    ok = jnp.where(pod.tsc_honor_affinity[None], nodeaff_ok[:, None], True)
    ok = ok & jnp.where(pod.tsc_honor_taints[None], taint_ok[:, None], True)
    return base[:, None] & ok & consider[None]                     # [N, C]


def spread_cnt(ct: ClusterTensors, pod: PodFeatures, tds: jnp.ndarray,
               eligible: jnp.ndarray, d_cap: int) -> jnp.ndarray:
    """[C, D] f32: matching pods per (constraint, domain), counting only
    pods on nodes eligible for that constraint (TpPairToMatchNum)."""
    tk_cap = ct.topo_dom.shape[1]
    c_cap = pod.tsc_tk.shape[0]
    m = _tsc_matches(ct, pod)                                      # [PT, C]
    m = m & eligible[jnp.maximum(ct.pod_node, 0)]                  # [PT, C]
    dom = tds[:, jnp.clip(pod.tsc_tk, 0, tk_cap - 1)]              # [PT, C]
    dom = jnp.where(pod.tsc_tk[None] != NONE, dom, NONE)
    ok = m & (dom != NONE)
    flat = jnp.broadcast_to(jnp.arange(c_cap)[None], m.shape) * d_cap \
        + jnp.clip(dom, 0)
    cnt = jnp.zeros((c_cap * d_cap,), jnp.float32)
    cnt = cnt.at[flat.reshape(-1)].add(ok.reshape(-1).astype(jnp.float32))
    return cnt.reshape(c_cap, d_cap)


def spread_exists(ct: ClusterTensors, pod: PodFeatures,
                  node_mask: jnp.ndarray, d_cap: int) -> jnp.ndarray:
    """[C, D] bool: domains present among masked-in nodes per constraint.
    node_mask: [N, C]."""
    c_cap = pod.tsc_tk.shape[0]
    node_dom = take_cols(ct.topo_dom, pod.tsc_tk, NONE)            # [N, C]
    return scatter_or(jnp.broadcast_to(jnp.arange(c_cap)[None],
                                       node_dom.shape),
                      node_dom, node_mask, c_cap, d_cap)




# ------------------------- the pod table, by blocks -------------------------
#
# Phase 1b's passes over the pod table (the scatters above) cost the
# table's capacity, whatever it holds: 131,072 slots where a cell keeps
# 3,010 pods. The mirror hands out the lowest free slot first, so the live
# slots sit under a high-water mark; fold_table runs the passes over
# fixed-width blocks of the table and stops after the last block that holds
# a live slot. Its trip count is read on the device from pod_valid, so one
# program serves a near-empty table and a full one. The reductions are ORs
# of booleans and f32 sums of whole numbers, which give the same bits in
# any order; the gathers to node space run once, after the loop.

# blocks a table is cut into (fewer where the table has fewer slots)
TABLE_BLOCKS = 16


def table_block(pt: int) -> int:
    """Slots in one block of a pod table of ``pt`` slots."""
    return -(-pt // TABLE_BLOCKS)


def table_blocks_for(hi: int, pt: int) -> int:
    """The blocks fold_table runs (BatchResult.table_blocks, as host
    arithmetic): whole blocks up to slot ``hi`` (1 + the highest slot in
    use; 0 for an empty table) of a table of ``pt`` slots."""
    return -(-hi // table_block(pt))


def fold_table(ct: ClusterTensors, table: jnp.ndarray, caps: Capacities,
               body):
    """Run ``body(ctb, tds)`` over the pod table's blocks up to the last
    that holds a live slot, and reduce what it returns (a pytree of maps):
    bool leaves by OR, the rest by sum; a table with no live slot gives
    zeros. ``table`` is the [PT, pi] blob (ClusterBlobs.pods_i32): each
    block is sliced from it before it is unpacked, so no pass and no
    unpack copy covers the whole table. ``ctb`` is ``ct`` with the block's
    pod-table fields, ``tds`` its slot_topo_dom; ``ct.pod_valid`` is the
    liveness read, so a caller that masks slots out (preemption's victims)
    masks them out of the blocks and the trip count alike."""
    pt = table.shape[0]
    w = table_block(pt)
    valid = ct.pod_valid
    pad = (-pt) % w
    if pad:
        # direct callers only (the mirror's tables are powers of two): the
        # last block's slots past the table are dead
        table = jnp.pad(table, [(0, pad), (0, 0)])
        valid = jnp.pad(valid, (0, pad))
    table_codec = codecs(caps)[1]
    empty = jnp.zeros((w, 0), jnp.float32)

    def block(k):
        with jax.named_scope("table_block"):
            f = table_codec.unpack(Blobs(
                f32=empty, i32=jax.lax.dynamic_slice_in_dim(table, k * w, w)))
            f["pod_valid"] = jax.lax.dynamic_slice_in_dim(valid, k * w, w)
            ctb = dataclasses.replace(ct, **f)
            return body(ctb, slot_topo_dom(ctb))

    def step(k, acc):
        return jax.tree.map(
            lambda a, b: a | b if a.dtype == jnp.bool_ else a + b,
            acc, block(k))

    zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        jax.eval_shape(block, 0))
    return jax.lax.fori_loop(0, _live_blocks(valid, w), step, zero)


def _live_blocks(valid: jnp.ndarray, w: int) -> jnp.ndarray:
    """[] i32: blocks of ``w`` slots up to the last True of ``valid``."""
    hi = jnp.max(jnp.where(valid, jnp.arange(1, valid.shape[0] + 1,
                                             dtype=jnp.int32), 0))
    return (hi + (w - 1)) // w


def table_blocks(ct: ClusterTensors) -> jnp.ndarray:
    """[] i32: the blocks fold_table runs over this table (the device's
    own count of table_blocks_for)."""
    return _live_blocks(ct.pod_valid, table_block(ct.pod_valid.shape[0]))


@dataclasses.dataclass
class TableStatics:
    """What phase 1b reads from the pod table for one pod (group), in node
    space where the commit scan wants nodes; None where not asked for."""

    anti_ok: jnp.ndarray | None = None    # [N] rules 1+2 vs the table
    present: jnp.ndarray | None = None    # [A, D] affinity presence
    any_match: jnp.ndarray | None = None  # [] some table pod matches a term
    ipa_raw: jnp.ndarray | None = None    # [N] raw InterPodAffinity score
    cnt: jnp.ndarray | None = None        # [C, D] spread match counts


def table_statics(ct: ClusterTensors, table: jnp.ndarray, caps: Capacities,
                  pod: PodFeatures, d_cap: int, *, forbid: bool = False,
                  presence: bool = False, hard_weight=None,
                  spread_el=None) -> TableStatics:
    """Phase 1b's table passes for one pod, folded over the table's live
    blocks (fold_table): the forbid map of rules 1+2 (``forbid``), the
    presence map of rule 3 (``presence``), the InterPodAffinity score map
    (given ``hard_weight``) and the spread counts over the nodes
    ``spread_el`` [N, C] admits (spread_eligible); each gathered to node
    space once, after the loop. The same answers as
    inter_pod_affinity_static, inter_pod_affinity_score and spread_cnt
    over the whole table, bit for bit."""

    def body(ctb, tds):
        out = {}
        if forbid:
            out["forbid"] = ipa_forbid_map(ctb, pod, tds, d_cap)
        if presence:
            out["present"], out["any_match"] = ipa_presence_map(
                ctb, pod, tds, d_cap)
        if hard_weight is not None:
            out["score"] = ipa_score_map(ctb, pod, tds, d_cap, hard_weight)
        if spread_el is not None:
            out["cnt"] = spread_cnt(ctb, pod, tds, spread_el, d_cap)
        return out

    maps = fold_table(ct, table, caps, body)
    return TableStatics(
        anti_ok=forbid_ok(ct, maps["forbid"]) if forbid else None,
        present=maps.get("present"), any_match=maps.get("any_match"),
        ipa_raw=(score_nodes(ct, maps["score"])
                 if hard_weight is not None else None),
        cnt=maps.get("cnt"))
