"""Preemption dry-run as a device sweep over victim-prefix removals.

The reference dry-runs preemption per candidate node: remove all
lower-priority pods, re-run filters, then reprieve victims highest-priority
first (preemption/preemption.go:682 DryRunPreemption,
defaultpreemption/default_preemption.go:219 SelectVictimsOnNode). The
TPU-native formulation evaluates EVERY node's every victim-prefix in one
launch: the host supplies, per node, the priority-ascending victims'
cumulative freed-resource sums ``vic_cumsum [N, K+1, R]`` (k=0 means no
eviction), and the kernel returns the minimal k per node that makes the pod
fit alongside the commit-invariant static filters. Because victims are
removed in ascending-importance order, the minimal resource-feasible prefix
is exactly the reprieve loop's fixed point for resource-driven preemption.

Topology effects of victim removal (an anti-affinity term owned by a victim)
are not modeled in the sweep: the preemptor is re-scheduled through the full
pipeline after its victims exit, so an over-optimistic candidate costs one
extra cycle, never a wrong placement.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from kubernetes_tpu.models.pipeline import (
    ALL_FEATURES,
    FILTER_PLUGINS,
    NUM_FILTER_PLUGINS,
    static_filters,
)
from kubernetes_tpu.ops.features import (
    Capacities,
    ClusterBlobs,
    PodBlobs,
    unpack_cluster,
    unpack_pods,
)
from kubernetes_tpu.utils.interner import NONE


def preempt_sweep(cblobs: ClusterBlobs, pblobs: PodBlobs,
                  wk: dict[str, jnp.ndarray], vic_cumsum: jnp.ndarray,
                  vic_cols: jnp.ndarray, caps: Capacities,
                  enabled_filters: tuple[bool, ...] | None = None,
                  free: jnp.ndarray | None = None) -> jnp.ndarray:
    """[P, N] i32: minimal victim count k (1..K) making each pod fit on
    each node; NONE where preemption cannot help (static filter fails,
    request exceeds allocatable, or even evicting every victim is not
    enough). A whole burst of preemptors sweeps in ONE launch.

    pblobs carries P pods. The freed-resource cumsum is COLUMN-SUBSET:
    ``vic_cols [C] i32`` names the resource columns any victim actually
    frees, ``vic_cumsum [N, K+1, C]`` is their cumulative freed request
    over the first k victims (k=0 row zero). Columns nobody frees are
    k-independent, so the plain fit-vs-base check covers them; this cuts
    the host->device cumsum transfer ~R/C-fold (74 -> ~4 columns on the
    PreemptionAsync shape — ~20MB to ~1MB per burst). Padding entries
    of vic_cols may alias column 0: their cumsum rows are +BIG so they
    never constrain.

    ``free`` overrides the snapshot free matrix (ct.free) as the fit
    baseline: the pipelined scheduler passes its live device-resident
    chain here so a preemptor's sweep sees waves still in flight —
    without it, the sweep would nominate slots an uncommitted wave has
    already claimed and the verification launch would bounce the plan a
    cycle later."""
    if enabled_filters is None:
        enabled_filters = (True,) * NUM_FILTER_PLUGINS
    ct = unpack_cluster(cblobs, caps)
    pods = unpack_pods(pblobs, caps)       # [P, ...] — BATCHED preemptors
    # columns handled by the k-dependent check (padding double-sets col 0;
    # the real col-0 entry still constrains through the subset check)
    col_freed = jnp.zeros((ct.free.shape[1],), bool).at[vic_cols].set(True)

    def per_pod(pod):
        # the sweep runs off the hot path: evaluate every static filter
        # (no workload-activity DCE)
        masks = static_filters(ct, pod, wk, enabled_filters,
                               frozenset(ALL_FEATURES))        # [5, N]
        static_ok = jnp.all(masks, axis=0) & ct.node_valid & pod.valid
        unresolvable = jnp.any(pod.req[None] > ct.allocatable, axis=-1)
        # fit after evicting the first k victims, against the same
        # effective free as the pipeline's fit check (nominated
        # reservations subtracted, own nomination handed back): [N, K+1]
        own = (jnp.arange(ct.free.shape[0]) == pod.nominated_row)
        base_free = ct.free if free is None else free
        base = (base_free - ct.nominated_req
                + jnp.where(own[:, None], pod.req[None], 0.0))
        fit0 = pod.req[None] <= base                           # [N, R]
        ok_rest = jnp.all(fit0 | col_freed[None], axis=-1)     # [N]
        base_c = base[:, vic_cols]                             # [N, C]
        req_c = pod.req[vic_cols]                              # [C]
        eff = base_c[:, None, :] + vic_cumsum                  # [N, K+1, C]
        fit = ok_rest[:, None] & jnp.all(req_c[None, None] <= eff, axis=-1)
        # minimal k with a fit (k=0 would mean it already fits — the
        # caller only sweeps rejected pods, but guard anyway)
        kmin = jnp.argmax(fit, axis=1).astype(jnp.int32)       # first True
        any_fit = jnp.any(fit, axis=1)
        ok = static_ok & ~unresolvable & any_fit
        return jnp.where(ok, kmin, jnp.int32(NONE))

    return jax.vmap(per_pod)(pods)         # [P, N]


@partial(jax.jit, static_argnames=("caps", "enabled_filters"))
def preempt_sweep_jit(cblobs, pblobs, wk, vic_cumsum, vic_cols, caps,
                      enabled_filters=None, free=None):
    return preempt_sweep(cblobs, pblobs, wk, vic_cumsum, vic_cols, caps,
                         enabled_filters, free)


def preempt_feasible(cblobs: ClusterBlobs, pblobs: PodBlobs,
                     wk: dict[str, jnp.ndarray], caps: Capacities,
                     table_valid: jnp.ndarray, free: jnp.ndarray,
                     enable_topology: bool = True, d_cap: int | None = None,
                     enabled_filters: tuple[bool, ...] | None = None
                     ) -> jnp.ndarray:
    """[N] bool: does ONE pod pass the FULL filter set on each node, with
    ``table_valid`` masking out victim pods and ``free`` overriding the
    per-node free resources?

    This is the exact dry-run the reference runs per candidate node
    (defaultpreemption SelectVictimsOnNode :219: remove victims, re-run
    RunFilterPluginsWithNominatedPods) — evaluated for EVERY node in one
    launch. The host encodes an eviction set as (table mask, freed
    resources); topology filters (anti-affinity, required affinity, hard
    spread) see the post-eviction world because every count/presence map is
    built from the masked table.
    """
    import dataclasses as _dc

    from kubernetes_tpu.ops import topology as T

    if enabled_filters is None:
        enabled_filters = (True,) * NUM_FILTER_PLUGINS
    if d_cap is None:
        d_cap = caps.domain_cap
    ct = unpack_cluster(cblobs, caps)
    ct = _dc.replace(ct, pod_valid=ct.pod_valid & table_valid)
    pod = jax.tree_util.tree_map(lambda x: x[0], unpack_pods(pblobs, caps))
    valid = ct.node_valid
    masks = static_filters(ct, pod, wk, enabled_filters,
                           frozenset(ALL_FEATURES))
    ok = jnp.all(masks, axis=0) & valid & pod.valid
    # resource fit against the evicted free state
    if enabled_filters[FILTER_PLUGINS.index("NodeResourcesFit")]:
        own = jnp.arange(free.shape[0]) == pod.nominated_row
        eff = free - ct.nominated_req + jnp.where(own[:, None],
                                                  pod.req[None], 0.0)
        ok = ok & jnp.all(pod.req[None] <= eff, axis=-1)
    if not enable_topology:
        return ok
    taint_ok, nodeaff_ok = masks[2], masks[3]
    spread_on = enabled_filters[FILTER_PLUGINS.index("PodTopologySpread")]
    ipa_on = enabled_filters[FILTER_PLUGINS.index("InterPodAffinity")]
    if not (spread_on or ipa_on):
        return ok
    if spread_on:
        used_c = pod.tsc_tk != jnp.int32(-1)
        used_hard = used_c & pod.tsc_hard
        el_hard = T.spread_eligible(ct, pod, nodeaff_ok, taint_ok, used_hard)
    # the victims are out of ct.pod_valid, so out of the fold's blocks
    ts = T.table_statics(ct, cblobs.pods_i32, caps, pod, d_cap,
                         forbid=ipa_on, presence=ipa_on,
                         spread_el=el_hard if spread_on else None)
    if spread_on:
        cnt = ts.cnt                                            # [C, D]
        exists_hard = T.spread_exists(ct, pod, el_hard, d_cap)
        min_cnt = jnp.min(jnp.where(exists_hard, cnt, jnp.inf), axis=1)
        min_cnt = jnp.where(jnp.isfinite(min_cnt), min_cnt, 0.0)
        num_domains = jnp.sum(exists_hard, axis=1)
        min_cnt = jnp.where((pod.tsc_min_domains > 0)
                            & (num_domains < pod.tsc_min_domains),
                            0.0, min_cnt)
        node_dom = T.take_cols(ct.topo_dom, pod.tsc_tk, jnp.int32(-1))
        self_m = T._tsc_self_match(pod).astype(jnp.float32)
        match_num = T.gather_rows(cnt, node_dom)                # [N, C]
        skew = match_num + self_m[None] - min_cnt[None]
        ok_c = (node_dom != jnp.int32(-1)) \
            & (skew <= pod.tsc_max_skew[None])
        ok = ok & jnp.all(ok_c | ~used_hard[None], axis=1)
    if ipa_on:
        term_used = pod.aff_tk != NONE
        node_dom3 = T.take_cols(ct.topo_dom, pod.aff_tk, NONE)
        has_lbl = node_dom3 != NONE
        term_ok = has_lbl & T.gather_rows(ts.present, node_dom3)
        pods_exist = jnp.all(term_ok | ~term_used[None], axis=1)
        all_lbl = jnp.all(has_lbl | ~term_used[None], axis=1)
        self_ok = pod.aff_self_match & ~ts.any_match & all_lbl
        aff_ok = jnp.where(jnp.any(term_used), pods_exist | self_ok, True)
        ok = ok & ts.anti_ok & aff_ok
    return ok


@partial(jax.jit, static_argnames=("caps", "enable_topology", "d_cap",
                                   "enabled_filters"))
def preempt_feasible_jit(cblobs, pblobs, wk, caps, table_valid, free,
                         enable_topology=True, d_cap=None,
                         enabled_filters=None):
    return preempt_feasible(cblobs, pblobs, wk, caps, table_valid, free,
                            enable_topology, d_cap, enabled_filters)
