"""Score extension point as device kernels, with reference-parity normalization.

One pod against all nodes, [N] float32 raw scores; normalization helpers
mirror each plugin's NormalizeScore. The 3-stage reference pipeline
(parallel Score -> Normalize -> weighted sum, runtime/framework.go:1117-1194)
collapses into fused tensor ops here.

Reference algorithms:
- least/most allocated:   noderesources/least_allocated.go:30, most_allocated.go:30
- balanced allocation:    noderesources/balanced_allocation.go (std of fractions)
- node affinity score:    nodeaffinity (sum of matched preferred weights)
- taint toleration score: tainttoleration:146 (intolerable PreferNoSchedule count)
- image locality:         imagelocality (scaled sum of present image sizes)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import common as C
from kubernetes_tpu.ops.features import (
    COL_CPU,
    COL_MEM,
    EFFECT_PREFER_NO_SCHEDULE,
    ClusterTensors,
    PodFeatures,
)
from kubernetes_tpu.ops.filters import _selector_match
from kubernetes_tpu.utils.interner import NONE

MAX_NODE_SCORE = 100.0


def utilization_fractions(alloc2: jnp.ndarray, nonzero_requested: jnp.ndarray,
                          pod_nonzero_req: jnp.ndarray) -> jnp.ndarray:
    """(NonZeroRequested + pod nonzero request) / allocatable for cpu, memory.
    [N, 2], clamped to [0, 1]; allocatable 0 -> fraction 1.

    Parameterized on the live ``nonzero_requested`` so the batched commit
    scan can feed its carry instead of the static snapshot column."""
    req = nonzero_requested + pod_nonzero_req[None]
    frac = jnp.where(alloc2 > 0, req / jnp.maximum(alloc2, 1e-9), 1.0)
    return jnp.clip(frac, 0.0, 1.0)


def least_allocated_from_fractions(frac: jnp.ndarray) -> jnp.ndarray:
    """mean over {cpu, mem} of (1 - utilization) * 100 (least_allocated.go:30,
    default weights 1/1)."""
    return jnp.mean(1.0 - frac, axis=-1) * MAX_NODE_SCORE


def most_allocated_from_fractions(frac: jnp.ndarray) -> jnp.ndarray:
    """mean utilization * 100 (most_allocated.go:30): bin-packing bias."""
    return jnp.mean(frac, axis=-1) * MAX_NODE_SCORE


def requested_to_capacity_ratio_from_fractions(
        frac: jnp.ndarray, shape_x: jnp.ndarray,
        shape_y: jnp.ndarray) -> jnp.ndarray:
    """Piecewise-linear utilization -> score per resource, averaged
    (requested_to_capacity_ratio.go:60 buildRequestedToCapacityRatioScorer):
    shape_x = utilization fractions 0..1 ascending, shape_y = scores
    0..100."""
    per_res = jnp.interp(frac, shape_x, shape_y)
    return jnp.mean(per_res, axis=-1)


def fit_score_from_fractions(frac: jnp.ndarray, strategy: str,
                             shape) -> jnp.ndarray:
    """NodeResourcesFit score under the configured ScoringStrategy
    (apis/config types.go ScoringStrategyType). ``strategy`` is STATIC —
    the launch compiles exactly one scorer."""
    if strategy == "MostAllocated":
        return most_allocated_from_fractions(frac)
    if strategy == "RequestedToCapacityRatio":
        return requested_to_capacity_ratio_from_fractions(
            frac, shape[0], shape[1])
    return least_allocated_from_fractions(frac)


def balanced_allocation_from_fractions(frac: jnp.ndarray) -> jnp.ndarray:
    """(1 - std(fractions)) * 100 (balanced_allocation.go)."""
    mean = jnp.mean(frac, axis=-1, keepdims=True)
    std = jnp.sqrt(jnp.mean((frac - mean) ** 2, axis=-1))
    return (1.0 - std) * MAX_NODE_SCORE


def alloc_cpu_mem(ct: ClusterTensors) -> jnp.ndarray:
    return jnp.stack([ct.allocatable[:, COL_CPU], ct.allocatable[:, COL_MEM]],
                     axis=-1)


def _requested_fractions(ct: ClusterTensors, pod: PodFeatures) -> jnp.ndarray:
    return utilization_fractions(alloc_cpu_mem(ct), ct.nonzero_requested,
                                 pod.nonzero_req)


def least_allocated(ct: ClusterTensors, pod: PodFeatures) -> jnp.ndarray:
    return least_allocated_from_fractions(_requested_fractions(ct, pod))


def most_allocated(ct: ClusterTensors, pod: PodFeatures) -> jnp.ndarray:
    return most_allocated_from_fractions(_requested_fractions(ct, pod))


def balanced_allocation(ct: ClusterTensors, pod: PodFeatures) -> jnp.ndarray:
    return balanced_allocation_from_fractions(_requested_fractions(ct, pod))


@jax.named_scope("node_affinity")
def node_affinity_score(ct: ClusterTensors, pod: PodFeatures) -> jnp.ndarray:
    """Sum of weights of matching PreferredSchedulingTerms (raw; normalized by
    max across nodes at aggregation)."""
    match = _selector_match(ct, pod.pref_col, pod.pref_op, pod.pref_is_field,
                            pod.pref_vals, pod.pref_num)  # [N, PW, E]
    used = pod.pref_op != NONE
    term_ok = jnp.all(match | ~used[None], axis=-1)       # [N, PW]
    term_nonempty = jnp.any(used, axis=-1)                # [PW]
    active = term_nonempty[None] & (pod.pref_weight[None] != 0)
    return jnp.sum(jnp.where(term_ok & active,
                             pod.pref_weight[None].astype(jnp.float32), 0.0),
                   axis=-1)


def taint_toleration_score(ct: ClusterTensors, pod: PodFeatures) -> jnp.ndarray:
    """Raw = count of intolerable PreferNoSchedule taints (lower is better;
    inverted by normalize_inverse)."""
    tolerated = C.tolerations_tolerate(
        pod.tol_valid, pod.tol_key, pod.tol_op, pod.tol_val, pod.tol_effect,
        ct.taint_keys, ct.taint_vals, ct.taint_effects)
    soft = (ct.taint_effects == EFFECT_PREFER_NO_SCHEDULE) & (ct.taint_keys != NONE)
    return jnp.sum(soft & ~tolerated, axis=-1).astype(jnp.float32)


def image_locality(ct: ClusterTensors, pod: PodFeatures,
                   num_nodes: jnp.ndarray) -> jnp.ndarray:
    """Scaled sum of sizes of requested images already present
    (imagelocality.go): each image's size is scaled by the fraction of nodes
    having it (spread), then mapped through [23Mi, 1000Mi] -> [0, 100]."""
    # presence [N, IM]: pod image im present in node's image list
    pim = pod.image_ids[None, :, None]            # [1, IM, 1]
    nim = ct.image_ids[:, None, :]                # [N, 1, I]
    present = jnp.any((nim == pim) & (pim != NONE), axis=-1)  # [N, IM]
    sizes = jnp.max(jnp.where(nim == pim, ct.image_sizes[:, None, :], 0.0),
                    axis=-1)                       # [N, IM] MiB
    # spread: fraction of (valid) nodes having each image
    have = jnp.sum(present & ct.node_valid[:, None], axis=0).astype(jnp.float32)
    spread = have / jnp.maximum(num_nodes.astype(jnp.float32), 1.0)  # [IM]
    summed = jnp.sum(present * sizes * spread[None], axis=-1)  # [N] MiB
    # thresholds (MiB): min 23Mi; max 1000Mi scaled by total container count
    # (image_locality.go calculatePriority maxThreshold * numContainers)
    min_t = 23.0
    max_t = 1000.0 * jnp.maximum(pod.num_containers, 1.0)
    return jnp.clip((summed - min_t) / (max_t - min_t), 0.0, 1.0) * MAX_NODE_SCORE


# ---------------- normalization (per-plugin NormalizeScore) ----------------


def normalize_max(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """DefaultNormalizeScore: score * 100 / max (helper.DefaultNormalizeScore)."""
    top = C.masked_max(scores, mask)
    top = jnp.where(jnp.isfinite(top) & (top > 0), top, 1.0)
    return scores * (MAX_NODE_SCORE / top)


def normalize_inverse(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Reverse normalize (taint toleration): 100 * (1 - score/max)."""
    top = C.masked_max(scores, mask)
    top = jnp.where(jnp.isfinite(top) & (top > 0), top, 1.0)
    return (1.0 - scores / top) * MAX_NODE_SCORE


def normalize_maxmin(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """InterPodAffinity NormalizeScore (scoring.go:258):
    100 * (score - min) / (max - min); all-equal -> 0."""
    mn = C.masked_min(scores, mask)
    mx = C.masked_max(scores, mask)
    diff = mx - mn
    ok = jnp.isfinite(diff) & (diff > 0)
    return jnp.where(ok, MAX_NODE_SCORE * (scores - mn)
                     / jnp.where(ok, diff, 1.0), 0.0)


def normalize_spread(scores: jnp.ndarray, mask: jnp.ndarray,
                     ignored: jnp.ndarray) -> jnp.ndarray:
    """PodTopologySpread NormalizeScore (scoring.go:226): lower raw count is
    better: 100 * (max + min - s) / max; max == 0 -> 100; ignored -> 0."""
    live = mask & ~ignored
    mn = C.masked_min(scores, live)
    mx = C.masked_max(scores, live)
    ok = jnp.isfinite(mx) & (mx > 0)
    out = jnp.where(ok, MAX_NODE_SCORE * (mx + mn - scores)
                    / jnp.where(ok, mx, 1.0), MAX_NODE_SCORE)
    return jnp.where(ignored, 0.0, out)
