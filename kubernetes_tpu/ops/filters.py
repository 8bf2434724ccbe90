"""Filter extension point as vmapped device predicates.

Each function evaluates one plugin's Filter for ONE pod against ALL nodes at
once — the tensorized replacement of the reference's per-node goroutine loop
``findNodesThatPassFilters`` (schedule_one.go:583-650). Returns [N] boolean
accept masks plus, where relevant, an "unresolvable" mask (the
UnschedulableAndUnresolvable distinction preemption relies on,
framework/types.go NodeToStatus).

Reference algorithms:
- NodeName:           plugins/nodename/node_name.go (spec.nodeName == node)
- NodeUnschedulable:  plugins/nodeunschedulable (spec.unschedulable unless tolerated)
- TaintToleration:    plugins/tainttoleration/taint_toleration.go:111
- NodeAffinity:       plugins/nodeaffinity/node_affinity.go:206-228
- NodePorts:          plugins/nodeports (HostPortInfo conflict, types.go:1291)
- NodeResourcesFit:   plugins/noderesources/fit.go:509-592 fitsRequest
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import common as C
from kubernetes_tpu.ops.features import (
    EFFECT_NO_EXECUTE,
    EFFECT_NO_SCHEDULE,
    OP_DOES_NOT_EXIST,
    OP_EXISTS,
    OP_GT,
    OP_IN,
    OP_LT,
    OP_NOT_IN,
    ClusterTensors,
    PodFeatures,
)
from kubernetes_tpu.utils.interner import NONE


def node_name(ct: ClusterTensors, pod: PodFeatures) -> jnp.ndarray:
    """spec.nodeName pin; unset matches every node."""
    return (pod.node_name_id == NONE) | (ct.node_name_id == pod.node_name_id)


def node_unschedulable(ct: ClusterTensors, pod: PodFeatures,
                       unschedulable_taint_key: jnp.ndarray) -> jnp.ndarray:
    """node.spec.unschedulable rejected unless the pod tolerates the
    node.kubernetes.io/unschedulable:NoSchedule taint."""
    n = ct.unschedulable.shape[0]
    key = jnp.broadcast_to(unschedulable_taint_key, (n, 1))
    val = jnp.broadcast_to(jnp.int32(0), (n, 1))  # empty-string value id 0
    eff = jnp.broadcast_to(jnp.int32(EFFECT_NO_SCHEDULE), (n, 1))
    tolerated = C.tolerations_tolerate(
        pod.tol_valid, pod.tol_key, pod.tol_op, pod.tol_val, pod.tol_effect,
        key, val, eff)[:, 0]
    return ~ct.unschedulable | tolerated


def taint_toleration(ct: ClusterTensors, pod: PodFeatures) -> jnp.ndarray:
    """Any untolerated NoSchedule/NoExecute taint rejects the node
    (UnschedulableAndUnresolvable in the reference)."""
    tolerated = C.tolerations_tolerate(
        pod.tol_valid, pod.tol_key, pod.tol_op, pod.tol_val, pod.tol_effect,
        ct.taint_keys, ct.taint_vals, ct.taint_effects)  # [N, T]
    hard = ((ct.taint_effects == EFFECT_NO_SCHEDULE)
            | (ct.taint_effects == EFFECT_NO_EXECUTE))
    untolerated = hard & ~tolerated & (ct.taint_keys != NONE)
    return ~jnp.any(untolerated, axis=-1)


def _take_cols(table: jnp.ndarray, cols: jnp.ndarray,
               fill) -> jnp.ndarray:
    """table: [N, K]; cols: [...] i32 column indices (NONE = key unseen
    cluster-wide). Returns [N, *cols.shape] with `fill` where col is NONE.

    Node labels are columnized (one dense value column per distinct label
    key), so selector evaluation is a cheap gather over K ~ 32 columns
    instead of a [N, ..., L] pair scan — the hot-path win that makes
    affinity kernels bandwidth-bound on [N, T, E] rather than [N, T, E, L].
    """
    k = table.shape[1]
    safe = jnp.clip(cols, 0, k - 1)
    out = jnp.take(table, safe.reshape(-1), axis=1)
    out = out.reshape((table.shape[0],) + cols.shape)
    return jnp.where(cols[None] >= 0, out, fill)


def _selector_match(ct: ClusterTensors, cols, ops, is_field, vals, nums):
    """match[N, *cols.shape] for node-selector expressions.

    cols/ops/is_field/nums: [T, E]; vals: [T, E, V].
    """
    val = _take_cols(ct.label_col_vals, cols, NONE)       # [N, T, E]
    present = val != NONE

    # matchFields: the only supported key is metadata.name -> node name id
    name_val = ct.node_name_id.reshape((-1,) + (1,) * cols.ndim)  # [N, 1, 1]
    name_val = jnp.broadcast_to(name_val, val.shape)              # [N, T, E]
    val = jnp.where(is_field[None], name_val, val)
    present = jnp.where(is_field[None], True, present)

    in_vals = C.isin(val, vals[None])                    # [N, T, E]
    # Gt/Lt: numeric label value from the packed per-column table. matchFields
    # (metadata.name) Gt/Lt is not supported (invalid per reference
    # validation: matchFields only allows metadata.name with In/NotIn).
    num_val = _take_cols(ct.label_col_nums, cols, jnp.nan)
    num_ok = (~jnp.isnan(num_val) & ~jnp.isnan(nums[None]) & ~is_field[None])
    gt = num_ok & (num_val > nums[None])
    lt = num_ok & (num_val < nums[None])

    op = ops[None]
    match = jnp.where(op == OP_IN, present & in_vals,
            jnp.where(op == OP_NOT_IN, ~(present & in_vals),
            jnp.where(op == OP_EXISTS, present,
            jnp.where(op == OP_DOES_NOT_EXIST, ~present,
            jnp.where(op == OP_GT, present & gt,
            jnp.where(op == OP_LT, present & lt, False))))))
    return match  # [N, *cols.shape]


def node_affinity(ct: ClusterTensors, pod: PodFeatures,
                  full: bool = True) -> jnp.ndarray:
    """spec.nodeSelector (exact pairs, ANDed) AND required node affinity
    (OR over terms, AND within term).

    ``full=False`` (the "nodeaffinity_pin" launch feature) compiles ONLY
    the single-node pin compare: every affinity-bearing pod in the batch
    reduced to a matchFields metadata.name In [v] term (the daemonset
    shape), so the [N, T, E, V] selector kernels never materialize."""
    pin_ok = (pod.aff_pin == NONE) | (ct.node_name_id == pod.aff_pin)  # [N]
    if not full:
        return pin_ok
    with jax.named_scope("node_affinity"):
        # nodeSelector pairs: node's value in the pair's label column must
        # equal the pair's value (col NONE -> key on no node -> never
        # matches)
        node_val = _take_cols(ct.label_col_vals, pod.nodesel_cols,
                              NONE)                              # [N, PL]
        used_pair = pod.nodesel_vals != NONE
        hit = node_val == pod.nodesel_vals[None]
        sel_ok = jnp.all(hit | ~used_pair[None], axis=-1)     # [N]

        match = _selector_match(ct, pod.sel_col, pod.sel_op,
                                pod.sel_is_field, pod.sel_vals,
                                pod.sel_num)  # [N, T, E]
        used = pod.sel_op != NONE  # [T, E]
        term_ok = jnp.all(match | ~used[None], axis=-1)  # [N, T]
        term_nonempty = jnp.any(used, axis=-1)  # [T]
        term_ok = term_ok & term_nonempty[None] & pod.sel_term_valid[None]
        any_term = jnp.any(pod.sel_term_valid)
        affinity_ok = jnp.where(any_term, jnp.any(term_ok, axis=-1), True)
        return sel_ok & affinity_ok & pin_ok


def node_ports(ct: ClusterTensors, pod: PodFeatures,
               wildcard_ip: jnp.ndarray) -> jnp.ndarray:
    """No requested host port may conflict with an occupied one
    (types.go:1291 CheckConflict: wildcard IP clashes with any IP)."""
    # pod ports [HP] vs node ports [N, P]
    pp = pod.hp_port[None, None, :]       # [1, 1, HP]
    pproto = pod.hp_proto[None, None, :]
    pip = pod.hp_ip[None, None, :]
    np_ = ct.port_nums[..., None]          # [N, P, 1]
    nproto = ct.port_protos[..., None]
    nip = ct.port_ips[..., None]
    same = (pp != NONE) & (np_ == pp) & (nproto == pproto)
    ip_clash = (nip == pip) | (nip == wildcard_ip) | (pip == wildcard_ip)
    conflict = same & ip_clash
    return ~jnp.any(conflict, axis=(1, 2))


def pod_pair_port_conflict(pods: PodFeatures,
                           wildcard_ip: jnp.ndarray) -> jnp.ndarray:
    """[B, B] bool: would pods i and j conflict on host ports if co-located?
    Wildcard-IP semantics as types.go:1291 CheckConflict.

    Used by the batched commit scan to preserve as-if-serial NodePorts
    semantics inside one launch: pod j may not land on a node where an
    earlier batch pod i with a conflicting hostPort was just committed."""
    pp = pods.hp_port
    a_port = pp[:, None, :, None]
    b_port = pp[None, :, None, :]
    a_proto = pods.hp_proto[:, None, :, None]
    b_proto = pods.hp_proto[None, :, None, :]
    a_ip = pods.hp_ip[:, None, :, None]
    b_ip = pods.hp_ip[None, :, None, :]
    same = (a_port != NONE) & (a_port == b_port) & (a_proto == b_proto)
    ip_clash = (a_ip == b_ip) | (a_ip == wildcard_ip) | (b_ip == wildcard_ip)
    return jnp.any(same & ip_clash, axis=(2, 3))


def resources_fit(ct: ClusterTensors, pod: PodFeatures
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """request <= free per resource column (fit.go:509-592).

    Returns (ok [N], unresolvable [N]) — unresolvable when the request
    exceeds the node's *allocatable* (no amount of preemption helps).
    """
    req = pod.req[None]                      # [1, R]
    ok = jnp.all(req <= ct.free, axis=-1)
    unresolvable = jnp.any(req > ct.allocatable, axis=-1)
    return ok, unresolvable
