"""Device kernels for gang admission: the fused gang-batch packer that
places ALL members of a PodGroup in one launch, plus the async capacity
bound the host-fallback PreFilter still consults.

``pack_gangs`` is the tentpole kernel (ISSUE 12): the batch's gang units
are packed as one ``[G, N]`` problem over the cluster mirror — one
representative pod row per gang (members of a device-packable gang are
request-identical by construction; heterogeneous gangs stay on the host
Permit path) and a ``need`` count of members to place. Per gang:

1. **Member capacity per node** — the static Filter masks (the same five
   commit-invariant plugins the main pipeline runs, via
   ``pipeline.static_filters``) AND a floored free/request division
   (``floor_div`` — exact where the TPU's f32 division is not) give
   ``cap_n`` = how many members node n can still hold, with nominated
   reservations subtracted exactly like the batched fit predicate.
2. **All-or-nothing feasibility reduction** — ``sum(cap_n) >= need`` is
   the gang's device verdict: every member places or none do. This
   replaces the per-member Permit round-trips with ONE verdict + one
   host commit, and it subsumes the old ``gang_capacity`` upper bound
   (``cap`` in the result is that bound, tightened by the static
   filters, fed back into the PreFilter memo so the fallback path never
   re-derives it).
3. **Topology-close packing** — nodes are filled in domain-major order
   under the packing topology key (zone; ``ct.topo_dom`` is the same
   compact domain table the spread/affinity kernels use): domains are
   ranked by member capacity DESCENDING (the packing score — the
   domain that can co-locate the most members wins), and within a
   domain the densest nodes fill first. ``spans`` reports how many
   domains the placement touched — the co-location number the
   GangTopologyPacking bench asserts on. Kant's whole-job
   topology-aware placement (PAPERS.md), expressed as a sort key
   instead of a per-member score term.

Gangs commit SEQUENTIALLY inside the launch (a lax.scan over gang rows):
gang g+1 sees g's placements in the carried free/nzr state, so one
launch admits a whole wave of gangs as-if-serial. The post-batch
``free``/``nzr`` chain to the next launch exactly like
``BatchResult.free``/``.nzr``.

``gang_capacity_device`` keeps the old optimistic bound for gangs the
packer cannot express (topology terms, heterogeneous members, claims) —
but ASYNC: it returns the device scalar, and the scheduler folds the
pull into its existing one-per-cycle ``device_get`` instead of the old
per-(sync, group) blocking pull.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

# big sentinel member-count for request columns/pods that bind nothing
_UNBOUNDED = 2.0 ** 20

# the composite node sort key packs (domain rank, density) into one i32:
# rank * _KEY_STRIDE + (_KEY_STRIDE - 1 - clipped capacity)
_KEY_STRIDE = 4096


def floor_div(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """How many whole ``b`` fit in ``a`` (a >= 0, b > 0, f32): the largest
    q with q * b <= a. NOT ``floor(a / b)``: f32 division on the TPU is
    not correctly rounded, and a quotient that is an exact integer in
    real arithmetic lands one ulp low there — floor(33 / 33) = 0, wrong
    in 15% of the exact pairs up to 4096 x 128 on a v5e — which strands
    a gang that fits. One multiply-and-compare step each way around the
    approximate quotient is exact, and it is the same q * b the commit
    subtracts from free."""
    q = jnp.floor(a / b)
    q = jnp.where((q + 1.0) * b <= a, q + 1.0, q)
    return jnp.where(q * b > a, q - 1.0, q)


@jax.tree_util.register_dataclass
@dataclass
class GangPackResult:
    """Per-gang outcome of one fused packing launch."""

    ok: jax.Array        # [G] bool: all-or-nothing verdict
    alloc: jax.Array     # [G, N] i32: members placed per node (0s when !ok)
    cap: jax.Array       # [G] i32: member-capacity bound over feasible nodes
    spans: jax.Array     # [G] i32: topology domains the placement touches
    free: jax.Array      # [N, R] f32: post-batch free resources (chains)
    nzr: jax.Array       # [N, 2] f32: post-batch nonzero-requested
    guard: jax.Array     # [] i32: NaN poison detector (bit 1, like pipeline)


def pack_gangs(cblobs, gblobs, wk, caps, need, tk,
               d_cap: int = 8,
               enabled_filters: tuple[bool, ...] | None = None,
               active: tuple[str, ...] | None = None,
               pfields: tuple[str, ...] | None = None,
               ptmpl=None,
               state: tuple[jnp.ndarray, jnp.ndarray] | None = None,
               own_nom: jnp.ndarray | None = None,
               ) -> GangPackResult:
    """Place every gang of the batch in one launch (module docstring).

    ``gblobs`` carries ONE representative pod row per gang ([G, ...]);
    ``need`` [G] i32 is how many members to place (0 = padding row, a
    no-op). ``tk`` (dynamic i32 scalar) is the packing topology key's
    column in ``ct.topo_dom``; -1 packs capacity-greedy with every node
    in one shared domain. ``d_cap`` (STATIC) bounds the domain space;
    the last slot is the pseudo-domain of unlabeled nodes. ``state``
    overrides free/nonzero_requested with a previous launch's chain.
    ``own_nom`` [G, N] i32 counts the gang's OWN members nominated per
    node (post-preemption retries): their reserved requests are handed
    back before the capacity division, the gang analog of the fit
    predicate's own-nomination hand-back (framework.go:989)."""
    from kubernetes_tpu.models.pipeline import (
        FILTER_PLUGINS,
        NUM_FILTER_PLUGINS,
        static_filters,
    )
    from kubernetes_tpu.ops.features import unpack_cluster, unpack_pods

    ct = unpack_cluster(cblobs, caps)
    gpods = unpack_pods(gblobs, caps, pfields, ptmpl)     # leaves [G, ...]
    free0 = ct.free if state is None else state[0]
    nzr0 = ct.nonzero_requested if state is None else state[1]
    if enabled_filters is None:
        enabled_filters = (True,) * NUM_FILTER_PLUGINS
    act = frozenset(active if active is not None else ())
    fit_on = enabled_filters[FILTER_PLUGINS.index("NodeResourcesFit")]
    valid = ct.node_valid
    n = valid.shape[0]

    def per_gang_static(pod):
        masks = static_filters(ct, pod, wk, enabled_filters, act)
        return jnp.all(masks, axis=0) & valid & pod.valid
    static_ok = jax.vmap(per_gang_static)(gpods)          # [G, N]

    # node -> packing domain: the tk column of the topology table; NONE
    # labels (and tk = -1, and ids past the bucket) collapse into the
    # last slot, the pseudo-domain of topology-less nodes
    dom_raw = ct.topo_dom[:, jnp.maximum(tk, 0)]          # [N]
    dom = jnp.where((tk >= 0) & (dom_raw >= 0) & (dom_raw < d_cap - 1),
                    dom_raw, d_cap - 1)
    arange_n = jnp.arange(n)

    if own_nom is None:
        own_nom = jnp.zeros((gpods.req.shape[0], n), jnp.int32)

    def body(carry, xs):
        free, nzr = carry
        ok_s, req, nzreq, m, onom = xs
        # member capacity per node: floored free/request over the columns
        # the request binds, nominated reservations subtracted like the
        # batched fit predicate (framework.go:989 AddPod pass) — except
        # the gang's own nominated members' reservations, handed back
        if fit_on:
            eff = jnp.maximum(
                free - ct.nominated_req
                + onom.astype(free.dtype)[:, None] * req[None, :], 0.0)
            active_col = req > 0.0
            safe_req = jnp.where(active_col, req, 1.0)
            per_col = floor_div(eff, safe_req)
            per_col = jnp.where(active_col[None, :], per_col,
                                jnp.float32(_UNBOUNDED))
            cap_f = jnp.min(per_col, axis=1)              # [N]
        else:
            cap_f = jnp.full((n,), jnp.float32(_UNBOUNDED))
        cap_n = jnp.where(ok_s, jnp.clip(cap_f, 0.0, _UNBOUNDED),
                          0.0).astype(jnp.int32)
        cap_total = jnp.minimum(jnp.sum(cap_n.astype(jnp.float32)),
                                2.0 ** 30).astype(jnp.int32)
        feasible = (cap_total >= m) & (m > 0)
        # domain-major greedy fill: rank domains by capacity descending
        # (the topology-close packing score), densest nodes first within
        # a domain; cumulative take fills exactly `m` members
        dcap = jax.ops.segment_sum(cap_n, dom, num_segments=d_cap)
        d_rank = jnp.argsort(jnp.argsort(-dcap))          # domain -> rank
        key = (d_rank[dom] * _KEY_STRIDE
               + (_KEY_STRIDE - 1
                  - jnp.minimum(cap_n, _KEY_STRIDE - 1)))
        order = jnp.argsort(key)                          # [N] fill order
        cap_sorted = cap_n[order]
        prefix = jnp.cumsum(cap_sorted) - cap_sorted
        take_sorted = jnp.clip(m - prefix, 0, cap_sorted)
        take = jnp.zeros((n,), jnp.int32).at[order].set(take_sorted)
        take = jnp.where(feasible, take, 0)
        # commit the whole gang into the carried usage state
        tf = take.astype(free.dtype)
        free = free - tf[:, None] * req[None, :]
        nzr = nzr + tf[:, None] * nzreq[None, :]
        used_dom = jax.ops.segment_sum((take > 0).astype(jnp.int32), dom,
                                       num_segments=d_cap)
        spans = jnp.sum((used_dom > 0).astype(jnp.int32))
        return (free, nzr), (feasible, take, cap_total, spans)

    xs = (static_ok, gpods.req, gpods.nonzero_req,
          jnp.asarray(need, jnp.int32), jnp.asarray(own_nom, jnp.int32))
    (free_out, nzr_out), (ok, alloc, cap, spans) = jax.lax.scan(
        body, (free0, nzr0), xs)
    guard = jnp.any(jnp.isnan(free_out)).astype(jnp.int32) << 1
    return GangPackResult(ok=ok, alloc=alloc, cap=cap, spans=spans,
                          free=free_out, nzr=nzr_out, guard=guard)


@partial(jax.jit, static_argnames=("caps", "d_cap", "enabled_filters",
                                   "active", "pfields"))
def pack_gangs_jit(cblobs, gblobs, wk, caps, need, tk, d_cap=8,
                   enabled_filters=None, active=None, pfields=None,
                   ptmpl=None, state=None, own_nom=None):
    return pack_gangs(cblobs, gblobs, wk, caps, need, tk, d_cap,
                      enabled_filters, active, pfields, ptmpl, state,
                      own_nom)


@jax.jit
def _capacity(free: jnp.ndarray, req: jnp.ndarray) -> jnp.ndarray:
    """[N, R] free x [R] request -> scalar i32 member-capacity bound."""
    active = req > 0.0
    safe_req = jnp.where(active, req, 1.0)
    per_col = floor_div(jnp.maximum(free, 0.0), safe_req)
    per_col = jnp.where(active[None, :], per_col, jnp.float32(2 ** 30))
    per_node = jnp.min(per_col, axis=1)
    # a request with NO active columns fits anywhere: cap at a big count
    any_active = jnp.any(active)
    total = jnp.sum(jnp.clip(per_node, 0.0, 2.0 ** 30))
    return jnp.where(any_active, total,
                     jnp.float32(2 ** 30)).astype(jnp.int32)


def gang_capacity_device(free, req) -> jax.Array:
    """The host-fallback capacity bound (see the old ``gang_capacity``
    docstring: an optimistic upper bound on how many request-shaped
    members still fit; only provable impossibility may reject on it) —
    returned as the DEVICE scalar. Callers must NOT block on it: the
    scheduler appends it to the one-per-cycle ``device_get`` pull and
    resolves the PreFilter memo a cycle later (the optimistic cost of
    the lag is one normal scheduling attempt, which the bound's contract
    already prices in)."""
    return _capacity(jnp.asarray(free, jnp.float32),
                     jnp.asarray(req, jnp.float32))
