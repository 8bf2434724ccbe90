"""Device kernels for batched DRA allocation feasibility.

The host DRA plugin (plugins/dra.py) used to evaluate claim feasibility
per (pod, node, device) in Python — the worst host tail in the suite.
This module is the device half of its replacement:

- the cluster's device inventory is mirrored into dense per-node tensors
  (``dev_valid``/``dev_selbits``/``dev_in_use``, [N, D]-shaped with D a
  static per-node device bucket), maintained incrementally by
  plugins.dra.DeviceAllocatorView from the ResourceSlice watch;
- every CEL selector (DeviceClass selectors, request selectors, and the
  legacy direct ``device_class_name`` match) is pre-compiled AT WATCH
  TIME into one bit of a per-device verdict bitmask (``dev_selbits``,
  SELBIT_WORDS uint32 words = up to 256 distinct selectors): host CEL
  evaluation happens once per (selector, device) lifetime instead of
  once per (pod, node, device, cycle);
- a request then matches a device iff the request's required-bit mask is
  a subset of the device's verdict bits — a vectorized AND/compare;
- ``batch_feasible`` evaluates the whole pending batch against the whole
  node set inside the SAME jitted program as Filter/Score
  (models.pipeline.schedule_batch ANDs its [B, N] verdict into the
  feasible mask), replicating the host allocator's greedy request-order,
  device-order semantics exactly (the parity contract the allocation
  fuzz in tests/test_dra_fuzz.py enforces).

Greedy parity: the host allocator (DynamicResources.allocate_claim)
walks a pod's unallocated claims in order, each claim's requests in
order, and fills each request with the FIRST eligible free devices in
node device order. The kernel mirrors that with a per-request
cumulative-sum rank over the eligibility mask: ``pick = eligible &
(cumsum <= count)``; picked devices join a carried ``taken`` mask so the
next request sees them as gone. All-mode requests (allocation_mode All)
are feasible iff at least one eligible device remains and take ALL of
them, matching the host's ``want = len(matched)`` arithmetic.

Claims outside the device-expressible subset (matchAttribute
constraints, firstAvailable alternatives, adminAccess, non-positive
counts, selectors that fail to parse) never reach this kernel: the
builder routes their pods through the unchanged host filter path.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

# fixed selector-bitmask width: 8 uint32 words = 256 distinct compiled
# selectors. Fixed (not grown) so the kernel never recompiles as
# selectors register; the 257th distinct selector routes its claims to
# the host path instead (DeviceAllocatorView.MAX_SELECTORS).
SELBIT_WORDS = 8
MAX_SELECTORS = SELBIT_WORDS * 32

# chunk of the pod axis evaluated per lax.map step: bounds the transient
# [chunk, N, D] eligibility masks for giant drain batches
DRA_CHUNK = 256

# ``pinned`` sentinels: -1 = no allocated claim pins this pod; -2 = an
# allocated claim pins it to a node that is not (or no longer) mirrored,
# or two claims pin it to different nodes — feasible nowhere
PIN_ANY = -1
PIN_NONE = -2


@jax.tree_util.register_dataclass
@dataclass
class DraBatch:
    """One launch's DRA inputs (all dynamic args; shapes are the static
    jit key: N = mirror node capacity, D = device bucket per node,
    Q = request bucket per pod, W = SELBIT_WORDS, B = batch bucket).

    Device-side inventory (resident between launches, re-pushed only on
    slice/selector/row changes — see DeviceAllocatorView):
      dev_valid    [N, D]  bool   device exists at (node row, slot)
      dev_selbits  [N, D, W] u32  bit s set iff compiled selector s
                                  accepts the device
      dev_in_use   [N, D]  bool   allocated to some claim (ledger +
                                  assume overlay), re-packed per cycle

    Per-batch claim tensors (packed per cycle from the pods' resolved
    claims; flattened requests across each pod's unallocated claims):
      req_mask     [B, Q, W] u32  bits a device must ALL carry
      req_count    [B, Q]  i32    ExactCount want (0 = unused slot)
      req_all      [B, Q]  bool   allocation_mode All
      pinned       [B]     i32    row an allocated claim pins the pod to
                                  (PIN_ANY / PIN_NONE sentinels)
      active       [B]     bool   pod routed through the device
                                  allocator (False rows verdict True)
    """

    dev_valid: jax.Array
    dev_selbits: jax.Array
    dev_in_use: jax.Array
    req_mask: jax.Array
    req_count: jax.Array
    req_all: jax.Array
    pinned: jax.Array
    active: jax.Array


def batch_feasible(dra: DraBatch) -> jnp.ndarray:
    """[B, N] bool: can every unallocated claim of pod b be allocated on
    node n (greedy host-parity semantics), and does n satisfy the pod's
    allocated-claim pins? Inactive rows are all-True (the caller ANDs
    this into the feasible mask)."""
    free = dra.dev_valid & ~dra.dev_in_use                      # [N, D]
    n = free.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    q_cap = dra.req_mask.shape[1]

    def per_pod(mask, count, is_all, pinned, active):
        taken = jnp.zeros(free.shape, bool)                     # [N, D]
        ok = jnp.ones((n,), bool)
        for q in range(q_cap):      # static unroll: Q is a small bucket
            sel_ok = jnp.all((dra.dev_selbits & mask[q][None, None, :])
                             == mask[q][None, None, :], axis=-1)  # [N, D]
            elig = free & ~taken & sel_ok
            csum = jnp.cumsum(elig.astype(jnp.int32), axis=1)
            total = csum[:, -1]                                 # [N]
            used = (count[q] > 0) | is_all[q]
            want = jnp.where(is_all[q], 1, count[q])
            ok = ok & (~used | (total >= want))
            # greedy pick in device order (parity with the host fill's
            # first-come walk); All mode takes every eligible device
            pick = elig & (is_all[q] | (csum <= count[q]))
            taken = taken | pick
        ok = ok & jnp.where(pinned >= 0, rows == pinned,
                            pinned == PIN_ANY)
        return ok | ~active

    b = dra.req_mask.shape[0]
    tree = (dra.req_mask, dra.req_count, dra.req_all, dra.pinned,
            dra.active)
    if b <= DRA_CHUNK:
        return jax.vmap(per_pod)(*tree)
    # chunk the pod axis so the transient [chunk, N, D] masks stay small
    pad = (-b) % DRA_CHUNK
    if pad:
        tree = jax.tree.map(
            lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)),
            tree)
    groups = (b + pad) // DRA_CHUNK
    tree = jax.tree.map(
        lambda x: x.reshape((groups, DRA_CHUNK) + x.shape[1:]), tree)
    out = jax.lax.map(lambda t: jax.vmap(per_pod)(*t), tree)
    return out.reshape((groups * DRA_CHUNK, n))[:b]


@jax.jit
def batch_feasible_jit(dra: DraBatch) -> jnp.ndarray:
    """Standalone jitted entry (tests, the parity fuzz); production goes
    through models.pipeline.schedule_batch, which fuses batch_feasible
    into the Filter/Score launch."""
    return batch_feasible(dra)
