"""Host->HBM mirror: packs the generation-diffed snapshot into dense blobs.

This is the TPU-native replacement for the reference's incremental snapshot
refresh (cache.go:186 UpdateSnapshot): instead of cloning Go NodeInfo structs,
we re-pack only *changed* node rows (generation diff) directly into dense
numpy blob buffers (one f32 + one i32 per struct kind, see ops.blobs) and ship
at most three arrays to the device per cycle. Each node keeps a stable row
index for its lifetime; scheduled pods occupy slots of a device pod table used
by inter-pod-affinity / topology-spread kernels.

All strings are interned (utils.interner); set-valued fields are padded to
the static Capacities. Over-capacity conditions raise CapacityError — the
caller re-buckets (doubles the capacity and re-packs, which recompiles the
kernels once per bucket).
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from kubernetes_tpu.api.labels import (
    label_selector_matches,
    requirements_match,
    selector_requirements,
)
from kubernetes_tpu.api.objects import (
    Affinity,
    Pod,
    PodAffinityTerm,
)
from kubernetes_tpu.api.resources import Resource
from kubernetes_tpu.backend.node_info import NodeInfo, PodInfo
from kubernetes_tpu.backend.snapshot import Snapshot
from kubernetes_tpu.ops import features as F
from kubernetes_tpu.ops.features import (
    Capacities,
    ClusterBlobs,
    ClusterTensors,
    PodBlobs,
    PodFeatures,
    codecs,
    unpack_cluster,
    unpack_pods,
)
from kubernetes_tpu.utils.interner import NONE, Interner

MI = 1024 * 1024

# taint the node controller applies for spec.unschedulable; the
# NodeUnschedulable plugin simulates tolerating it (plugins/nodeunschedulable)
TAINT_UNSCHEDULABLE = "node.kubernetes.io/unschedulable"

# ---- PodFeatures field groups for subset transfers (pod_fields) ----
# fields every launch reads (fit, tie-break, unschedulable-taint simulation,
# NodeName, the commit scan/auction carries)
POD_CORE_FIELDS = (
    "valid", "req", "nonzero_req", "num_containers", "priority",
    "ns", "name_id", "uid_id", "nominated_row", "node_name_id",
    "tol_valid", "tol_key", "tol_op", "tol_val", "tol_effect",
)
# per active-feature additions (Mirror.launch_features)
POD_FEATURE_FIELDS = {
    "images": ("image_ids",),
    "ports": ("hp_ip", "hp_proto", "hp_port"),
    "nodeaffinity": (
        "aff_pin", "nodesel_cols", "nodesel_vals", "sel_term_valid",
        "sel_col", "sel_op", "sel_is_field", "sel_vals", "sel_num",
        "pref_weight", "pref_col", "pref_op", "pref_is_field", "pref_vals",
        "pref_num"),
    # pin-only batches (daemonset shape): ONE i32 per pod instead of the
    # 14 selector/preferred arrays — the kernels compile to a [N] compare
    "nodeaffinity_pin": ("aff_pin",),
}
# everything the topology kernels read (enable_topology launches)
POD_TOPO_FIELDS = (
    "plabel_vals", "aff_self_match",
    "tsc_tk", "tsc_max_skew", "tsc_hard", "tsc_min_domains",
    "tsc_sel_cols", "tsc_sel_ops", "tsc_sel_vals",
    "tsc_honor_affinity", "tsc_honor_taints",
) + tuple(
    f"{g}_{suffix}"
    for g in ("aff", "anti", "paff", "panti")
    for suffix in ("tk", "ns", "ns_all", "sel_cols", "sel_ops", "sel_vals")
) + ("paff_weight", "panti_weight")

_unpack_cluster_jit = jax.jit(unpack_cluster, static_argnums=1)


def _f32_ceil(x) -> np.float32:
    """Smallest float32 >= x (x exact in float64 for byte values < 2^53:
    /MI is a power-of-two scale). Demand rounds UP. Comparisons go
    through python float: NEP-50 weak promotion would otherwise demote
    x to float32 and hide the rounding error being tested for."""
    v = np.float32(x)
    return v if float(v) >= float(x) else np.nextafter(v,
                                                       np.float32(np.inf))


def _f32_floor(x) -> np.float32:
    """Largest float32 <= x. Capacity rounds DOWN."""
    v = np.float32(x)
    return v if float(v) <= float(x) else np.nextafter(v,
                                                       np.float32(-np.inf))


def _round_row_f32(row64: np.ndarray, up: bool) -> np.ndarray:
    """Vectorized directed f32 rounding of a float64 row (the scalar
    helpers per column were measurable on the pod-commit fast path)."""
    v = row64.astype(np.float32)
    back = v.astype(np.float64)
    m = (back < row64) if up else (back > row64)
    if m.any():
        v[m] = np.nextafter(v[m],
                            np.float32(np.inf) if up
                            else np.float32(-np.inf))
    return v
_unpack_pods_jit = jax.jit(unpack_pods, static_argnums=1)


@jax.named_scope("scatter_rows")
def _scatter_rows(buf, idx, rows):
    return buf.at[idx].set(rows)


# donate the resident buffer: the update happens in place on device
_scatter_rows_jit = jax.jit(_scatter_rows, donate_argnums=(0,))


import dataclasses


@dataclasses.dataclass
class LaunchSpec:
    """Everything one schedule_batch launch needs (Mirror.prepare_launch).
    ``enable_topology``/``d_cap``/``active``/``pfields`` are the STATIC
    launch args; ``ptmpl`` is the device-resident template backing the
    subset pod blobs."""

    cblobs: ClusterBlobs
    pblobs: PodBlobs
    enable_topology: bool
    d_cap: int
    active: tuple[str, ...]
    pfields: tuple[str, ...]
    ptmpl: PodBlobs
    # topology dedup groups (see pipeline: group-level topology statics).
    # gid [B] i32: per-pod group id; rep [G_cap] i32: representative pod row
    # per group (padded); g_cap: static pow2 group-count bucket.
    gid: jnp.ndarray | None = None
    rep: jnp.ndarray | None = None
    g_cap: int = 0
    # batched DRA allocator inputs (ops.dra.DraBatch), attached by the
    # Scheduler after prepare_launch when the batch carries device-routed
    # claim pods; None compiles the DRA kernel out of the launch
    dra: object | None = None
    # SOFT-ONLY topology launch: enable_topology is on but no batch pod
    # carries a required (anti)affinity term or a DoNotSchedule spread
    # constraint — soft terms are scores, not constraints, so the caller
    # may run the parallel auction with the fused soft-score terms
    # (pipeline._soft_statics) instead of the serial commit scan
    topo_soft: bool = False
    # 1 + the highest pod-table slot in use when cblobs was taken (0: an
    # empty table): pipeline.table_blocks_for(table_hi, slots) is the
    # blocks of the table the launch's phase 1b reads
    table_hi: int = 0


class CapacityError(Exception):
    """A padded capacity was exceeded; caller should re-bucket (double the
    capacity and re-pack; kernels recompile once per bucket)."""

    def __init__(self, field: str, needed: int):
        super().__init__(f"capacity exceeded for {field}: need {needed}")
        self.field = field
        self.needed = needed



# phase-1 dedup group bucket for no-topology launches (prepare_launch):
# FIXED so the static g_cap jit key never varies with batch composition
P1_DEDUP_GROUP_CAP = 8

# bucket hysteresis (ISSUE 15): the topology DOMAIN bucket (a static
# jit arg) EXPANDS immediately on demand but only SHRINKS after this
# many consecutive launches needed at most half of it — and the
# high-water mark survives capacity re-buckets (adopt_hysteresis), so
# an oscillating cluster size (churn recreating nodes around a growth
# boundary) stops minting fresh compiled shapes every swing
# (scheduler_device_compiles_total{cause=rebucket|topology_bucket}
# stays flat across the oscillation).
BUCKET_DECAY_LAUNCHES = 64

# bound of the packed-row cache (Mirror._pack_batch_np), per field set:
# one more distinct pod shape than this clears it, so a stream of pods
# that never repeat holds a few MiB at most
POD_ROW_CACHE_ENTRIES = 4096

# widest dirty set whose scatter bucket is compiled ahead (_warm_scatter): a
# launch dirties at most a node row and a pod slot a pod, and no
# configuration launches more than 4,096. Warming up to the bound where a
# push becomes a full upload (a quarter of the table) cost 1.2 to 2 s and
# 10 to 273 MB of peak device memory a start on the chip, for buckets of
# 8,192 to 65,536 rows that only a bulk change can reach (PERF.md 6, PR 34)
SCATTER_WARM_ROWS = 4096


def _selector_key(sel):
    """A LabelSelector's content for Mirror._pod_row_key (None stays
    None: a nil selector packs differently from an empty one)."""
    if sel is None:
        return None
    return (tuple(sel.match_labels.items()),
            tuple((e.key, e.operator, tuple(e.values))
                  for e in sel.match_expressions)
            if sel.match_expressions else ())


def _term_key(weight: int, t: PodAffinityTerm) -> tuple:
    """What a packed (anti-)affinity term reads of the term itself, for
    the two content keys (Mirror._pod_row_key, Mirror._slot_row_key). The
    caller has turned away a term with a namespace_selector."""
    return (weight, t.topology_key, _selector_key(t.label_selector),
            tuple(t.namespaces), tuple(t.match_label_keys),
            tuple(t.mismatch_label_keys))


def _weighted_terms(pi: PodInfo) -> tuple:
    """A PodInfo's four term lists in the pod table's order (anti,
    affinity, preferred affinity, preferred anti), each as (weight, term)
    pairs; a required term weighs 0."""
    return ([(0, t) for t in pi.required_anti_affinity_terms],
            [(0, t) for t in pi.required_affinity_terms],
            [(w.weight, w.pod_affinity_term)
             for w in pi.preferred_affinity_terms],
            [(w.weight, w.pod_affinity_term)
             for w in pi.preferred_anti_affinity_terms])


class Mirror:
    def __init__(self, interner: Interner | None = None,
                 caps: Capacities = Capacities(), mesh=None):
        self.caps = caps
        # multi-chip: shard the resident node table over the mesh's 'nodes'
        # axis (SURVEY §5.7 — the node axis is what outgrows one chip's
        # HBM). Every launch consuming to_blobs() then runs SPMD over the
        # mesh with no further plumbing: jit partitions the program from
        # the operand shardings, reductions become ICI collectives.
        self.mesh = mesh
        self._dev_sharding: dict[str, object] = {}
        self._scatter_fns: dict[str, object] = {}
        if mesh is not None:
            from kubernetes_tpu.parallel import mirror_shardings

            self._dev_sharding = mirror_shardings(mesh)
            for key, sh in self._dev_sharding.items():
                # pin the scatter output to the resident sharding so the
                # incremental path can never drift the buffer to a layout
                # the launch programs weren't compiled for
                self._scatter_fns[key] = jax.jit(
                    _scatter_rows, donate_argnums=(0,), out_shardings=sh)
        self.interner = interner or Interner()
        self.node_codec, self.table_codec, self.pod_codec = codecs(caps)
        self.node_f32, self.node_i32 = self.node_codec.alloc(caps.nodes)
        _, self.pods_i32 = self.table_codec.alloc(caps.pods)
        self._row_of: dict[str, int] = {}        # node name -> row
        self._row_gen: dict[str, int] = {}       # node name -> packed generation
        self._free_rows: list[int] = list(range(caps.nodes - 1, -1, -1))
        self._ext_index: dict[str, int] = {}     # extended resource -> column
        # columnized node labels: key string -> column
        self._label_col: dict[str, int] = {}
        # columnized pod labels (separate key space from node labels)
        self._pod_label_col: dict[str, int] = {}
        # topology keys in use by any term/constraint: key -> tk index, with
        # per-tk compact domain ids (value id -> dense domain index) and the
        # raw node labels per row for backfilling when a NEW topology key
        # registers after nodes were already packed (rare: hostname/zone/
        # region are pre-registered below)
        self._topo_col: dict[str, int] = {}
        self._tk_key: list[str] = []
        self._tk_domains: list[dict[int, int]] = []
        self._row_node_labels: dict[int, dict[str, str]] = {}
        # topo keys referenced by any packed term/constraint (batch or table):
        # bounds the domain scatter space a launch actually needs
        self._used_tks: set[int] = set()
        # table pods carrying terms: uid -> the PodInfo whose four term
        # lists the slot was packed from (_slot_holds compares them)
        self._uids_with_terms: dict[str, PodInfo] = {}
        # namespace store (name -> labels) for unrolling namespaceSelectors;
        # table pods whose terms carry a non-empty namespaceSelector repack
        # when the namespace set changes (sync checks ns_generation)
        self._namespaces: dict[str, dict[str, str]] = {}
        self._ns_gen = 0
        self._uids_with_nssel: set[str] = set()
        # nominated (preemptor) pods: packed per-cycle by set_nominated under
        # "nominated:<uid>" keys; per-row reserved request sums
        self._nominated_uids: set[str] = set()
        self._nominated_req_of_row: dict[int, np.ndarray] = {}
        self._pod_tmpl: tuple[np.ndarray, np.ndarray] | None = None
        self._pod_tmpl_dev = None          # device push of _pod_template
        self._subset_tmpl: dict[tuple, tuple[np.ndarray, np.ndarray]] = {}
        # packed-row cache: fields-tuple -> content key (_pod_row_key) ->
        # row, and its counts (totals of the scheduler's life: a fresh
        # mirror takes them over in adopt_hysteresis)
        self._pod_rows: dict[tuple, dict] = {}
        self.row_cache_hits = 0
        self.row_cache_misses = 0
        self.row_cache_bypass = 0
        self.row_cache_clears = 0
        # seconds _pack_batch_np spent on the rows the cache did not serve
        # (bypass and miss alike), by its own clock pair around each such
        # row (the flight recorder's pack_full view); a hit reads no clock
        self.pack_full_s = 0.0
        self._table_i32_tmpl: np.ndarray | None = None
        # pt_label_vals rows by the labels' content (pod_labels_row), and
        # the offsets the term-free arm of _pack_pod_slot writes at
        self._label_rows: dict[tuple, np.ndarray] = {}
        tc_off = self.table_codec._i32_off
        self._slot_scalar_off = tuple(
            tc_off[name][0] for name in
            ("pod_node", "pod_ns", "pod_uid", "pod_nominated"))
        self._slot_labels_off = tc_off["pt_label_vals"]
        self._slot_valid_off = tc_off["pod_valid"][0]
        # packed pod-table rows of pods with terms, by the slot's content
        # (_slot_row_key), read-only, and the cache's counts: totals of
        # the scheduler's life, as the packed-row cache's are
        self._slot_rows: dict[tuple, np.ndarray] = {}
        self.slot_row_hits = 0
        self.slot_row_misses = 0
        self.slot_row_bypass = 0
        self.slot_row_clears = 0
        self._row_node_obj: dict[int, object] = {}  # row -> packed Node obj
        # [N, R] float64 image of each row's allocatable, true while the
        # row's Node object stands (NodeInfo.set_node derives both): the
        # exact minuend of ``free`` (_update_rows_resources)
        self._alloc64 = np.zeros((caps.nodes, caps.res_cols), np.float64)
        # what sync and patch_node did, totals of the scheduler's life
        # like the row cache's (sync_stats; adopt_hysteresis carries them)
        self.rows_synced = 0
        self.slots_packed = 0
        self.slots_kept = 0
        self.slots_released = 0
        # of slots_packed, the slots of pods with (anti)affinity terms:
        # _pack_pod_slot's terms arm (a copied row or a full pack), and
        # the seconds it spent there by its own clock pair (the flight
        # recorder's slot_pack_terms view)
        self.slots_packed_terms = 0
        self.slot_terms_s = 0.0
        # workload-activity tracking for launch_features(): which rows carry
        # taints / used host ports / images — a feature absent cluster-wide
        # AND batch-wide compiles out of the launch entirely
        self._rows_with_taints: set[int] = set()
        self._rows_with_ports: set[int] = set()
        self._rows_with_images: set[int] = set()
        # every namespace any packed pod lives in: selectors are evaluated
        # over store ∪ pod namespaces (labels default {}), matching the
        # reference's nil-nsLabels behavior for namespaces that have no
        # Namespace object (AffinityTerm.Matches with empty labels.Set)
        self._known_pod_ns: set[str] = set()
        self._pod_slot: dict[str, int] = {}      # pod uid -> pod-table slot
        # node -> uid -> the Pod object its slot was packed from, held
        # strongly so identity is a sound first test (a bare id() could be
        # reused after GC); an object of equal content is re-pointed to
        # (_slot_holds), any other re-packs
        self._node_pods: dict[str, dict[str, Pod]] = {}
        self._node_of_pod: dict[str, str] = {}   # uid -> node name
        self._free_slots: list[int] = list(range(caps.pods - 1, -1, -1))
        # 1 + the highest slot in use (0: none). The lowest free slot goes
        # first, so the live slots sit under it; phase 1b reads the table
        # up to it (LaunchSpec.table_hi)
        self.slots_hi = 0
        self._row_names: list[str | None] = [None] * caps.nodes
        # domain-bucket hysteresis high-water mark + decay counter (see
        # BUCKET_DECAY_LAUNCHES); survives re-bucketing via
        # adopt_hysteresis so a fresh mirror doesn't re-learn it
        self._d_hw = 0
        self._d_low = 0
        # incremental device-mirror dirty tracking: per-row/slot sets feed a
        # scatter-update of the resident HBM buffers (the row-level analog of
        # the reference's generation-diffed UpdateSnapshot, cache.go:186);
        # the bool flags force a full re-upload (first sync, topo backfill)
        self._dirty_full = {"node": True, "pods": True}
        self._dirty_rows: set[int] = set()
        self._dirty_slots: set[int] = set()
        self._dev: dict[str, jax.Array] = {}
        self._last_sync: tuple[int, int] | None = None
        # (last_sync, hash) memo behind free_fingerprint()
        self._free_fp: tuple | None = None
        # stable well-known ids, interned up front
        self.wk_unschedulable_key = self._i(TAINT_UNSCHEDULABLE)
        self.wk_wildcard_ip = self._i("0.0.0.0")
        # pre-register the ubiquitous topology keys so backfill never runs
        # for them (LABEL_HOSTNAME/ZONE/REGION, api.objects)
        for key in ("kubernetes.io/hostname", "topology.kubernetes.io/zone",
                    "topology.kubernetes.io/region"):
            self.topo_col(key)

    def well_known(self) -> dict[str, jnp.ndarray]:
        return {
            "unschedulable_taint_key": jnp.int32(self.wk_unschedulable_key),
            "wildcard_ip": jnp.int32(self.wk_wildcard_ip),
        }

    # ------------- interning helpers -------------

    def _i(self, s: str) -> int:
        # ids are unbounded: no device-side vocab table exists (numeric label
        # values ride the per-node label_nums column instead)
        return self.interner.intern(s)

    def label_col(self, key: str) -> int:
        """Register (or fetch) the label column for a node-label key.
        Only NODES register columns; pods resolve with label_col_lookup."""
        col = self._label_col.get(key)
        if col is None:
            if len(self._label_col) >= self.caps.label_cols:
                raise CapacityError("label_cols", len(self._label_col) + 1)
            self._label_col[key] = col = len(self._label_col)
        return col

    def label_col_lookup(self, key: str) -> int:
        """Column for a key, NONE if no node carries it (the selector then
        matches no node's label — pods repack every cycle, so a key that
        appears later is picked up on the next pack)."""
        return self._label_col.get(key, NONE)

    def pod_label_col(self, key: str) -> int:
        """Register (or fetch) the pod-label column for a key. Registered
        from BOTH pod labels and term selectors so that whichever side packs
        first, the (col, value) match stays consistent."""
        col = self._pod_label_col.get(key)
        if col is None:
            if len(self._pod_label_col) >= self.caps.pod_label_cols:
                raise CapacityError("pod_label_cols",
                                    len(self._pod_label_col) + 1)
            self._pod_label_col[key] = col = len(self._pod_label_col)
        return col

    def topo_col(self, key: str) -> int:
        """Register (or fetch) the topology-key index for a term/constraint
        topology key. A NEW key after nodes were packed backfills the
        topo_dom column for every packed row from the retained node labels."""
        tk = self._topo_col.get(key)
        if tk is not None:
            return tk
        if len(self._topo_col) >= self.caps.topo_cols:
            raise CapacityError("topo_cols", len(self._topo_col) + 1)
        self._topo_col[key] = tk = len(self._topo_col)
        self._tk_key.append(key)
        self._tk_domains.append({})
        if self._row_node_labels:
            off, _ = self.node_codec._i32_off["topo_dom"]
            for row, labels in self._row_node_labels.items():
                value = labels.get(key)
                dom = (self.domain_id(tk, self._i(value))
                       if value is not None else NONE)
                self.node_i32[row, off + tk] = dom
            self._dirty_full["node"] = True
        return tk

    def domain_id(self, tk: int, value_id: int) -> int:
        """Compact per-topology-key domain index for a label value."""
        dmap = self._tk_domains[tk]
        d = dmap.get(value_id)
        if d is None:
            d = dmap[value_id] = len(dmap)
            if d >= self.caps.domain_cap:
                raise CapacityError("domains", d + 1)
        return d

    def ext_col(self, resource_name: str) -> int:
        col = self._ext_index.get(resource_name)
        if col is None:
            nxt = F.NUM_NATIVE_COLS + len(self._ext_index)
            if nxt >= self.caps.res_cols:
                raise CapacityError("ext_resources", len(self._ext_index) + 1)
            self._ext_index[resource_name] = col = nxt
        return col

    def _res_row64(self, r: Resource) -> np.ndarray:
        """Exact float64 column image (exact for byte values < 2^53:
        /MI is a power-of-two scale)."""
        row = np.zeros((self.caps.res_cols,), np.float64)
        row[F.COL_CPU] = r.milli_cpu
        row[F.COL_MEM] = r.memory / MI
        row[F.COL_EPH] = r.ephemeral_storage / MI
        row[F.COL_PODS] = r.allowed_pod_number
        for name, v in r.scalar.items():
            row[self.ext_col(name)] = v
        return row

    def _res_row(self, r: Resource, capacity: bool = False) -> np.ndarray:
        """Pack a Resource into its f32 column image. f32 is EXACT for
        Mi-granular memory up to 16 TiB and integer values up to 2^24
        (ops/features.py unit notes) — but odd-byte memory or huge
        extended-resource counts are not representable, and a silently
        nearest-rounded image could flip the device fit compare against
        the exact-integer semantics of fitsRequest (fit.go:509-592).
        Non-representable quantities are therefore rounded
        CONSERVATIVELY: demand (pod requests) rounds UP;
        ``capacity=True`` (node allocatable, and preemption freed-amount
        rows, which add back onto capacity) rounds DOWN. Differences
        like free = alloc - requested are computed in float64 and
        floored (_update_rows_resources): subtracting two f32 images
        would round to NEAREST and could overstate headroom."""
        return _round_row_f32(self._res_row64(r), up=not capacity)

    def _pairs(self, labels: dict[str, str], cap: int, what: str
               ) -> tuple[np.ndarray, np.ndarray]:
        if len(labels) > cap:
            raise CapacityError(what, len(labels))
        k = np.full((cap,), NONE, np.int32)
        v = np.full((cap,), NONE, np.int32)
        for idx, (key, val) in enumerate(labels.items()):
            k[idx] = self._i(key)
            v[idx] = self._i(val)
        return k, v

    # ------------- node rows -------------

    def row_of(self, name: str) -> int:
        return self._row_of.get(name, -1)

    def name_of_row(self, row: int) -> str | None:
        return self._row_names[row] if 0 <= row < len(self._row_names) else None

    # ------------- preemption dry-run views -------------

    def table_valid_mask(self, exclude_uids) -> np.ndarray:
        """[PT] bool, False at the slots of ``exclude_uids``: the victim
        masking a preemption dry-run feeds to preempt_feasible (the device
        analog of RemovePod in the reference's per-node dry-run,
        preemption.go:682)."""
        m = np.ones((self.caps.pods,), bool)
        for uid in exclude_uids:
            s = self._pod_slot.get(uid)
            if s is not None:
                m[s] = False
        return m

    def free_matrix(self) -> np.ndarray:
        """[N, R] f32 copy of the free-resource columns from the host-side
        node blobs — the base a dry-run adds evicted requests onto."""
        off, size = self.node_codec._f32_off["free"]
        return self.node_f32[:, off:off + size].copy()

    def free_fingerprint(self) -> str:
        """Content hash of the free matrix, memoized per sync: the gang
        capacity memo's freshness token. CONTENT-keyed on purpose — a
        reserve-then-rollback wave bumps the cache version but returns
        free to identical bytes, and a version-keyed token would churn
        the memo forever (the async bound would never land while a
        doomed gang keeps reserving and rolling back)."""
        if self._free_fp is None or self._free_fp[0] != self._last_sync:
            import hashlib

            h = hashlib.blake2b(self.free_matrix().tobytes(),
                                digest_size=8).hexdigest()
            self._free_fp = (self._last_sync, h)
        return self._free_fp[1]

    def _pack_ports(self, info: NodeInfo, f: dict[str, np.ndarray],
                    row: int | None = None) -> None:
        caps = self.caps
        entries = [(ip, proto, port)
                   for ip, s in info.used_ports.ports.items()
                   for (proto, port) in s]
        if len(entries) > caps.node_ports:
            raise CapacityError("node_ports", len(entries))
        if row is not None:
            (self._rows_with_ports.add(row) if entries
             else self._rows_with_ports.discard(row))
        pi = np.full((caps.node_ports,), NONE, np.int32)
        pp = np.full((caps.node_ports,), NONE, np.int32)
        pn = np.full((caps.node_ports,), NONE, np.int32)
        for i, (ip, proto, port) in enumerate(entries):
            pi[i] = self._i(ip)
            pp[i] = self._i(proto)
            pn[i] = port
        f["port_ips"], f["port_protos"], f["port_nums"] = pi, pp, pn

    def _update_rows_resources(self, rows: list[int],
                               infos: list[NodeInfo]) -> None:
        """What a node's pods move, for every row a sync (or one
        patch_node) touched, in one vector pass: free and
        nonzeroRequested, the port fields where the row has or had a host
        port, then the pod-table reconcile and the packed generation.
        The node objects' own fields are _pack_node_row's, which has run
        for the rows whose Node object changed.

        free is the exact float64 difference floored into f32:
        alloc_f32 - req_f32 would round to NEAREST and can overstate the
        exact free."""
        if not rows:
            return
        nc = self.node_codec
        rows_a = np.asarray(rows, np.intp)
        req = np.zeros((len(rows), self.caps.res_cols), np.float64)
        req[:, :F.COL_PODS] = [
            (r.milli_cpu, r.memory / MI, r.ephemeral_storage / MI)
            for r in [info.requested for info in infos]]
        for i, info in enumerate(infos):
            if info.requested.scalar:
                for name, v in info.requested.scalar.items():
                    req[i, self.ext_col(name)] = v
        alloc = self._alloc64[rows_a]
        free = _round_row_f32(alloc - req, up=False)
        free[:, F.COL_PODS] = alloc[:, F.COL_PODS] - [
            len(info.pods) for info in infos]
        off, size = nc._f32_off["free"]
        self.node_f32[rows_a, off:off + size] = free
        off, size = nc._f32_off["nonzero_requested"]
        self.node_f32[rows_a, off:off + size] = [
            (z.milli_cpu, z.memory / MI)
            for z in [info.non_zero_requested for info in infos]]
        self._dirty_rows.update(rows)
        for row, info in zip(rows, infos):
            # a row that has no host port and had none keeps its three
            # NONE port fields as they stand
            if info.used_ports.ports or row in self._rows_with_ports:
                f: dict[str, np.ndarray] = {}
                self._pack_ports(info, f, row)
                nc.pack_into(self.node_f32[row], self.node_i32[row], f)
            self._reconcile_node_pods(row, info)
            self._row_gen[info.name] = info.generation

    def _pack_node_row(self, row: int, info: NodeInfo) -> None:
        """The full arm: every field that comes from the Node object
        (and the port fields, so a fresh row reads NONE there). What the
        node's pods move follows in _update_rows_resources."""
        caps = self.caps
        node = info.node
        assert node is not None
        f: dict[str, np.ndarray] = {}
        alloc64 = self._alloc64[row] = self._res_row64(info.allocatable)
        f["allocatable"] = _round_row_f32(alloc64, up=False)
        f["nominated_req"] = self._nominated_req_of_row.get(
            row, np.zeros((caps.res_cols,), np.float32))
        f["node_valid"] = np.bool_(True)
        f["unschedulable"] = np.bool_(node.spec.unschedulable)
        f["node_name_id"] = np.int32(self._i(node.metadata.name))
        vals = np.full((caps.label_cols,), NONE, np.int32)
        nums = np.full((caps.label_cols,), np.nan, np.float32)
        for key, value in node.metadata.labels.items():
            col = self.label_col(key)
            vid = self._i(value)
            vals[col] = vid
            nums[col] = self.interner.numeric(vid)
        f["label_col_vals"] = vals
        f["label_col_nums"] = nums
        doms = np.full((caps.topo_cols,), NONE, np.int32)
        for tk, key in enumerate(self._tk_key):
            value = node.metadata.labels.get(key)
            if value is not None:
                doms[tk] = self.domain_id(tk, self._i(value))
        f["topo_dom"] = doms
        self._row_node_labels[row] = node.metadata.labels
        self._dirty_rows.add(row)
        if len(node.spec.taints) > caps.node_taints:
            raise CapacityError("node_taints", len(node.spec.taints))
        tk = np.full((caps.node_taints,), NONE, np.int32)
        tv = np.full((caps.node_taints,), NONE, np.int32)
        te = np.full((caps.node_taints,), NONE, np.int32)
        for i, t in enumerate(node.spec.taints):
            tk[i] = self._i(t.key)
            tv[i] = self._i(t.value)
            te[i] = F.effect_id(t.effect)
        f["taint_keys"], f["taint_vals"], f["taint_effects"] = tk, tv, te
        (self._rows_with_taints.add(row) if node.spec.taints
         else self._rows_with_taints.discard(row))
        self._pack_ports(info, f, row)
        imgs = list(info.image_sizes.items())
        (self._rows_with_images.add(row) if imgs
         else self._rows_with_images.discard(row))
        if len(imgs) > caps.node_images:
            imgs = imgs[: caps.node_images]  # best-effort: scoring-only signal
        ii = np.full((caps.node_images,), NONE, np.int32)
        isz = np.zeros((caps.node_images,), np.float32)
        for i, (name, size) in enumerate(imgs):
            ii[i] = self._i(name)
            isz[i] = size / MI
        f["image_ids"], f["image_sizes"] = ii, isz
        self.node_codec.pack_into(self.node_f32[row], self.node_i32[row], f)
        self._row_node_obj[row] = node

    def _reconcile_node_pods(self, row: int, info: NodeInfo) -> None:
        """Bring the node's pod-table slots to ``info.pods``: one pass
        that packs the pods new to the node and re-packs the ones whose
        slot would read differently; a pod whose object was replaced by
        one of equal content (the informer's confirmation of an assumed
        pod, Cache.add_pod) keeps its slot. The release scan runs only
        when the counts say something left the node."""
        name = info.name
        current = self._node_pods.get(name)
        if current is None:
            current = self._node_pods[name] = {}
        known = 0
        fresh: list[PodInfo] = []
        for pi in info.pods:
            pod = pi.pod
            uid = pod.metadata.uid
            packed = current.get(uid)
            if packed is pod:
                known += 1
            elif packed is None:
                fresh.append(pi)
            else:
                known += 1
                if self._slot_holds(uid, packed, pi):
                    current[uid] = pod
                    self.slots_kept += 1
                else:
                    self._release_pod_slot(uid)
                    self._pack_pod_slot(uid, pi, row, name)
        # nominated slots are owned by set_nominated, not the node diff
        if self._nominated_uids:
            known += sum(1 for uid in current
                         if uid.startswith("nominated:"))
        if known != len(current):
            live_uids = {p.pod.metadata.uid for p in info.pods}
            for uid in [u for u in current if u not in live_uids
                        and not u.startswith("nominated:")]:
                self._release_pod_slot(uid)
        for pi in fresh:
            uid = pi.pod.metadata.uid
            if uid in self._pod_slot:
                # moved here before its source node was reconciled
                self._release_pod_slot(uid)
            self._pack_pod_slot(uid, pi, row, name)

    def _slot_holds(self, uid: str, packed_pod: Pod, pi: PodInfo) -> bool:
        """Would _pack_pod_slot write into ``uid``'s slot, packed from
        ``packed_pod`` on the same row, the bytes it holds? Everything
        the pack reads of a pod is compared with what it read then: the
        namespace, the labels (the pt_label_vals row, and the values a
        term's match_label_keys copy) and the four term lists of the
        PodInfo. A bind sets only spec.node_name, so a confirmation
        holds; an update that moves a label does not."""
        old, new = packed_pod.metadata, pi.pod.metadata
        if old.namespace != new.namespace or old.labels != new.labels:
            return False
        packed = self._uids_with_terms.get(uid)
        if packed is None:
            return not NodeInfo._has_affinity(pi)
        return packed is pi or (
            packed.required_anti_affinity_terms
            == pi.required_anti_affinity_terms
            and packed.required_affinity_terms
            == pi.required_affinity_terms
            and packed.preferred_affinity_terms
            == pi.preferred_affinity_terms
            and packed.preferred_anti_affinity_terms
            == pi.preferred_anti_affinity_terms)

    def pod_labels_row(self, labels: dict[str, str]) -> np.ndarray:
        """Labels as a pod-label-column value row [Kp] (registers keys),
        read-only: pods of one deployment share it. Kept by the labels'
        content under _pack_batch_np's invariant (pod-label columns and
        the interner only append for the Mirror's life, so a kept row
        stays true and a hit may skip the registering) and bounded as
        that cache is."""
        key = tuple(labels.items())
        row = self._label_rows.get(key)
        if row is None:
            row = np.full((self.caps.pod_label_cols,), NONE, np.int32)
            for k, v in labels.items():
                row[self.pod_label_col(k)] = self._i(v)
            row.flags.writeable = False
            if len(self._label_rows) > POD_ROW_CACHE_ENTRIES:
                self._label_rows.clear()
            self._label_rows[key] = row
        return row

    def _pack_term_group(self, pi_terms, weights, pod: Pod, prefix: str,
                         f: dict[str, np.ndarray]) -> None:
        """One (anti)affinity term group -> tk/ns/ns_all/sel_cols/sel_ops/
        sel_vals arrays (+ weight for preferred groups)."""
        caps = self.caps
        A, NS, MS, V2 = (caps.aff_terms, caps.aff_ns, caps.aff_sel,
                         caps.aff_sel_vals)
        tk = np.full((A,), NONE, np.int32)
        ns = np.full((A, NS), NONE, np.int32)
        nall = np.zeros((A,), bool)
        sc = np.full((A, MS), NONE, np.int32)
        so = np.full((A, MS), NONE, np.int32)
        sv = np.full((A, MS, V2), NONE, np.int32)
        if len(pi_terms) > A:
            raise CapacityError("aff_terms", len(pi_terms))
        for t_idx, term in enumerate(pi_terms):
            self._pack_aff_term(term, pod, tk, ns, nall, sc, so, sv, t_idx)
        f[f"{prefix}_tk"] = tk
        f[f"{prefix}_ns"] = ns
        f[f"{prefix}_ns_all"] = nall
        f[f"{prefix}_sel_cols"] = sc
        f[f"{prefix}_sel_ops"] = so
        f[f"{prefix}_sel_vals"] = sv
        if weights is not None:
            w = np.zeros((A,), np.int32)
            w[: len(weights)] = weights
            f[f"{prefix}_weight"] = w

    def _table_template(self) -> np.ndarray:
        """Packed pods_i32 row of a term-free table pod (pod_valid=True,
        everything else at defaults): the fast-path base every no-affinity
        bound pod copies instead of re-deriving ~30 padded term arrays
        (the dominant host cost of committing constraint-free workloads)."""
        if self._table_i32_tmpl is None:
            tf32, ti32 = self.table_codec.alloc(1)
            pi = PodInfo(Pod())
            f: dict[str, np.ndarray] = {}
            f["pod_valid"] = np.bool_(True)
            f["pod_node"] = np.int32(0)
            f["pod_ns"] = np.int32(NONE)
            f["pod_uid"] = np.int32(NONE)
            f["pod_nominated"] = np.bool_(False)
            f["pt_label_vals"] = np.full((self.caps.pod_label_cols,), NONE,
                                         np.int32)
            self._pack_term_group([], None, pi.pod, "pod_anti", f)
            self._pack_term_group([], None, pi.pod, "pod_aff", f)
            self._pack_term_group([], [], pi.pod, "pod_paff", f)
            self._pack_term_group([], [], pi.pod, "pod_panti", f)
            self.table_codec.pack_into(tf32[0], ti32[0], f)
            self._table_i32_tmpl = ti32[0]
        return self._table_i32_tmpl

    @staticmethod
    def _slot_row_key(pi: PodInfo):
        """Content key of the pod-table row cache (_pack_pod_slot's terms
        arm): everything that arm reads of the pod apart from the three
        columns patched per slot (pod_node, pod_uid, pod_nominated), and
        nothing of the mirror's mutable state. That is the namespace (a
        term with no listed namespaces defaults to the owner's, and
        pod_ns), the labels (pt_label_vals, and the values a term's
        match_label_keys / mismatch_label_keys copy) and the four term
        lists: what _slot_holds compares. None where the row is not a
        function of that content: a term with a namespace_selector
        (_resolve_term_namespaces reads the namespace store and
        _known_pod_ns, and _repack_nssel_pods re-packs such slots when a
        namespace appears), the line _pod_row_key draws. Dicts and lists
        go in in their own order: two spellings of one content get two
        keys and two equal rows, an entry lost and never a wrong row."""
        groups = _weighted_terms(pi)
        for group in groups:
            for _w, t in group:
                if t.namespace_selector is not None:
                    return None
        meta = pi.pod.metadata
        return (meta.namespace, tuple(meta.labels.items()),
                *[tuple(_term_key(w, t) for w, t in g) for g in groups])

    def _pack_pod_slot(self, uid: str, pi: PodInfo, row: int, node_name: str,
                       nominated: bool = False) -> None:
        """Write ``uid``'s pod-table slot. A pod without terms copies the
        template row and patches its scalars and labels; a pod with terms
        copies the row kept under its content key (_slot_row_key) and
        patches pod_node, pod_uid and pod_nominated, or, on a miss, takes
        the full pack (_pack_term_slot) and leaves a read-only copy of
        the row under the key. A pod with no key (a namespace_selector)
        takes the full pack every time, counted as a bypass.

        INVARIANT, as _pack_batch_np's: a hit is byte for byte what the
        full pack would write for that pod now. A kept row is derived
        from the pod's content and from registries that only append for
        the Mirror's life (the interner, pod_label_col, topo_col), and a
        re-bucketed mirror is a FRESH Mirror with other row widths and an
        empty cache (adopt_hysteresis takes over the counts only). A hit
        skips the full pack's side effects (topo_col, _used_tks,
        pod_label_col, the interner): sound only because a miss on this
        same Mirror ran them and nothing un-registers. An edit that lets
        the full pack read mutable state for a keyed pod has to make
        _slot_row_key return None for it. The cache is bounded as
        _pod_rows is: cleared past POD_ROW_CACHE_ENTRIES, a clear counted.
        The clock pair stands around the whole terms arm, hit and miss
        alike: slot_terms_s is the seconds spent on slots of pods with
        terms, and slots_packed_terms counts every such slot (= hits +
        misses + bypass, slot_row_cache_stats)."""
        self._note_namespace(pi.pod.metadata.namespace)
        if not self._free_slots:
            raise CapacityError("pods", self.caps.pods + 1)
        slot = self._free_slots.pop()
        self.slots_hi = max(self.slots_hi, slot + 1)
        pod = pi.pod
        has_terms = bool(pi.required_anti_affinity_terms
                         or pi.required_affinity_terms
                         or pi.preferred_affinity_terms
                         or pi.preferred_anti_affinity_terms)
        if not has_terms:
            # template fast path: copy + patch the 5 scalar fields + labels
            dst = self.pods_i32[slot]
            dst[:] = self._table_template()
            o_node, o_ns, o_uid, o_nominated = self._slot_scalar_off
            intern = self.interner.intern
            dst[o_node] = row
            dst[o_ns] = intern(pod.metadata.namespace)
            dst[o_uid] = intern(pod.metadata.uid)
            dst[o_nominated] = 1 if nominated else 0
            if pod.metadata.labels:
                off, size = self._slot_labels_off
                dst[off:off + size] = self.pod_labels_row(pod.metadata.labels)
            self.slots_packed += 1
            self._dirty_slots.add(slot)
            self._pod_slot[uid] = slot
            self._node_pods[node_name][uid] = pod
            self._node_of_pod[uid] = node_name
            return
        t0 = time.perf_counter()
        dst = self.pods_i32[slot]
        key = self._slot_row_key(pi)
        kept = self._slot_rows.get(key) if key is not None else None
        if kept is not None:
            self.slot_row_hits += 1
            dst[:] = kept
            o_node, _o_ns, o_uid, o_nominated = self._slot_scalar_off
            dst[o_node] = row
            dst[o_uid] = self.interner.intern(pod.metadata.uid)
            dst[o_nominated] = 1 if nominated else 0
        else:
            self._pack_term_slot(dst, pi, row, nominated)
            if key is None:
                self.slot_row_bypass += 1
                if any(t.namespace_selector is not None
                       and (t.namespace_selector.match_labels
                            or t.namespace_selector.match_expressions)
                       for group in _weighted_terms(pi) for _w, t in group):
                    self._uids_with_nssel.add(uid)
            else:
                self.slot_row_misses += 1
                if len(self._slot_rows) > POD_ROW_CACHE_ENTRIES:
                    self._slot_rows.clear()
                    self.slot_row_clears += 1
                kept = dst.copy()
                kept.flags.writeable = False
                self._slot_rows[key] = kept
        self.slots_packed += 1
        self.slots_packed_terms += 1
        self._dirty_slots.add(slot)
        self._pod_slot[uid] = slot
        self._node_pods[node_name][uid] = pod
        self._node_of_pod[uid] = node_name
        self._uids_with_terms[uid] = pi
        self.slot_terms_s += time.perf_counter() - t0

    def _pack_term_slot(self, dst: np.ndarray, pi: PodInfo, row: int,
                        nominated: bool) -> None:
        """The full pack of a pod-table row with terms into ``dst``: every
        field derived from the pod and the registries (some thirty padded
        arrays through the codec)."""
        pod = pi.pod
        f: dict[str, np.ndarray] = {}
        f["pod_valid"] = np.bool_(True)
        f["pod_node"] = np.int32(row)
        f["pod_ns"] = np.int32(self._i(pod.metadata.namespace))
        f["pod_uid"] = np.int32(self._i(pod.metadata.uid))
        f["pod_nominated"] = np.bool_(nominated)
        f["pt_label_vals"] = self.pod_labels_row(pod.metadata.labels)
        self._pack_term_group(pi.required_anti_affinity_terms, None, pod,
                              "pod_anti", f)
        self._pack_term_group(pi.required_affinity_terms, None, pod,
                              "pod_aff", f)
        self._pack_term_group(
            [w.pod_affinity_term for w in pi.preferred_affinity_terms],
            [w.weight for w in pi.preferred_affinity_terms], pod, "pod_paff", f)
        self._pack_term_group(
            [w.pod_affinity_term for w in pi.preferred_anti_affinity_terms],
            [w.weight for w in pi.preferred_anti_affinity_terms], pod,
            "pod_panti", f)
        self.table_codec.pack_into(dst[:0].view(np.float32), dst, f)

    @staticmethod
    def _effective_exprs(sel, owner_labels: dict[str, str],
                         match_label_keys, mismatch_label_keys):
        """A LabelSelector as (key, operator, values) requirement tuples,
        with match/mismatchLabelKeys merged as In/NotIn requirements copying
        the owner pod's values (strategy.go
        applyMatchLabelKeysAndMismatchLabelKeys: keys absent from the owner's
        labels are skipped; nil selector skips the merge and matches nothing).
        Returns None for a nil selector."""
        if sel is None:
            return None
        exprs = selector_requirements(sel)
        for k in match_label_keys:
            if k in owner_labels:
                exprs.append((k, "In", [owner_labels[k]]))
        for k in mismatch_label_keys:
            if k in owner_labels:
                exprs.append((k, "NotIn", [owner_labels[k]]))
        return exprs

    def _pack_exprs(self, exprs, sel_c: np.ndarray, sel_o: np.ndarray,
                    sel_v: np.ndarray, t_idx: int) -> None:
        """Requirement tuples -> op-coded expression rows at term t_idx.
        exprs=None (nil selector, labels.Nothing()) packs a sentinel In
        expression no real value can satisfy."""
        caps = self.caps
        if exprs is None:
            sel_c[t_idx, 0] = 0
            sel_o[t_idx, 0] = F.op_id("In")
            sel_v[t_idx, 0, 0] = F.IMPOSSIBLE
            return
        if len(exprs) > caps.aff_sel:
            raise CapacityError("aff_sel", len(exprs))
        for i, (k, op, values) in enumerate(exprs):
            sel_c[t_idx, i] = self.pod_label_col(k)
            sel_o[t_idx, i] = F.op_id(op)
            if len(values) > caps.aff_sel_vals:
                raise CapacityError("aff_sel_vals", len(values))
            for j, v in enumerate(values):
                sel_v[t_idx, i, j] = self._i(v)

    def _note_namespace(self, ns_name: str) -> None:
        """Record a pod's namespace. A namespace first seen AFTER table pods
        with namespaceSelector terms were packed invalidates their unrolled
        lists (a DoesNotExist/NotIn selector can match the new namespace's
        empty/absent labels) — repack them."""
        if ns_name in self._known_pod_ns:
            return
        self._known_pod_ns.add(ns_name)
        if self._uids_with_nssel:
            self._repack_nssel_pods()

    def _repack_nssel_pods(self) -> None:
        for uid in list(self._uids_with_nssel):
            node_name = self._node_of_pod.get(uid)
            pod = self._node_pods.get(node_name or "", {}).get(uid)
            row = self._row_of.get(node_name or "")
            if node_name is None or pod is None or row is None:
                continue
            self._release_pod_slot(uid)
            # a "nominated:<uid>" overlay slot must keep its pod_nominated
            # flag through the repack, or the dual-pass rule
            # (RunFilterPluginsWithNominatedPods) breaks for it
            self._pack_pod_slot(uid, PodInfo(pod), row, node_name,
                                nominated=uid in self._nominated_uids)

    def _resolve_term_namespaces(self, term: PodAffinityTerm, owner: Pod
                                 ) -> tuple[list[str], bool]:
        """(explicit namespace list, all-namespaces flag) for a term.

        The pack-time analog of the reference's
        mergeAffinityTermNamespacesIfNotEmpty (interpodaffinity/plugin.go:123):
        a non-empty namespaceSelector unrolls into explicit names over the
        namespace store PLUS every namespace a packed pod lives in (labels
        default to {} when no Namespace object exists — the reference's nil
        nsLabels, so DoesNotExist/NotIn selectors match them). If the
        selector matches every known namespace, the all-namespaces flag is
        packed instead of the list — exact under the repack-on-new-namespace
        rule (_note_namespace) and immune to aff_ns capacity blowup for
        broad selectors. The EMPTY selector ({}) always matches everything;
        nil selector + no explicit namespaces defaults to the owner's
        namespace (getNamespacesFromPodAffinityTerm, types.go:749)."""
        explicit = list(term.namespaces)
        nssel = term.namespace_selector
        if nssel is not None:
            if not nssel.match_labels and not nssel.match_expressions:
                return sorted(set(explicit)), True
            universe = set(self._namespaces) | self._known_pod_ns
            matched = [name for name in universe
                       if label_selector_matches(
                           nssel, self._namespaces.get(name, {}))]
            if universe and len(matched) == len(universe):
                return sorted(set(explicit)), True
            explicit.extend(matched)
        elif not explicit:
            explicit = [owner.metadata.namespace]
        return sorted(set(explicit)), False

    def _pack_aff_term(self, term: PodAffinityTerm, pod: Pod,
                       tk: np.ndarray, ns: np.ndarray, ns_all: np.ndarray,
                       sel_c: np.ndarray, sel_o: np.ndarray,
                       sel_v: np.ndarray, t_idx: int) -> None:
        """Shared (anti)affinity term encoding: topology key -> tk index,
        namespaces resolved/unrolled, selector -> op-coded expressions."""
        caps = self.caps
        tk[t_idx] = self.topo_col(term.topology_key)
        self._used_tks.add(int(tk[t_idx]))
        namespaces, all_flag = self._resolve_term_namespaces(term, pod)
        if len(namespaces) > caps.aff_ns:
            raise CapacityError("aff_ns", len(namespaces))
        for i, n in enumerate(namespaces):
            ns[t_idx, i] = self._i(n)
        ns_all[t_idx] = all_flag
        exprs = self._effective_exprs(term.label_selector, pod.metadata.labels,
                                      term.match_label_keys,
                                      term.mismatch_label_keys)
        self._pack_exprs(exprs, sel_c, sel_o, sel_v, t_idx)

    def term_matches_pod(self, term: PodAffinityTerm, owner: Pod,
                         target: Pod) -> bool:
        """Host oracle: does `term` (owned by `owner`) select `target`?
        (AffinityTerm.Matches, framework/types.go:545) — full LabelSelector
        + namespaceSelector + match/mismatchLabelKeys semantics."""
        namespaces, ns_all = self._resolve_term_namespaces(term, owner)
        if not ns_all and target.metadata.namespace not in namespaces:
            return False
        exprs = self._effective_exprs(term.label_selector,
                                      owner.metadata.labels,
                                      term.match_label_keys,
                                      term.mismatch_label_keys)
        return requirements_match(exprs, target.metadata.labels)

    def _release_pod_slot(self, uid: str) -> None:
        slot = self._pod_slot.pop(uid, None)
        if slot is None:
            return
        self.pods_i32[slot] = 0  # pod_valid -> False, rest zeroed
        self._free_slots.append(slot)
        if slot + 1 == self.slots_hi:
            live = np.flatnonzero(self.pods_i32[:slot, self._slot_valid_off])
            self.slots_hi = int(live[-1]) + 1 if live.size else 0
        self._dirty_slots.add(slot)
        self.slots_released += 1
        self._uids_with_terms.pop(uid, None)
        self._uids_with_nssel.discard(uid)
        node = self._node_of_pod.pop(uid, None)
        if node is not None:
            self._node_pods.get(node, {}).pop(uid, None)

    def _invalidate_row(self, name: str) -> None:
        row = self._row_of.pop(name)
        self._row_gen.pop(name, None)
        self._row_names[row] = None
        self.node_f32[row] = 0.0
        self.node_i32[row] = 0  # node_valid -> False
        self._dirty_rows.add(row)
        self._row_node_labels.pop(row, None)
        self._row_node_obj.pop(row, None)
        self._nominated_req_of_row.pop(row, None)
        self._rows_with_taints.discard(row)
        self._rows_with_ports.discard(row)
        self._rows_with_images.discard(row)
        for uid in list(self._node_pods.get(name, {})):
            self._release_pod_slot(uid)
        self._node_pods.pop(name, None)
        self._free_rows.append(row)

    def patch_node(self, name: str, info: NodeInfo | None
                   ) -> tuple[int, np.ndarray, np.ndarray] | None:
        """Repack ONE node's row from its LIVE cache aggregate, outside the
        snapshot sync — the host half of chain-surviving churn. The mirror
        row moves exactly as a full sync would have moved it (same pack
        helpers, pod-table reconcile included) and ``_row_gen`` records the
        live generation so a later full sync skips the already-consistent
        row. Returns ``(row, free, nzr)`` for the caller to scatter into
        the device-resident chain (zeros for a removed node — a zeroed
        free row fits nothing, matching node_valid=False), or None when
        the node was never mirrored (nothing to patch). Raises
        CapacityError when the node table is full or the node outgrows a
        pack capacity — the caller falls back to whole-chain invalidation
        and the normal resync/_grow ladder."""
        row = self._row_of.get(name)
        if info is None or info.node is None:
            if row is None:
                return None
            self._invalidate_row(name)
            self._free_fp = None
            self.rows_synced += 1
            return (row, np.zeros((self.caps.res_cols,), np.float32),
                    np.zeros((2,), np.float32))
        if row is None:
            if not self._free_rows:
                raise CapacityError("nodes", len(self._row_of) + 1)
            row = self._free_rows.pop()
            self._row_of[name] = row
            self._row_names[row] = name
        if self._row_node_obj.get(row) is not info.node:
            self._pack_node_row(row, info)
        self._update_rows_resources([row], [info])
        self._free_fp = None
        self.rows_synced += 1
        # the two fields as they have just been written
        f_off = self.node_codec._f32_off
        off, size = f_off["free"]
        free = self.node_f32[row, off:off + size].copy()
        off, size = f_off["nonzero_requested"]
        return (row, free, self.node_f32[row, off:off + size].copy())

    # ------------- sync -------------

    def sync(self, snapshot: Snapshot) -> int:
        """Incrementally repack rows for nodes whose generation advanced.
        Returns the number of rows repacked."""
        # O(1) no-op when the snapshot hasn't changed since the last sync of
        # this same snapshot object (Snapshot.version is bumped by every
        # mutating Cache.update_snapshot)
        prev = self._last_sync
        if prev == (id(snapshot), snapshot.version):
            return 0
        # stands again only once every row is through: a sync that raised
        # (CapacityError) must not leave the next one a delta to trust
        self._last_sync = None
        # namespace set changed: refresh the store and repack every table pod
        # whose terms carry a namespaceSelector (their unrolled ns lists are
        # stale) — the incremental analog of the reference resolving
        # namespaceSelectors freshly each cycle
        if snapshot.ns_generation != self._ns_gen:
            self._ns_gen = snapshot.ns_generation
            self._namespaces = snapshot.namespaces
            self._repack_nssel_pods()
        repacked = 0
        changed = snapshot.changed_nodes
        if changed is not None and prev == (id(snapshot),
                                            snapshot.version - 1):
            # exactly one refresh since this mirror's last sync of this
            # snapshot, over an unchanged node set: the nodes it re-cloned
            # are the only ones whose generation can have advanced
            infos = [snapshot.node_info_map[name] for name in changed]
        else:
            infos = snapshot.node_info_list
            live = {info.name for info in infos}
            # removals first so a same-sync node swap can reuse the freed row
            for name in list(self._row_of):
                if name not in live:
                    self._invalidate_row(name)
                    repacked += 1
        rows: list[int] = []
        moved: list[NodeInfo] = []
        for info in infos:
            name = info.name
            row = self._row_of.get(name)
            if row is None:
                if not self._free_rows:
                    raise CapacityError("nodes", len(self._row_of) + 1)
                row = self._free_rows.pop()
                self._row_of[name] = row
                self._row_names[row] = name
            if self._row_gen.get(name) != info.generation:
                # a pod-only change (the Node object stands) takes the
                # resources pass alone
                if self._row_node_obj.get(row) is not info.node:
                    self._pack_node_row(row, info)
                rows.append(row)
                moved.append(info)
        self._update_rows_resources(rows, moved)
        repacked += len(rows)
        self._last_sync = (id(snapshot), snapshot.version)
        self.rows_synced += repacked
        return repacked

    def sync_stats(self) -> dict:
        """What sync and patch_node wrote, for /debug/trace and the
        registry: node rows repacked; pod-table slots packed (of them
        ``slots_packed_terms`` for pods with affinity terms, the terms
        arm: slot_row_cache_stats parts them), released, and kept (the
        pod's object was replaced by one of equal content: re-pointed,
        nothing written). A backlog of
        pods that bind once packs one slot a pod and keeps about one a
        pod."""
        return {"rows_synced": self.rows_synced,
                "slots_packed": self.slots_packed,
                "slots_packed_terms": self.slots_packed_terms,
                "slots_kept": self.slots_kept,
                "slots_released": self.slots_released}

    def _push(self, key: str, host_buf: np.ndarray, dirty: set[int],
              full: bool) -> None:
        """Refresh one device buffer: full upload on first use / bulk change,
        otherwise a row-scatter of only the dirty rows into the resident
        (donated) HBM buffer — the device half of the incremental
        UpdateSnapshot (a few hundred KB per cycle instead of the whole
        multi-MB mirror over the host<->TPU link)."""
        dev = self._dev.get(key)
        limit = max(64, host_buf.shape[0] // 4)
        if dev is None or full or len(dirty) > limit:
            sh = self._dev_sharding.get(key)
            self._dev[key] = (jnp.asarray(host_buf) if sh is None
                              else jax.device_put(host_buf, sh))
            if dev is None:
                self._warm_scatter(key, host_buf, limit)
            return
        if not dirty:
            return
        idx = sorted(dirty)
        k = 1
        while k < len(idx):
            k *= 2
        # pad with duplicates of the last row: same index + same data is an
        # idempotent write, and keeps the scatter shape in pow2 buckets so
        # XLA compiles one kernel per bucket, not per row-count
        idx = idx + [idx[-1]] * (k - len(idx))
        arr = np.asarray(idx, np.int32)
        scatter = self._scatter_fns.get(key, _scatter_rows_jit)
        self._dev[key] = scatter(dev, jnp.asarray(arr),
                                 jnp.asarray(host_buf[arr]))

    def _warm_scatter(self, key: str, host_buf: np.ndarray,
                      limit: int) -> None:
        """Compile (or load from the compile cache) the row scatter of
        one freshly uploaded buffer at every pow2 bucket a launch's dirty
        set can take: up to SCATTER_WARM_ROWS, or to `limit` where that
        is less (a dirty set over `limit` rows is a full upload). Each is
        a write that changes nothing: row 0 onto itself. The launch cache
        is blind to these programs, so a bucket first met mid-drain was a
        compile nobody counted: under steady arrivals every launch
        dirties 10 or 15 rows, and the one launch that a pause made carry
        20 met the 32-row bucket inside the measured window (PERF.md 7,
        fault 3)."""
        scatter = self._scatter_fns.get(key, _scatter_rows_jit)
        limit = min(limit, SCATTER_WARM_ROWS)
        k = 1
        while True:
            self._dev[key] = scatter(
                self._dev[key], jnp.asarray(np.zeros(k, np.int32)),
                jnp.asarray(np.broadcast_to(host_buf[0],
                                            (k, host_buf.shape[1]))))
            if k >= limit:
                return
            k *= 2

    def to_blobs(self) -> ClusterBlobs:
        """Refresh the device-resident mirror (incremental row scatter or
        full upload) and return the ClusterBlobs handles."""
        full_node = self._dirty_full["node"]
        self._push("node_f32", self.node_f32, self._dirty_rows, full_node)
        self._push("node_i32", self.node_i32, self._dirty_rows, full_node)
        self._push("pods_i32", self.pods_i32, self._dirty_slots,
                   self._dirty_full["pods"])
        self._dirty_full = {"node": False, "pods": False}
        self._dirty_rows.clear()
        self._dirty_slots.clear()
        return ClusterBlobs(node_f32=self._dev["node_f32"],
                            node_i32=self._dev["node_i32"],
                            pods_i32=self._dev["pods_i32"])

    def to_device(self) -> ClusterTensors:
        """ClusterTensors view (single jitted unpack dispatch) — test/tooling
        convenience; the scheduling pipeline unpacks blobs inside its own jit."""
        return _unpack_cluster_jit(self.to_blobs(), self.caps)

    def _hysteresis(self, hw_attr: str, low_attr: str, need: int) -> int:
        """Sticky pow2 bucket: expand to ``need`` immediately; shrink by
        ONE halving only after BUCKET_DECAY_LAUNCHES consecutive launches
        whose demand fit in half the bucket. The compile-count analog of
        TCP slow decrease — an oscillating demand signal settles on the
        high-water program instead of recompiling every swing."""
        hw = getattr(self, hw_attr)
        if need >= hw:
            setattr(self, hw_attr, need)
            setattr(self, low_attr, 0)
            return need
        if need <= hw // 2:
            low = getattr(self, low_attr) + 1
            if low >= BUCKET_DECAY_LAUNCHES:
                hw = max(need, hw // 2)
                setattr(self, hw_attr, hw)
                setattr(self, low_attr, 0)
            else:
                setattr(self, low_attr, low)
        else:
            setattr(self, low_attr, 0)
        return hw

    def adopt_hysteresis(self, prev: "Mirror") -> None:
        """Carry the sticky domain-bucket high-water mark across a
        capacity re-bucket (scheduler._grow builds a FRESH mirror):
        without this a rebuilt mirror re-derives a smaller bucket from
        its still-empty domain tables and the next churn swing pays the
        compile again. The two row caches' counts, pack_full_s and
        sync_stats' come along too: they are totals the registry and the
        flight recorder read by delta, and the caches themselves start
        empty."""
        self._d_hw = prev._d_hw
        self.row_cache_hits = prev.row_cache_hits
        self.row_cache_misses = prev.row_cache_misses
        self.row_cache_bypass = prev.row_cache_bypass
        self.row_cache_clears = prev.row_cache_clears
        self.pack_full_s = prev.pack_full_s
        self.slot_row_hits = prev.slot_row_hits
        self.slot_row_misses = prev.slot_row_misses
        self.slot_row_bypass = prev.slot_row_bypass
        self.slot_row_clears = prev.slot_row_clears
        self.rows_synced = prev.rows_synced
        self.slots_packed = prev.slots_packed
        self.slots_packed_terms = prev.slots_packed_terms
        self.slot_terms_s = prev.slot_terms_s
        self.slots_kept = prev.slots_kept
        self.slots_released = prev.slots_released

    def launch_d_cap(self, enable_topology: bool) -> int:
        """The static d_cap for one launch: the domain bucket when the
        launch runs topology kernels, else a CANONICAL 0 — a no-topology
        program never reads domains, and keying it on the domain count
        would make a scaled-down warmup (fewer nodes -> smaller bucket)
        compile a DIFFERENT program than the full-scale run, paying a
        fresh multi-second XLA compile on the first measured batch."""
        if not enable_topology:
            return 0
        return min(self._hysteresis("_d_hw", "_d_low",
                                    self.domain_bucket()),
                   self.caps.domain_cap)

    def domain_bucket(self) -> int:
        """Static scatter-space size for the next launch: power-of-two over
        the max domain count among topology keys any packed term/constraint
        references (>= 8 to limit recompiles). The device analog of sizing
        the reference's topologyPair hash maps to what the workload touches."""
        need = max((len(self._tk_domains[tk]) for tk in self._used_tks),
                   default=1)
        d = 8
        while d < need:
            d *= 2
        return min(d, self.caps.domain_cap)

    def gang_pack_domain(self) -> tuple[int, int]:
        """(tk, d_bucket) for the gang packer's topology-close fill
        order: the ZONE topology key's column and a pow2 domain bucket
        (+1 slot for the pseudo-domain of unlabeled nodes) when any
        node carries a zone label; (-1, 8) otherwise — the packer then
        fills capacity-greedy with every node in one shared domain."""
        from kubernetes_tpu.api.objects import LABEL_ZONE

        tk = self._topo_col.get(LABEL_ZONE)
        if tk is None or not self._tk_domains[tk]:
            return -1, 8
        need = len(self._tk_domains[tk]) + 1
        d = 8
        while d < need:
            d *= 2
        return tk, min(d, self.caps.domain_cap + 1)

    @staticmethod
    def batch_topology_soft_only(pods: list[Pod]) -> bool:
        """True when no batch pod carries topology work that CONSTRAINS:
        required (anti)affinity terms or DoNotSchedule spread. A soft-only
        batch's topology terms are pure Score work, which the parallel
        auction can fuse (preferred weights + ScheduleAnyway spread) — the
        preferred-band workloads stop paying the serial commit scan."""
        for p in pods:
            a = p.spec.affinity
            if a is not None:
                pa, pan = a.pod_affinity, a.pod_anti_affinity
                if pa is not None and pa.required:
                    return False
                if pan is not None and pan.required:
                    return False
            for t in p.spec.topology_spread_constraints:
                if t.when_unsatisfiable == "DoNotSchedule":
                    return False
        return True

    @staticmethod
    def batch_has_topology(pods: list[Pod]) -> bool:
        """Host-side PreFilter-Skip: does any pod in the batch carry
        (anti)affinity terms or topology spread constraints?"""
        for p in pods:
            a = p.spec.affinity
            if a is not None and (a.pod_affinity is not None
                                  or a.pod_anti_affinity is not None):
                return True
            if p.spec.topology_spread_constraints:
                return True
        return False

    def table_has_topology(self) -> bool:
        """True if any scheduled pod in the table carries (anti)affinity
        terms — those reject (existing anti-affinity) or score (existing
        required/preferred terms) even a constraint-free incoming batch."""
        return bool(self._uids_with_terms)

    def set_nominated(self, by_node: dict[str, list[Pod]]) -> None:
        """Refresh the nominated-pod overlay: pending preemptors with a
        NominatedNodeName occupy pod-table slots on their nominated row
        (anti-affinity counts them; required-affinity presence and scoring
        exclude them via pod_nominated — the device analog of the dual pass
        in RunFilterPluginsWithNominatedPods, runtime/framework.go:989) and
        reserve their resource requests in the node row's nominated_req."""
        for uid in list(self._nominated_uids):
            self._release_pod_slot(uid)
        self._nominated_uids.clear()
        off, size = self.node_codec._f32_off["nominated_req"]
        for row in list(self._nominated_req_of_row):
            self.node_f32[row, off:off + size] = 0.0
            self._dirty_rows.add(row)
        self._nominated_req_of_row.clear()
        for node_name, pods in by_node.items():
            row = self._row_of.get(node_name)
            if row is None or not pods:
                continue
            req_sum = np.zeros((self.caps.res_cols,), np.float32)
            for pod in pods:
                pi = PodInfo(pod)
                key = "nominated:" + pod.metadata.uid
                self._pack_pod_slot(key, pi, row, node_name, nominated=True)
                self._nominated_uids.add(key)
                req_sum += self._res_row(pi.request)
                req_sum[F.COL_PODS] += 1.0
            self._nominated_req_of_row[row] = req_sum
            self.node_f32[row, off:off + size] = req_sum
            self._dirty_rows.add(row)

    # ------------- pod packing -------------

    def pack_pod(self, pod: Pod, active_only: bool = False
                 ) -> dict[str, np.ndarray]:
        """Pod -> PodFeatures field dict (numpy).

        With ``active_only`` the dict contains ONLY the fields this pod
        actually uses; absent fields take their defaults from the packed
        empty-pod template (_pod_template) — the fast path that keeps
        per-pod pack cost proportional to the pod's features, not the
        schema size."""
        caps = self.caps
        pi = PodInfo(pod)
        out: dict[str, np.ndarray] = {}
        out["req"] = self._res_row(pi.request)
        out["req"][F.COL_PODS] = 1.0  # each pod consumes one pod slot
        out["nonzero_req"] = np.asarray(
            [pi.non_zero_request.milli_cpu, pi.non_zero_request.memory / MI],
            np.float32)
        out["num_containers"] = np.float32(
            len(pod.spec.containers) + len(pod.spec.init_containers))
        out["priority"] = np.int32(pod.priority())
        out["ns"] = np.int32(self._i(pod.metadata.namespace))
        out["name_id"] = np.int32(self._i(pod.metadata.name))
        out["uid_id"] = np.int32(self._i(pod.metadata.uid))
        # own-reservation add-back is only sound if this pod's reservation is
        # actually inside nominated_req (set_nominated ran with it); a stale
        # status.nominatedNodeName must NOT inflate free
        nom = pod.status.nominated_node_name
        reserved = ("nominated:" + pod.metadata.uid) in self._nominated_uids
        out["nominated_row"] = np.int32(
            self._row_of.get(nom, NONE) if nom and reserved else NONE)
        if pod.metadata.labels or not active_only:
            out["plabel_vals"] = self.pod_labels_row(pod.metadata.labels)
        if pod.spec.node_selector or not active_only:
            if len(pod.spec.node_selector) > caps.pod_labels:
                raise CapacityError("pod_labels", len(pod.spec.node_selector))
            ns_cols = np.full((caps.pod_labels,), NONE, np.int32)
            ns_vals = np.full((caps.pod_labels,), NONE, np.int32)
            for idx, (k, v) in enumerate(pod.spec.node_selector.items()):
                ns_cols[idx] = self.label_col_lookup(k)
                ns_vals[idx] = self._i(v)
            out["nodesel_cols"], out["nodesel_vals"] = ns_cols, ns_vals
        aff = pod.spec.affinity
        if (aff is not None and aff.node_affinity is not None) \
                or not active_only:
            pin = self._node_affinity_pin(
                aff.node_affinity if aff is not None else None)
            if pin is not None and active_only:
                # daemonset shape: the whole required clause is one
                # metadata.name In [v] matchFields term — pack the pin id
                # only; the selector/preferred arrays keep their template
                # defaults (and a pin-only batch never transfers them)
                out["aff_pin"] = np.int32(self._i(pin))
            else:
                self._pack_node_affinity(pod, out)
        if pod.spec.tolerations or not active_only:
            self._pack_tolerations(pod, out)
        if any(p.host_port > 0 for c in pod.spec.containers
               for p in c.ports) or not active_only:
            self._pack_host_ports(pod, out)
        if (aff is not None and (aff.pod_affinity is not None
                                 or aff.pod_anti_affinity is not None)) \
                or not active_only:
            self._pack_pod_affinity(pod, pi, out)
        if pod.spec.topology_spread_constraints or not active_only:
            self._pack_spread(pod, out)
        imgs = [c.image for c in pod.spec.containers if c.image]
        if imgs or not active_only:
            out["image_ids"] = np.full((caps.pod_images,), NONE, np.int32)
            for idx, img in enumerate(imgs[: caps.pod_images]):
                out["image_ids"][idx] = self._i(img)
        if pod.spec.node_name or not active_only:
            out["node_name_id"] = np.int32(
                self._i(pod.spec.node_name) if pod.spec.node_name else NONE)
        out["valid"] = np.bool_(True)
        return out

    def _pod_template(self) -> tuple[np.ndarray, np.ndarray]:
        """Packed blob rows of an empty pod: the defaults every active_only
        pack starts from."""
        if self._pod_tmpl is None:
            f32, i32 = self.pod_codec.alloc()
            self.pod_codec.pack_into(f32, i32, self.pack_pod(Pod()))
            self._pod_tmpl = (f32, i32)
        return self._pod_tmpl

    @staticmethod
    def _node_affinity_pin(na) -> str | None:
        """The daemonset-controller pattern: required node affinity whose
        ENTIRE clause is one term holding exactly one matchFields
        metadata.name In [single value] expression, with no preferred
        terms riding along. Returns the pinned node name (semantically a
        NodeName pin under the NodeAffinity plugin), else None."""
        if na is None or na.preferred or na.required is None:
            return None
        terms = na.required.node_selector_terms
        if len(terms) != 1:
            return None
        t = terms[0]
        if t.match_expressions or len(t.match_fields) != 1:
            return None
        f = t.match_fields[0]
        if f.key != "metadata.name" or f.operator != "In" \
                or len(f.values) != 1:
            return None
        return f.values[0]

    def _pack_node_affinity(self, pod: Pod, out: dict[str, np.ndarray]) -> None:
        caps = self.caps
        T, E, V = caps.sel_terms, caps.sel_exprs, caps.sel_vals
        out["aff_pin"] = np.int32(NONE)
        out["sel_term_valid"] = np.zeros((T,), bool)
        out["sel_col"] = np.full((T, E), NONE, np.int32)
        out["sel_op"] = np.full((T, E), NONE, np.int32)
        out["sel_is_field"] = np.zeros((T, E), bool)
        out["sel_vals"] = np.full((T, E, V), NONE, np.int32)
        out["sel_num"] = np.full((T, E), np.nan, np.float32)
        aff = pod.spec.affinity
        required = (aff.node_affinity.required
                    if aff and aff.node_affinity else None)
        if required is not None:
            terms = required.node_selector_terms
            if len(terms) > T:
                raise CapacityError("sel_terms", len(terms))
            for ti, term in enumerate(terms):
                out["sel_term_valid"][ti] = True
                self._pack_term_exprs(term, out["sel_col"], out["sel_op"],
                                      out["sel_is_field"], out["sel_vals"],
                                      out["sel_num"], ti)
        # preferred
        PW = caps.pref_terms
        out["pref_weight"] = np.zeros((PW,), np.int32)
        out["pref_col"] = np.full((PW, E), NONE, np.int32)
        out["pref_op"] = np.full((PW, E), NONE, np.int32)
        out["pref_is_field"] = np.zeros((PW, E), bool)
        out["pref_vals"] = np.full((PW, E, V), NONE, np.int32)
        out["pref_num"] = np.full((PW, E), np.nan, np.float32)
        preferred = (aff.node_affinity.preferred
                     if aff and aff.node_affinity else [])
        if len(preferred) > PW:
            raise CapacityError("pref_terms", len(preferred))
        for ti, wterm in enumerate(preferred):
            out["pref_weight"][ti] = wterm.weight
            self._pack_term_exprs(wterm.preference, out["pref_col"],
                                  out["pref_op"], out["pref_is_field"],
                                  out["pref_vals"], out["pref_num"], ti)

    def _pack_term_exprs(self, term, keys, ops, is_field, vals, nums, ti) -> None:
        caps = self.caps
        exprs = ([(e, False) for e in term.match_expressions]
                 + [(e, True) for e in term.match_fields])
        if len(exprs) > caps.sel_exprs:
            raise CapacityError("sel_exprs", len(exprs))
        for ei, (e, fld) in enumerate(exprs):
            # matchExpressions reference a label COLUMN (NONE if no node
            # carries the key); matchFields (metadata.name) keep col NONE
            keys[ti, ei] = NONE if fld else self.label_col_lookup(e.key)
            ops[ti, ei] = F.op_id(e.operator)
            is_field[ti, ei] = fld
            if len(e.values) > caps.sel_vals:
                raise CapacityError("sel_vals", len(e.values))
            for vi, v in enumerate(e.values):
                vals[ti, ei, vi] = self._i(v)
            if e.operator in ("Gt", "Lt") and len(e.values) == 1:
                try:
                    nums[ti, ei] = float(int(e.values[0]))
                except ValueError:
                    nums[ti, ei] = np.nan

    def _pack_tolerations(self, pod: Pod, out: dict[str, np.ndarray]) -> None:
        TO = self.caps.tolerations
        tols = pod.spec.tolerations
        if len(tols) > TO:
            raise CapacityError("tolerations", len(tols))
        out["tol_key"] = np.full((TO,), NONE, np.int32)
        out["tol_op"] = np.full((TO,), NONE, np.int32)
        out["tol_val"] = np.full((TO,), NONE, np.int32)
        out["tol_effect"] = np.full((TO,), NONE, np.int32)
        out["tol_valid"] = np.zeros((TO,), bool)
        for i, t in enumerate(tols):
            out["tol_valid"][i] = True
            out["tol_key"][i] = self._i(t.key) if t.key else NONE
            out["tol_op"][i] = (F.TOL_EXISTS if t.operator == "Exists"
                                else F.TOL_EQUAL)
            out["tol_val"][i] = self._i(t.value)
            out["tol_effect"][i] = (F.effect_id(t.effect) if t.effect else NONE)

    def _pack_host_ports(self, pod: Pod, out: dict[str, np.ndarray]) -> None:
        HP = self.caps.pod_ports
        ports = [(p.host_ip, p.protocol, p.host_port)
                 for c in pod.spec.containers for p in c.ports if p.host_port > 0]
        if len(ports) > HP:
            raise CapacityError("pod_ports", len(ports))
        out["hp_ip"] = np.full((HP,), NONE, np.int32)
        out["hp_proto"] = np.full((HP,), NONE, np.int32)
        out["hp_port"] = np.full((HP,), NONE, np.int32)
        for i, (ip, proto, port) in enumerate(ports):
            out["hp_ip"][i] = self._i(ip or "0.0.0.0")
            out["hp_proto"][i] = self._i(proto or "TCP")
            out["hp_port"][i] = port

    def _pack_pod_affinity(self, pod: Pod, pi: PodInfo,
                           out: dict[str, np.ndarray]) -> None:
        self._pack_term_group(pi.required_affinity_terms, None, pod, "aff", out)
        self._pack_term_group(pi.required_anti_affinity_terms, None, pod,
                              "anti", out)
        self._pack_term_group(
            [w.pod_affinity_term for w in pi.preferred_affinity_terms],
            [w.weight for w in pi.preferred_affinity_terms], pod, "paff", out)
        self._pack_term_group(
            [w.pod_affinity_term for w in pi.preferred_anti_affinity_terms],
            [w.weight for w in pi.preferred_anti_affinity_terms], pod,
            "panti", out)
        # first-pod-of-group rule (satisfyPodAffinity, filtering.go): does the
        # pod match ALL of its own required affinity terms?
        out["aff_self_match"] = np.bool_(
            bool(pi.required_affinity_terms)
            and all(self.term_matches_pod(t, pod, pod)
                    for t in pi.required_affinity_terms))

    def _pack_spread(self, pod: Pod, out: dict[str, np.ndarray]) -> None:
        caps = self.caps
        C, MS = caps.spread_constraints, caps.aff_sel
        out["tsc_tk"] = np.full((C,), NONE, np.int32)
        out["tsc_max_skew"] = np.zeros((C,), np.int32)
        out["tsc_hard"] = np.zeros((C,), bool)
        out["tsc_min_domains"] = np.zeros((C,), np.int32)
        out["tsc_sel_cols"] = np.full((C, MS), NONE, np.int32)
        out["tsc_sel_ops"] = np.full((C, MS), NONE, np.int32)
        out["tsc_sel_vals"] = np.full((C, MS, self.caps.aff_sel_vals), NONE,
                                      np.int32)
        out["tsc_honor_affinity"] = np.ones((C,), bool)
        out["tsc_honor_taints"] = np.zeros((C,), bool)
        tscs = pod.spec.topology_spread_constraints
        if len(tscs) > C:
            raise CapacityError("spread_constraints", len(tscs))
        for i, t in enumerate(tscs):
            out["tsc_tk"][i] = self.topo_col(t.topology_key)
            self._used_tks.add(int(out["tsc_tk"][i]))
            out["tsc_max_skew"][i] = t.max_skew
            out["tsc_hard"][i] = t.when_unsatisfiable == "DoNotSchedule"
            out["tsc_min_domains"][i] = t.min_domains or 0
            # nil selector = labels.Nothing(): matches no pod, selfMatchNum 0
            # (filtering.go:311); matchLabelKeys merge as In requirements
            # (strategy.go applyMatchLabelKeys — spread has no mismatch keys)
            exprs = self._effective_exprs(t.label_selector,
                                          pod.metadata.labels,
                                          t.match_label_keys, [])
            self._pack_exprs(exprs, out["tsc_sel_cols"], out["tsc_sel_ops"],
                             out["tsc_sel_vals"], i)
            out["tsc_honor_affinity"][i] = t.node_affinity_policy == "Honor"
            out["tsc_honor_taints"][i] = t.node_taints_policy == "Honor"

    def pack_batch_blobs(self, pods: list[Pod], batch_size: int,
                         fields: tuple[str, ...] | None = None) -> PodBlobs:
        """Pack pods into a [B]-batched PodBlobs (2 device transfers), padding
        to batch_size with invalid rows. With ``fields`` the blobs carry only
        that subset (BlobCodec.subset_layout) — the launch splices the rest
        from the device-resident template (pod_template_blobs), keeping the
        per-batch host->device transfer proportional to what the workload
        uses instead of the full schema."""
        if fields is None:
            self._batch_prepass(pods, batch_size)
            f32, i32 = self.pod_codec.alloc(batch_size)
            tf32, ti32 = self._pod_template()
            f32[: len(pods)] = tf32
            i32[: len(pods)] = ti32
            for b, pod in enumerate(pods):
                self.pod_codec.pack_into(f32[b], i32[b],
                                         self.pack_pod(pod, active_only=True))
            # padding rows stay zeroed => valid False
            return PodBlobs(f32=jnp.asarray(f32), i32=jnp.asarray(i32))
        f32, i32 = self._pack_batch_np(pods, batch_size, fields)
        return PodBlobs(f32=jnp.asarray(f32), i32=jnp.asarray(i32))

    def _batch_prepass(self, pods: list[Pod], batch_size: int) -> None:
        """Validate + register every batch pod's label keys so a term packed
        for pod i can reference a column pod j>i carries, and note every
        batch namespace so term nsSelector unrolls see all of them."""
        if not pods:
            raise ValueError("empty batch")
        if len(pods) > batch_size:
            raise ValueError(f"{len(pods)} pods exceed batch_size {batch_size}")
        for pod in pods:
            self._note_namespace(pod.metadata.namespace)
            for k in pod.metadata.labels:
                self.pod_label_col(k)

    @staticmethod
    def _pod_row_key(pod: Pod):
        """Content key of the packed-row cache: every input that
        pack_pod(pod, active_only=True) reads except name and uid (those
        two columns are patched per pod), or None where the row is not a
        function of the pod's content alone and the pod takes the slow
        path every time (counted as a bypass):

        * status.nominated_node_name: nominated_row reads _nominated_uids
          and _row_of, which set_nominated rewrites every cycle;
        * node_selector and node affinity: label_col_lookup answers NONE
          until a node brings the key, so the same pod packs differently
          after a sync (a metadata.name pin is a key of its own a pod, so
          nothing is lost by leaving it out);
        * an (anti-)affinity term with a namespace_selector:
          _resolve_term_namespaces reads the namespace store and
          _known_pod_ns;
        * spec.node_name: such a pod is not for scheduling.

        Dicts and lists go into the key in their own order, unsorted: two
        pods that spell one content in two orders get two keys and two
        equal rows, which costs an entry and never a wrong row. Deployment
        -shaped batches are thousands of pods identical up to name and uid,
        and re-deriving the whole row a pod was the dominant host pack
        cost; the key has to stay a few microseconds."""
        s = pod.spec
        if s.node_name or s.node_selector or pod.status.nominated_node_name:
            return None
        aff = s.affinity
        aff_key = None
        if aff is not None:
            if aff.node_affinity is not None:
                return None
            groups = []
            for grp in (aff.pod_affinity, aff.pod_anti_affinity):
                if grp is None:
                    groups.append(None)
                    continue
                terms = [(0, t) for t in grp.required]
                terms += [(w.weight, w.pod_affinity_term)
                          for w in grp.preferred]
                for _w, t in terms:
                    if t.namespace_selector is not None:
                        return None
                groups.append((len(grp.required), tuple(
                    _term_key(w, t) for w, t in terms)))
            aff_key = tuple(groups)
        meta = pod.metadata
        return (
            meta.namespace, s.priority,
            tuple(meta.labels.items()) if meta.labels else (),
            tuple((c.image, tuple(c.resources.requests.items()),
                   tuple((p.host_ip, p.protocol, p.host_port)
                         for p in c.ports) if c.ports else ())
                  for c in s.containers),
            tuple((c.restart_policy, tuple(c.resources.requests.items()))
                  for c in s.init_containers) if s.init_containers else (),
            tuple(s.overhead.items()) if s.overhead else (),
            tuple((t.max_skew, t.topology_key, t.when_unsatisfiable,
                   t.min_domains, _selector_key(t.label_selector),
                   tuple(t.match_label_keys), t.node_affinity_policy,
                   t.node_taints_policy)
                  for t in s.topology_spread_constraints)
            if s.topology_spread_constraints else (),
            tuple((t.key, t.operator, t.value, t.effect)
                  for t in s.tolerations) if s.tolerations else (),
            aff_key)

    def _pack_batch_np(self, pods: list[Pod], batch_size: int,
                       fields: tuple[str, ...]
                       ) -> tuple[np.ndarray, np.ndarray]:
        """Subset-packed batch rows as host arrays (pack_batch_blobs body;
        prepare_launch also hashes these rows for topology-group dedup).

        Pods share one cached packed row per content key (_pod_row_key);
        only the identity columns (name_id, uid_id) are patched per pod.
        A hit is byte for byte what pack_pod would pack for that pod now.

        INVARIANT: a row in _pod_rows is derived from the pod's content
        and from registries that only append for the Mirror's lifetime
        (the interner, pod_label_col, topo_col and its domains, ext_col,
        _used_tks), and re-bucketing constructs a FRESH Mirror with an
        empty cache. A hit also skips pack_pod's side effects (registering
        label columns and topology keys, _used_tks): that is sound only
        because an earlier miss on this same Mirror ran them, and nothing
        un-registers. An edit that lets pack_pod read mutable state for a
        keyed pod has to put that state's version into the key or make
        _pod_row_key return None for it."""
        self._batch_prepass(pods, batch_size)
        tmpl = self._subset_tmpl.get(fields)
        if tmpl is None:
            tf32, ti32 = self._pod_template()
            tmpl = self.pod_codec.subset_template(fields, tf32, ti32)
            self._subset_tmpl[fields] = tmpl
        f32, i32 = self.pod_codec.alloc_subset(fields, batch_size)
        f32[: len(pods)] = tmpl[0]
        i32[: len(pods)] = tmpl[1]
        _f_off, i_off, _, _ = self.pod_codec.subset_layout(fields)
        # identity patch offsets; a subset omitting them (any-subset is a
        # legal BlobCodec contract) just skips the cache fast path
        name_ent = i_off.get("name_id")
        uid_ent = i_off.get("uid_id")
        cacheable = name_ent is not None and uid_ent is not None
        cache = self._pod_rows.setdefault(fields, {})
        hits = misses = 0
        full_s = 0.0
        clock = time.perf_counter
        for b, pod in enumerate(pods):
            key = self._pod_row_key(pod) if cacheable else None
            row = cache.get(key) if key is not None else None
            if row is not None:
                hits += 1
                f32[b] = row[0]
                i32[b] = row[1]
            else:
                t0 = clock()
                self.pod_codec.pack_into_subset(
                    fields, f32[b], i32[b],
                    self.pack_pod(pod, active_only=True))
                if key is not None:
                    misses += 1
                    if len(cache) > POD_ROW_CACHE_ENTRIES:
                        cache.clear()
                        self.row_cache_clears += 1
                    cache[key] = (f32[b].copy(), i32[b].copy())
                full_s += clock() - t0
            if cacheable:
                i32[b, name_ent[0]] = self._i(pod.metadata.name)
                i32[b, uid_ent[0]] = self._i(pod.metadata.uid)
        self.row_cache_hits += hits
        self.row_cache_misses += misses
        self.row_cache_bypass += len(pods) - hits - misses
        self.pack_full_s += full_s
        return f32, i32

    def slot_row_cache_stats(self) -> dict:
        """The pod-table row cache's counts (_pack_pod_slot's terms arm),
        for /debug/trace and the registry: slots_packed_terms = hits +
        misses + bypass."""
        return {"hits": self.slot_row_hits,
                "misses": self.slot_row_misses,
                "bypass": self.slot_row_bypass,
                "clears": self.slot_row_clears,
                "entries": len(self._slot_rows)}

    def row_cache_stats(self) -> dict:
        """The packed-row cache's counts, for /debug/trace and the
        registry: pods packed = hits + misses + bypass."""
        return {"hits": self.row_cache_hits,
                "misses": self.row_cache_misses,
                "bypass": self.row_cache_bypass,
                "clears": self.row_cache_clears,
                "entries": sum(len(c) for c in self._pod_rows.values())}

    # identity fields excluded from the topology-group signature: two pods
    # differing ONLY in these compute identical topology statics (name/uid
    # feed tie-breaking and diagnostics, which stay per-pod). Exception:
    # NOMINATED pods keep their uid in the signature — the pod table's
    # self-exclusion (topology.table_mask) compares table-entry uids against
    # the scheduled pod's uid, so a nominated pod sharing a group with
    # another pod would inherit the representative's self-exclusion.
    GROUP_IGNORED_FIELDS = ("name_id", "uid_id")

    def _batch_groups(self, f32: np.ndarray, i32: np.ndarray, n_pods: int,
                      fields: tuple[str, ...],
                      max_groups: int | None = None
                      ) -> tuple[np.ndarray, np.ndarray, int] | None:
        """Dedup batch rows into topology groups: (gid [B], rep [G_cap],
        g_cap). Pods with byte-identical packed rows (minus identity fields)
        share all topology statics and pairwise term matches, so the device
        computes them once per GROUP (pipeline phase-1/scan); padding rows
        form their own group.

        ``max_groups`` (probe mode): bail out with None as soon as the
        distinct-row count (padding group included) would exceed it, so a
        heterogeneous batch doesn't pay full per-row hashing for a result
        the caller will discard."""
        batch_size = f32.shape[0]
        f_off, i_off, _, _ = self.pod_codec.subset_layout(fields)
        fh = f32[:n_pods]
        ih = i32[:n_pods].copy()
        nominated = None
        if "nominated_row" in i_off:
            noff, _ = i_off["nominated_row"]
            nominated = ih[:, noff] != NONE
        for name in self.GROUP_IGNORED_FIELDS:
            if name in i_off:
                off, size = i_off[name]
                if nominated is None:
                    ih[:, off:off + size] = 0
                else:   # keep identity for nominated pods (see above)
                    ih[~nominated, off:off + size] = 0
        gid = np.zeros((batch_size,), np.int32)
        seen: dict[bytes, int] = {}
        reps: list[int] = []
        # the padding group (if any) counts against max_groups up front
        cap = (max_groups - (1 if n_pods < batch_size else 0)
               if max_groups is not None else None)
        for b in range(n_pods):
            key = fh[b].tobytes() + ih[b].tobytes()
            g = seen.get(key)
            if g is None:
                g = len(reps)
                if cap is not None and g >= cap:
                    return None
                seen[key] = g
                reps.append(b)
            gid[b] = g
        if n_pods < batch_size:          # padding rows: one shared group
            gid[n_pods:] = len(reps)
            reps.append(n_pods)
        # min 2: a full homogeneous batch (no padding group) would otherwise
        # bucket to g_cap=1 while partial batches of the same workload get 2,
        # flapping the static arg and recompiling between them
        g_cap = 2
        while g_cap < len(reps):
            g_cap *= 2
        rep = np.full((g_cap,), reps[0], np.int32)
        rep[: len(reps)] = reps
        return gid, rep, g_cap

    def pack_batch(self, pods: list[Pod], batch_size: int) -> PodFeatures:
        """PodFeatures view of a packed batch (jitted unpack; test/tooling)."""
        return _unpack_pods_jit(self.pack_batch_blobs(pods, batch_size), self.caps)

    @staticmethod
    def batch_has_host_ports(pods: list[Pod]) -> bool:
        return any(p.host_port > 0 for pod in pods
                   for c in pod.spec.containers for p in c.ports)

    def pod_fields(self, active: tuple[str, ...],
                   topo: bool) -> tuple[str, ...]:
        """The PodFeatures fields this launch's kernels can read, given its
        active features — everything else rides the device-resident template
        instead of the (slow) host->device link. Sorted for a stable jit
        static-arg key."""
        fields = set(POD_CORE_FIELDS)
        for feat in active:
            fields.update(POD_FEATURE_FIELDS.get(feat, ()))
        if topo:
            fields.update(POD_TOPO_FIELDS)
        return tuple(sorted(fields))

    def pod_template_blobs(self) -> PodBlobs:
        """Device-resident 1-row full-schema template (pushed once).

        INVARIANT: _pod_tmpl_dev / _subset_tmpl are cached for the
        Mirror's lifetime. That is sound only because template content is
        state-independent (empty-pod defaults; the interner is append-only)
        and re-bucketing constructs a FRESH Mirror. An edit that makes
        _pod_template depend on mutable state must invalidate these."""
        if self._pod_tmpl_dev is None:
            f32, i32 = self._pod_template()
            self._pod_tmpl_dev = PodBlobs(f32=jnp.asarray(f32),
                                          i32=jnp.asarray(i32))
        return self._pod_tmpl_dev

    def launch_features(self, pods: list[Pod]) -> tuple[str, ...]:
        """STATIC activity flags for one launch (schedule_batch ``active``):
        a feature used by neither the batch nor any mirrored node compiles
        out of the launch program entirely — the workload-shaped analog of
        PreFilter-Skip, and the reason a constraint-free drain runs just the
        fit/utilization kernels."""
        feats = []
        full_aff = any_pin = False
        for pod in pods:
            aff = pod.spec.affinity
            na = aff.node_affinity if aff is not None else None
            if pod.spec.node_selector \
                    or (na is not None
                        and self._node_affinity_pin(na) is None):
                full_aff = True
                break
            if na is not None:
                any_pin = True
        if full_aff:
            feats.append("nodeaffinity")
        elif any_pin:
            # every affinity in the batch is a metadata.name pin: compile
            # only the [N] pin compare (the daemonset fast path)
            feats.append("nodeaffinity_pin")
        if self._rows_with_taints:
            feats.append("taints")
        if self._rows_with_ports or self.batch_has_host_ports(pods):
            feats.append("ports")
        if self._rows_with_images and any(
                c.image for pod in pods for c in pod.spec.containers):
            feats.append("images")
        return tuple(feats)

    def prepare_launch(self, pods: list[Pod], batch_size: int
                       ) -> LaunchSpec:
        """Everything one schedule_batch launch needs, in the right order:
        pods are packed BEFORE the cluster blobs are fetched, so a topology
        key first referenced by this batch has its backfilled topo_dom
        column on device for this very launch (not the next one)."""
        feats = self.launch_features(pods)
        enable = self.batch_has_topology(pods) or self.table_has_topology()
        pfields = self.pod_fields(feats, enable)
        f32, i32 = self._pack_batch_np(pods, batch_size, pfields)
        pblobs = PodBlobs(f32=jnp.asarray(f32), i32=jnp.asarray(i32))
        gid = rep = None
        g_cap = 0
        if enable:
            # NOTE: g_cap deliberately has NO sticky high-water. Compiled
            # programs are cached per static key, so flapping between two
            # SEEN g_cap values costs nothing; padding every launch to a
            # past batch's group count would pay real per-launch compute
            # (a 100-namespace init phase would tax the whole homogeneous
            # measure phase at [G=128] statics). Hysteresis applies where
            # it prevents NEW shapes: d_cap across mirror rebuilds
            # (launch_d_cap / adopt_hysteresis).
            gid_np, rep_np, g_cap = self._batch_groups(
                f32, i32, len(pods), pfields)
            gid = jnp.asarray(gid_np)
            rep = jnp.asarray(rep_np)
        elif pods:
            # phase-1 static dedup for deployment-shaped NO-topology
            # batches: identical specs share all static filters/scores, so
            # the [B, N] phase-1 work collapses to [G, N] + a gather. Only
            # taken at a FIXED tiny group bucket — g_cap is a static jit
            # arg, and a fixed 8 keeps every batch of a workload (warmup,
            # full-size, the short tail batch) on the same compiled
            # program; spec-diverse batches bail out of the probe early
            # and take the per-pod path, also a stable program.
            probe = self._batch_groups(f32, i32, len(pods), pfields,
                                       max_groups=P1_DEDUP_GROUP_CAP)
            if probe is not None:
                gid_np, rep_np, _ = probe
                rep8 = np.full((P1_DEDUP_GROUP_CAP,), rep_np[0], np.int32)
                rep8[: len(rep_np)] = rep_np[: P1_DEDUP_GROUP_CAP]
                gid = jnp.asarray(gid_np)
                rep = jnp.asarray(rep8)
                g_cap = P1_DEDUP_GROUP_CAP
        return LaunchSpec(cblobs=self.to_blobs(), pblobs=pblobs,
                          enable_topology=enable,
                          d_cap=self.launch_d_cap(enable),
                          active=feats, pfields=pfields,
                          ptmpl=self.pod_template_blobs(),
                          gid=gid, rep=rep, g_cap=g_cap,
                          topo_soft=(enable and
                                     self.batch_topology_soft_only(pods)),
                          table_hi=self.slots_hi)
