"""Preemption: the generic Evaluator + the DefaultPreemption PostFilter.

Host orchestration mirrors /root/reference/pkg/scheduler/framework/
preemption/preemption.go (Evaluator.Preempt :232, findCandidates :307,
SelectCandidate/pickOneNodeForPreemption :395,:565, prepareCandidate :428)
and plugins/defaultpreemption/default_preemption.go (PostFilter :133,
SelectVictimsOnNode :219, PodEligibleToPreemptOthers :327,
GetOffsetAndNumCandidates :186) — with the per-node dry-run replaced by ONE
device sweep over victim prefixes (ops.preempt.preempt_sweep).

Victim ordering: pods on a node sort ascending by importance
(util.MoreImportantPod: priority, then start time) so the minimal feasible
prefix evicts the least-important pods first — the resource-space fixed
point of the reference's remove-all-then-reprieve loop.
"""

from __future__ import annotations

import logging
import random
import time
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.api.labels import label_selector_matches
from kubernetes_tpu.api.objects import (
    LABEL_POD_GROUP,
    Pod,
    pod_group_key,
)
from kubernetes_tpu.hub import Fenced, StaleRing, Unavailable
from kubernetes_tpu.framework.interface import (
    PostFilterPlugin,
    PreEnqueuePlugin,
    Status,
)
from kubernetes_tpu.ops import features as F
from kubernetes_tpu.ops.preempt import preempt_feasible_jit, preempt_sweep_jit
from kubernetes_tpu.utils.interner import NONE

logger = logging.getLogger("kubernetes_tpu.preemption")

# sentinel: the incremental victim-state update cannot represent the new
# cluster shape; fall back to a full rebuild
_REBUILD = object()

MI = 1024 * 1024

# default_preemption.go:40-44 (DefaultPreemptionArgs defaults)
MIN_CANDIDATE_NODES_PERCENTAGE = 10
MIN_CANDIDATE_NODES_ABSOLUTE = 100

# bound on exact dry-run launches per preemption attempt: candidates tried
# (verification) + reprieve steps on the winner
MAX_VERIFY_CANDIDATES = 8
MAX_REPRIEVE_STEPS = 16

# Evaluator.sweep_stats()'s keys: the pods swept, the four outcomes they
# add up to, the victim state's two refresh paths, stranded preemptors,
# and the host kmins a burst computed against those it reused
SWEEP_STATS = ("preemptors_swept", "nominated", "no_candidates",
               "ineligible", "extender_errors", "victim_state_rebuilds",
               "victim_state_updates", "stranded_activations",
               "kmin_computed", "kmin_reused")


@dataclass
class Candidate:
    """One preemption candidate (candidate.go): a node + its victims."""

    node_name: str
    row: int
    victims: list[Pod]
    pdb_violations: int
    # True once an extender's ProcessPreemption pass ran: the victim list
    # is FINAL — verification may discard the candidate but must never
    # regrow or reprieve the list (the reference runs callExtenders after
    # the dry-run's reprieve, so extender trims are authoritative)
    victims_final: bool = False


class Evaluator:
    """Generic preemption evaluator over the device mirror."""

    def __init__(self, hub, get_mirror, get_caps, get_enabled_filters,
                 nominator, rng: random.Random | None = None):
        self.hub = hub
        # callables: the scheduler re-buckets the mirror/caps, and the
        # framework (which owns the filter config) is built after us
        self._get_mirror = get_mirror
        self._get_caps = get_caps
        self._get_enabled_filters = get_enabled_filters
        self.nominator = nominator
        self._rng = rng or random.Random(0)
        # request-row cache: a victim's packed resource row is immutable per
        # uid FOR A GIVEN MIRROR — a re-bucketed mirror changes res_cols and
        # ext-resource column order, so the cache is tied to the mirror
        # object and dropped when the scheduler rebuilds it
        self._res_rows: dict[tuple[str, bool], np.ndarray] = {}
        self._res_rows_mirror: object = None
        # async preemption (preemption.go:460 prepareCandidateAsync +
        # kep 4832): pods whose victims are still being evicted, and the
        # eviction work queue the scheduler drains between cycles
        self.preempting: set[str] = set()
        self._pending: list[tuple[Candidate, Pod]] = []
        # nominee status-clear writes deferred by a hub outage (the
        # local nomination is already dropped; only the API write waits)
        self._pending_clears: list[str] = []
        # scheduler-installed: activates preemptors whose flush produced no
        # deletion event (empty/already-deleted victim sets) — the gate
        # opener of last resort (see flush_evictions)
        self.activate_fn = None
        # scheduler-installed (pipelined waves): when True, a preemptor
        # whose eviction wave FIRED is also activated explicitly at flush
        # end — it re-probes on the very next wave instead of waiting out
        # the deletion event's backoff routing (its nominated reservation
        # protects the freed slot meanwhile)
        self.activate_flushed = False
        # scheduler-installed (pipelined waves): () -> live device free
        # matrix (the scheduler's resident free/nzr chain) or None. When
        # set and live, the sweep/probe fit baselines see in-flight waves
        # the snapshot free matrix has not absorbed yet
        self.live_free_fn = None
        # scheduler-installed: () -> [HTTPExtender]; candidates pass
        # through ProcessPreemption before selection (preemption.go:335)
        self.extenders_fn = None
        self.metrics = None     # SchedulerMetrics, set by the Scheduler
        # scheduler-installed fencing: () -> (epoch, lease_name) | ();
        # queued evictions and nomination clears carry the epoch of the
        # flush that lands them, so a deposed leader's backlog is
        # rejected (Fenced) instead of evicting pods the new leader may
        # have re-planned around
        self.fencing_fn = None
        self.fenced_metric = None   # (verb) -> None, set by the Scheduler
        # incremental victim-sweep state per preemptor priority (see
        # _collect_victims): row_gen-keyed victim lists + the resident
        # device cumsum, refreshed by row-scatter between bursts
        self._vic_state: dict[int, dict] = {}
        # seconds batch_preempt spent, by its own clock pair around each
        # call (the flight recorder's preempt_select view), and the
        # sweep's counts (sweep_stats): totals of the evaluator's life
        self.select_s = 0.0
        self._sweep = dict.fromkeys(SWEEP_STATS, 0)
        # preemptor uid -> row of a nomination the batched path made that
        # the mirror's nominated overlay does not reserve yet (it is
        # refreshed by a launch's pack, and two failure handlings can
        # sweep with no pack between them): later sweeps leave the row
        # alone until the overlay holds it or the nomination is gone
        self._unreserved: dict[str, int] = {}

    def sweep_stats(self) -> dict:
        """What the batched sweep did, totals of the evaluator's life:
        preemptors_swept (pods handed to batch_preempt) = nominated +
        no_candidates + ineligible + extender_errors (an outage mid-sweep
        leaves the rest unaccounted); victim_state_rebuilds against
        victim_state_updates (_collect_victims' full pass over every pod
        against its incremental one); stranded_activations (preemptors
        a flush activated because their plan fired no deletion of its
        own: victims already gone or claimed by an earlier plan, or a
        gang eviction blocked); kmin_computed + kmin_reused = the eligible
        preemptors that reached finish_batch_preempt (one kmin computed a
        distinct request and own nominated row of a burst, the rest
        reused)."""
        return dict(self._sweep)

    # ---------------- eligibility (default_preemption.go:327) -------------

    def pod_eligible_to_preempt_others(self, pod: Pod) -> tuple[bool, str]:
        if pod.spec.preemption_policy == "Never":
            return False, "preemptionPolicy=Never"
        nom = pod.status.nominated_node_name
        if nom:
            # if the nominated node has a terminating lower-priority pod, the
            # previous preemption is still in flight: wait for it
            mirror = self._get_mirror()
            row = mirror.row_of(nom)
            if row >= 0:
                snap_pods = self._pods_on_node(nom)
                for p in snap_pods:
                    if (p.metadata.deletion_timestamp is not None
                            and p.priority() < pod.priority()):
                        return False, "previous victims still terminating"
        return True, ""

    # ---------------- candidate discovery ----------------

    def _pods_on_node(self, node_name: str) -> list[Pod]:
        info = self.cache_snapshot.get(node_name)
        return [pi.pod for pi in info.pods] if info is not None else []

    def find_candidates(self, pod: Pod, snapshot,
                        resource_only: bool = False) -> list[Candidate]:
        """Device sweep + host assembly of (node, victims) candidates.
        ``resource_only``: the caller knows the pod's rejection was pure
        NodeResourcesFit, so the sweep's answer is exact and the
        full-filter dry-run machinery is skipped."""
        self.cache_snapshot = snapshot.node_info_map
        mirror = self._get_mirror()
        caps = self._get_caps()
        prio = pod.priority()
        prep = self._collect_victims(prio, snapshot, mirror, caps)
        if prep is None:
            return []
        victims_by_row, cumsum_np, vic_cols, cols_np = prep

        pblobs = mirror.pack_batch_blobs([pod], 1)
        cblobs = mirror.to_blobs()
        live_free = (self.live_free_fn()
                     if self.live_free_fn is not None else None)
        # the device sweep is the one reader of the victim cumsum on the
        # device: it is uploaded here, never kept there between sweeps
        kmin = np.asarray(preempt_sweep_jit(
            cblobs, pblobs, mirror.well_known(), jnp.asarray(cumsum_np),
            jnp.asarray(vic_cols), caps, self._get_enabled_filters(pod),
            free=live_free))[0]
        self._kmin = kmin                     # reused by _minimize_victims
        self._victims_by_row = victims_by_row

        # candidate rows: full-filter feasibility with EVERY victim evicted
        # (the reference's remove-all first step, default_preemption.go:219,
        # evaluated for all nodes in one launch). This is the exact superset
        # of per-node-eviction feasibility for monotone filters; the chosen
        # candidate is re-verified with per-node masking before any eviction
        # happens, so an optimistic row costs one extra launch, never a
        # wrong eviction. Topology-blocked preemptors (a victim's
        # anti-affinity, a hard spread violation) find candidates here even
        # though they "fit" resource-wise — the gap the resource-only sweep
        # could not cover.
        if resource_only:
            # the pod was rejected ONLY by NodeResourcesFit: the resource
            # sweep's kmin IS the reference's remove-then-reprieve fixed
            # point (victims sorted ascending importance), so candidate
            # rows and minimal victim sets come straight from it — zero
            # additional dry-run launches on the hot preemption path
            return self._assemble_candidates(
                pod, kmin, victims_by_row, snapshot, mirror,
                mirror.free_matrix(), self.hub.list_pdbs())

        all_uids = {pi.pod.metadata.uid
                    for vs in victims_by_row.values() for pi in vs}
        # keep victims that could SATISFY the preemptor's required affinity
        # visible: masking them cluster-wide would under-approximate
        # feasibility (the reference only ever removes the candidate node's
        # own pods). A provider-victim on the chosen node itself is caught
        # by the exact per-node verification.
        aff = pod.spec.affinity
        aff_terms = (aff.pod_affinity.required
                     if aff is not None and aff.pod_affinity is not None
                     else [])
        if aff_terms:
            for vs in victims_by_row.values():
                for pi in vs:
                    v = pi.pod
                    for term in aff_terms:
                        ns_ok = (v.metadata.namespace
                                 == pod.metadata.namespace
                                 if not term.namespaces
                                 else v.metadata.namespace in term.namespaces)
                        if ns_ok and label_selector_matches(
                                term.label_selector, v.metadata.labels):
                            all_uids.discard(v.metadata.uid)
                            break
        r_cols = caps.res_cols
        freed = {}
        for row, vs in victims_by_row.items():
            full = np.zeros((r_cols,), np.float32)
            full[cols_np] = cumsum_np[row, len(vs), : len(cols_np)]
            freed[row] = full
        feas = self._dryrun_feasible(pod, all_uids, freed)
        rows = [row for row in victims_by_row if feas[row]]
        if not rows:
            return []

        # candidate subset: random offset + bounded count (preemption.go:307
        # GetOffsetAndNumCandidates)
        num_nodes = len(snapshot.node_info_list)
        want = max(num_nodes * MIN_CANDIDATE_NODES_PERCENTAGE // 100,
                   MIN_CANDIDATE_NODES_ABSOLUTE)
        rows.sort()
        off = self._rng.randrange(len(rows))
        picked = [rows[(off + i) % len(rows)]
                  for i in range(min(want, len(rows)))]

        pdbs = self.hub.list_pdbs()
        out = []
        for row in picked:
            vs = victims_by_row[row]
            # rank candidates by their minimal-victim ESTIMATE: the kmin
            # prefix when the resource sweep found one (exact for
            # resource-blocked preemptors), the full list otherwise —
            # select_candidate's pdb/priority/count keys would regress if
            # computed over pods that will never be evicted
            k = int(kmin[row])
            if k != NONE and 1 <= k <= len(vs):
                vs = vs[:k]
            victims = [pi.pod for pi in vs]
            out.append(Candidate(
                node_name=mirror.name_of_row(row) or "",
                row=row, victims=victims,
                pdb_violations=self._pdb_violations(victims, pdbs)))
        return out

    def _dryrun_feasible(self, pod: Pod, exclude_uids, freed_by_row
                         ) -> np.ndarray:
        """[N] bool: FULL filter set for ``pod`` with ``exclude_uids``
        masked out of the device pod table and each row's free resources
        raised by its freed vector (ops.preempt.preempt_feasible)."""
        mirror = self._get_mirror()
        caps = self._get_caps()
        tval = mirror.table_valid_mask(exclude_uids)
        live_free = (self.live_free_fn()
                     if self.live_free_fn is not None else None)
        # live chain wins when present: the probe's fit baseline then
        # includes waves still in flight (np.array forces a writable
        # host copy off the device buffer)
        free = (np.array(live_free, np.float32) if live_free is not None
                else mirror.free_matrix())
        for row, vec in freed_by_row.items():
            free[row] = free[row] + vec
        pblobs = mirror.pack_batch_blobs([pod], 1)
        enable = (mirror.table_has_topology()
                  or mirror.batch_has_topology([pod]))
        return np.asarray(preempt_feasible_jit(
            mirror.to_blobs(), pblobs, mirror.well_known(), caps,
            jnp.asarray(tval), jnp.asarray(free), enable,
            mirror.launch_d_cap(enable), self._get_enabled_filters(pod)))

    def _res_row_cached(self, pod: Pod, freed: bool = False) -> np.ndarray:
        """A pod's f32 resource row: demand (the preemptor's request)
        rounds UP; ``freed=True`` (a victim's contribution handed back
        to capacity) rounds DOWN — summing ceiled victim rows onto free
        would overstate post-eviction headroom and evict pods for a
        preemption that cannot succeed."""
        from kubernetes_tpu.api.resources import pod_request

        key = (pod.metadata.uid, freed)
        rr = self._res_rows.get(key)
        if rr is None:
            rr = np.asarray(self._get_mirror()._res_row(
                pod_request(pod), capacity=freed), np.float32)
            self._res_rows[key] = rr
        return rr

    def _minimize_victims(self, pod: Pod, cand: Candidate,
                          pdbs) -> Candidate | None:
        """Exact verification + reprieve for one candidate (the
        reference's per-node reprieve loop, default_preemption.go:219):

        1. Verify the pod actually fits with ONLY this node's victims
           evicted (full filters). A candidate from the optimistic
           all-evicted pass that fails here is discarded — no eviction ever
           happens on an unverified candidate.
        2. If the resource sweep found a feasible prefix, try it first: the
           prefix (least-important victims) is the resource-space reprieve
           fixed point, one launch to confirm.
        3. Otherwise reprieve victims one at a time — PDB-violating victims
           first, then most-important-first — keeping each reprieve that
           leaves the pod feasible (bounded by MAX_REPRIEVE_STEPS).
        """
        row = cand.row
        victims = list(cand.victims)        # ascending importance

        def feasible_with(vset: list[Pod]) -> bool:
            if not vset:
                return False
            freed = np.zeros_like(self._res_row_cached(vset[0],
                                                       freed=True))
            for v in vset:
                freed = freed + self._res_row_cached(v, freed=True)
            feas = self._dryrun_feasible(
                pod, {v.metadata.uid for v in vset}, {row: freed})
            return bool(feas[row])

        if cand.victims_final:
            # an extender trimmed this list: it is authoritative — verify
            # as-is; never regrow to the full set or reprieve further
            return cand if feasible_with(victims) else None

        kmin = getattr(self, "_kmin", None)
        k = int(kmin[row]) if kmin is not None else NONE
        from_prefix = k != NONE and len(victims) == k
        if not feasible_with(victims):
            # the candidate carried the kmin-trimmed ranking estimate; try
            # the node's full victim set before giving up (topology-blocked
            # preemptors may need more than the resource prefix)
            full = [pi.pod for pi in self._victims_by_row.get(row, [])]
            if len(full) > len(victims) and feasible_with(full):
                victims = full
                from_prefix = False
            else:
                return None                 # unverifiable candidate: discard
        elif from_prefix:
            # the verified set IS the resource sweep's minimal prefix: the
            # reprieve loop cannot shrink it further (each prefix k-1 was
            # already infeasible by kmin's minimality) — skip the per-victim
            # launches entirely for the resource-blocked common case
            return Candidate(
                node_name=cand.node_name, row=row, victims=victims,
                pdb_violations=self._pdb_violations(victims, pdbs))
        if k != NONE and 1 <= k < len(victims):
            prefix = victims[:k]
            if feasible_with(prefix):
                victims = prefix
        if len(victims) > 1:
            flags = self._pdb_violation_flags(victims, pdbs)
            # reprieve order: PDB-violating first, then priority desc,
            # then older first (filterPodsWithPDBViolation + reprievePod)
            order = sorted(
                range(len(victims)),
                key=lambda i: (not flags[i], -victims[i].priority(),
                               victims[i].metadata.creation_timestamp))
            kept = set()
            steps = 0
            for i in order:
                if steps >= MAX_REPRIEVE_STEPS or len(victims) - len(kept) <= 1:
                    break
                trial = [v for j, v in enumerate(victims)
                         if j != i and j not in kept]
                steps += 1
                if feasible_with(trial):
                    kept.add(i)
            victims = [v for j, v in enumerate(victims) if j not in kept]
        return Candidate(
            node_name=cand.node_name, row=row, victims=victims,
            pdb_violations=self._pdb_violations(victims, pdbs))

    @staticmethod
    def _pdb_violation_flags(victims: list[Pod], pdbs) -> list[bool]:
        """Per-victim: does evicting it violate some exhausted PDB?"""
        budget = {pdb.metadata.uid: pdb.disruptions_allowed for pdb in pdbs}
        flags = []
        for v in victims:
            matched = [pdb for pdb in pdbs
                       if pdb.metadata.namespace == v.metadata.namespace
                       and pdb.selector is not None
                       and label_selector_matches(pdb.selector,
                                                  v.metadata.labels)]
            flags.append(any(budget[pdb.metadata.uid] <= 0
                             for pdb in matched))
            for pdb in matched:
                budget[pdb.metadata.uid] -= 1
        return flags

    @staticmethod
    def _pdb_violations(victims: list[Pod], pdbs) -> int:
        """How many VICTIMS violate some PDB's disruptionsAllowed — each pod
        counts at most once even if it matches several exhausted PDBs
        (preemption.go filterPodsWithPDBViolation classifies per pod); every
        eviction still draws down each matching PDB's budget."""
        budget = {pdb.metadata.uid: pdb.disruptions_allowed for pdb in pdbs}
        violations = 0
        for v in victims:
            matched = [pdb for pdb in pdbs
                       if pdb.metadata.namespace == v.metadata.namespace
                       and pdb.selector is not None
                       and label_selector_matches(pdb.selector,
                                                  v.metadata.labels)]
            if any(budget[pdb.metadata.uid] <= 0 for pdb in matched):
                violations += 1
            for pdb in matched:
                budget[pdb.metadata.uid] -= 1
        return violations

    # ------------- extender pass (preemption.go:335 callExtenders) --------

    def call_extenders(self, pod: Pod,
                       candidates: list[Candidate]) -> list[Candidate]:
        """Run every preemption-capable interested extender over the
        candidate map: extenders veto nodes (omission) and trim victim
        lists (trims are FINAL — victims_final). An ignorable extender's
        transport failure is skipped; a non-ignorable one raises
        ExtenderError so the caller aborts the attempt as an ERROR, not
        a misleading 'no candidates' (preemption.go:349)."""
        from kubernetes_tpu.extender import ExtenderError

        extenders = self.extenders_fn() if self.extenders_fn else []
        relevant = [ext for ext in extenders
                    if ext.supports_preemption and ext.is_interested(pod)]
        if not relevant or not candidates:
            return candidates
        by_node = {c.node_name: c for c in candidates}
        node_to_victims = {c.node_name: list(c.victims)
                           for c in candidates}
        pdbs = {c.node_name: c.pdb_violations for c in candidates}
        for ext in relevant:
            try:
                survivors = ext.process_preemption(pod, node_to_victims,
                                                   pdbs)
            except ExtenderError as e:
                if ext.cfg.ignorable:
                    continue
                logger.warning("preemption extender failed: %s", e)
                raise
            # a node returned with NO victims is removed, like upstream
            # callExtenders deletes empty/unresolvable entries — an
            # empty-victim candidate would otherwise always win selection
            # while evicting nothing
            node_to_victims = {n: v for n, (v, _p) in survivors.items()
                               if v}
            pdbs = {n: p for n, (_v, p) in survivors.items() if _v}
            if not node_to_victims:
                return []
        out = []
        for node, victims in node_to_victims.items():
            c = by_node[node]
            if len(victims) < len(c.victims):
                # the extender TRIMMED a verified-minimal list: upstream
                # trusts the extender blindly; we add a cheap host
                # resource-sufficiency check and drop candidates whose
                # trimmed set can no longer free enough (a bad extender
                # must not cause a pointless eviction)
                if not self._resources_sufficient(pod, c.row, victims):
                    continue
            out.append(Candidate(node_name=c.node_name, row=c.row,
                                 victims=victims,
                                 pdb_violations=pdbs.get(node, 0),
                                 victims_final=True))
        return out

    def _resources_sufficient(self, pod: Pod, row: int,
                              victims: list[Pod]) -> bool:
        """Host arithmetic: do these victims' requests free enough on
        ``row`` for the pod to fit resource-wise? (Necessary, not
        sufficient, for topology-blocked preemptors — still strictly
        safer than upstream's unchecked trust in extender trims.)"""
        mirror = self._get_mirror()
        free = np.asarray(mirror.free_matrix()[row], np.float32)
        nom = getattr(mirror, "_nominated_req_of_row", {}).get(row)
        if nom is not None:
            free = free - np.asarray(nom, np.float32)
        req = np.asarray(self._res_row_cached(pod), np.float32)
        nnn = pod.status.nominated_node_name
        if nnn and mirror.row_of(nnn) == row:
            free = free + req
        freed = np.zeros_like(req)
        for v in victims:
            freed = freed + self._res_row_cached(v, freed=True)
        return bool(np.all(req <= free + freed))

    # ---------------- selection (preemption.go:565 pickOneNode) -----------

    @staticmethod
    def candidate_key(c: Candidate):
        """pickOneNodeForPreemption's ordering (preemption.go:565):
        fewest PDB violations, lowest max victim priority, lowest
        priority sum, fewest victims, latest-started important victim."""
        prios = [v.priority() for v in c.victims]
        high = max(prios) if prios else -(2 ** 31)
        # latest start of the highest-priority victim: prefer evicting
        # the youngest important pod
        starts = [v.metadata.creation_timestamp for v in c.victims
                  if v.priority() == high]
        latest = max(starts) if starts else 0.0
        return (c.pdb_violations, high, sum(prios), len(c.victims),
                -latest, c.node_name)

    @staticmethod
    def select_candidate(candidates: list[Candidate]) -> Candidate | None:
        if not candidates:
            return None
        return min(candidates, key=Evaluator.candidate_key)

    # ---------------- execution (preemption.go:428 prepareCandidate) ------

    def prepare_candidate(self, candidate: Candidate, pod: Pod) -> None:
        """Queue the eviction work (prepareCandidateAsync, kep 4832): the
        scheduler drains it via flush_evictions OUTSIDE the scheduling
        cycle, and the DefaultPreemption PreEnqueue gate keeps the
        preemptor parked until its victims are gone."""
        self.preempting.add(pod.metadata.uid)
        self._pending.append((candidate, pod))

    def has_pending(self) -> bool:
        """Whether flush_evictions has queued work (evictions or deferred
        nomination clears) — the scheduler's cue to time the flush as an
        eviction_flush phase instead of skipping the empty no-op."""
        return bool(self._pending or self._pending_clears)

    def flush_evictions(self) -> int:
        """Execute queued evictions; returns the number of preparations
        run. The preemptor leaves ``preempting`` BEFORE the last victim
        deletion so that deletion's cluster event finds the gate open and
        requeues it (preemption.go:528's ordering). A candidate whose
        victim set is empty — or whose victims were already deleted by an
        overlapping candidate this flush — produces NO deletion event, so
        its preemptor is activated explicitly (``activate_fn``): without
        that, two preemptors nominating the same node can deadlock parked
        behind each other's reservations."""
        # retry API nomination clears a previous outage deferred (the
        # local nominator entries are already gone, so only the status
        # write can be replayed)
        fargs = self.fencing_fn() if self.fencing_fn is not None else ()
        clears, self._pending_clears = self._pending_clears, []
        for uid in clears:
            try:
                self.hub.clear_nominated_node(uid, *fargs)
            except Unavailable:
                self._pending_clears.append(uid)
            except Fenced:
                self._note_fenced("clear_nominated_node")
                # deposed: the new leader owns preemption policy now —
                # drop the clear backlog AND the eviction backlog (a
                # re-elected leader replaying either under its newer
                # epoch would launder stale decisions) and ungate every
                # queued preemptor for the retry path
                self._pending_clears = []
                dropped, self._pending = self._pending, []
                stranded = []
                for _cand, p in dropped:
                    self.preempting.discard(p.metadata.uid)
                    stranded.append(p)
                if stranded and self.activate_fn is not None:
                    self.activate_fn(stranded)
                return 0
            except Exception:  # noqa: BLE001 — pod gone: nothing to clear
                pass
        work, self._pending = self._pending, []
        stranded = []
        try:
            self._flush_candidates(work, stranded, fargs)
        finally:
            # the activation of already-processed stranded preemptors
            # must fire even when an outage aborts the flush mid-way:
            # they are no longer in ``preempting`` and no deletion event
            # will requeue them (activate_fn is queue-local, hub-free)
            if stranded and self.activate_fn is not None:
                self.activate_fn(stranded)
        return len(work)

    def _note_fenced(self, verb: str) -> None:
        if self.fenced_metric is not None:
            self.fenced_metric(verb)
        logger.warning("preemption %s rejected: this scheduler's fencing "
                       "epoch was deposed; dropping the eviction backlog",
                       verb)

    def _flush_candidates(self, work: list, stranded: list,
                          fargs: tuple = ()) -> None:
        """One flush = plan, then ONE multi-delete wave (ISSUE 15).

        Phase A walks the backlog host-side (nomination clears, gang
        expansion, PDB/priority guards) into per-candidate victim plans;
        phase B opens every planned preemptor's gate and commits ALL
        victim deletions as one ``hub.delete_pods`` wave — a single lock
        acquisition / RPC instead of one per victim; phase C strands any
        candidate none of whose victims actually produced a deletion
        event. Hubs without the batched verb (sharded facades, old
        peers) keep the per-victim path with identical semantics."""
        batched = getattr(self.hub, "delete_pods", None)
        if not callable(batched):
            return self._flush_candidates_serial(work, stranded, fargs)
        listed: dict = {}

        def _list_once():
            if "pods" not in listed:
                listed["pods"] = self.hub.list_pods()
            return listed["pods"]

        plans: list = []            # (pod, victims) per surviving candidate
        for i, (candidate, pod) in enumerate(work):
            try:
                dropped = self.nominator.clear_for_node_below_priority(
                    candidate.node_name, pod.priority())
                for nominee in dropped:
                    try:
                        self.hub.clear_nominated_node(
                            nominee.metadata.uid, *fargs)
                    except Unavailable:
                        self._pending_clears.append(nominee.metadata.uid)
                victims, blocked = self._expand_gang_victims(
                    candidate.victims, pod, _list_once)
                if blocked:
                    logger.info("gang eviction for %s blocked: %s",
                                pod.key(), blocked)
                    self.preempting.discard(pod.metadata.uid)
                    stranded.append(pod)
                    self._sweep["stranded_activations"] += 1
                    continue
                plans.append((pod, victims))
            except Unavailable:
                # outage mid-planning: nothing is deleted yet — the
                # whole backlog (already-planned candidates included)
                # replays; every planning step is idempotent
                planned = {p.metadata.uid for (p, _v) in plans}
                self._pending = (
                    [w for w in work if w[1].metadata.uid in planned]
                    + work[i:] + self._pending)
                raise
        if not plans:
            return
        # phase B: gates open BEFORE any deletion event can fire (the
        # batched form of preemption.go:528's ordering), then one wave
        uids: list[str] = []
        owner: dict[str, int] = {}  # victim uid -> first plan claiming it
        for i, (pod, victims) in enumerate(plans):
            self.preempting.discard(pod.metadata.uid)
            for v in victims:
                if v.metadata.uid not in owner:
                    owner[v.metadata.uid] = i
                    uids.append(v.metadata.uid)
        try:
            gone = set(batched(uids, *fargs)) if uids else set()
        except Unavailable:
            # the wave's verdict is unknown: re-gate + requeue every
            # planned candidate; a replayed wave skips already-gone
            # victims, so replay is idempotent
            for pod, _v in plans:
                self.preempting.add(pod.metadata.uid)
            self._pending = ([w for w in work
                              if w[1].metadata.uid in
                              {p.metadata.uid for (p, _v) in plans}]
                             + self._pending)
            raise
        except StaleRing:
            # a ring slot froze mid-wave (segment export in flight):
            # partially-committed deletes already dispatched their
            # events; re-gate + requeue like the Unavailable case —
            # replay is idempotent — but swallow: the freeze heals on
            # its own (import / abort / FROZEN_TTL), no outage to note
            for pod, _v in plans:
                self.preempting.add(pod.metadata.uid)
            self._pending = ([w for w in work
                              if w[1].metadata.uid in
                              {p.metadata.uid for (p, _v) in plans}]
                             + self._pending)
            return
        except Fenced:
            self._note_fenced("delete_pod")
            for pod, _v in plans:
                stranded.append(pod)
            self._pending = []
            return
        for i, (pod, victims) in enumerate(plans):
            # a plan is "fired" only by a deletion it OWNS (first claim in
            # plan order — the serial path's exact discipline): a candidate
            # whose victims were all claimed by overlapping earlier plans
            # produces no deletion event of its own, so its preemptor must
            # be activated explicitly or two preemptors nominating the
            # same node deadlock in escalating backoff behind each other's
            # reservations
            fired = any(v.metadata.uid in gone
                        and owner[v.metadata.uid] == i for v in victims)
            if not fired:
                self._sweep["stranded_activations"] += 1
            # pipelined waves: a FIRED preemptor is activated too — its
            # re-probe rides the very next scheduling wave instead of
            # waiting for the deletion event's backoff routing (the
            # nominated reservation keeps the freed slot protected, and
            # queue.activate is a no-op for pods already runnable)
            if not fired or self.activate_flushed:
                stranded.append(pod)

    def _flush_candidates_serial(self, work: list, stranded: list,
                                 fargs: tuple = ()) -> None:
        # one cluster pod list per FLUSH, fetched lazily on the first
        # gang victim and shared by every candidate — per-candidate
        # list_pods() would pay a full-cluster RPC for each gang
        # eviction in the backlog
        listed: dict = {}

        def _list_once():
            if "pods" not in listed:
                listed["pods"] = self.hub.list_pods()
            return listed["pods"]

        for i, (candidate, pod) in enumerate(work):
            try:
                # lower-priority nominees on this node must re-evaluate:
                # drop the nomination AND clear the API status; the
                # update event re-activates them
                dropped = self.nominator.clear_for_node_below_priority(
                    candidate.node_name, pod.priority())
                for nominee in dropped:
                    try:
                        self.hub.clear_nominated_node(
                            nominee.metadata.uid, *fargs)
                    except Unavailable:
                        # the nominator entry is dropped for good — a
                        # retried candidate would find nothing to clear
                        # — so park the STATUS write itself for replay
                        self._pending_clears.append(nominee.metadata.uid)
                # whole-gang eviction: a victim that belongs to a gang
                # takes its ENTIRE gang with it (cluster-wide), never a
                # partial slice — a half-evicted gang would keep burning
                # nodes on a job that can no longer run
                victims, blocked = self._expand_gang_victims(
                    candidate.victims, pod, _list_once)
                if blocked:
                    # a pulled-in co-member is protected (exhausted PDB,
                    # or outranks the preemptor): the gang cannot be
                    # evicted whole, so nothing of it is evicted at all —
                    # strand the preemptor to re-evaluate other nodes
                    logger.info("gang eviction for %s blocked: %s",
                                pod.key(), blocked)
                    self.preempting.discard(pod.metadata.uid)
                    stranded.append(pod)
                    self._sweep["stranded_activations"] += 1
                    continue
                for victim in victims[:-1]:
                    try:
                        self.hub.delete_pod(victim.metadata.uid, *fargs)
                    except Unavailable:
                        raise           # outage ≠ "already gone"
                    except Fenced:
                        raise
                    except Exception:  # noqa: BLE001 — gone is fine
                        pass
                self.preempting.discard(pod.metadata.uid)
                fired = False
                if victims:
                    try:
                        self.hub.delete_pod(victims[-1].metadata.uid,
                                            *fargs)
                        fired = True
                    except Unavailable:
                        raise
                    except Fenced:
                        raise
                    except Exception:  # noqa: BLE001
                        pass
                if not fired:
                    self._sweep["stranded_activations"] += 1
                # pipelined waves: activate fired preemptors too (see the
                # batched path) so the re-probe rides the next wave
                if not fired or self.activate_flushed:
                    stranded.append(pod)
            except Unavailable:
                # hub outage mid-candidate: requeue it and the whole
                # unprocessed tail so nothing is dropped on the floor.
                # Re-gate THIS candidate's preemptor: its discard may
                # already have run, and an ungated preemptor could fail
                # another cycle and enqueue a duplicate candidate before
                # this one replays. Every step above is idempotent on
                # replay (NotFound deletes are swallowed, set ops).
                self.preempting.add(pod.metadata.uid)
                self._pending = work[i:] + self._pending
                raise
            except Fenced:
                # deposed mid-flush: the new leader owns eviction policy.
                # Drop the WHOLE backlog (replaying it under a newer
                # epoch would launder stale decisions) and ungate every
                # affected preemptor so the new leader's informer events
                # — or their own retries — can pick them back up.
                self._note_fenced("delete_pod")
                for _cand, p in work[i:]:
                    self.preempting.discard(p.metadata.uid)
                    stranded.append(p)
                self._pending = []
                return

    def _expand_gang_victims(self, victims: list[Pod],
                             preemptor: Pod | None = None,
                             list_pods=None) -> tuple[list[Pod], str]:
        """All-or-nothing eviction: victims carrying a gang label pull in
        every BOUND member of their gang (one hub scan, only when a gang
        victim is actually present; ``list_pods`` lets the flush share a
        single scan across its whole backlog). Returns ``(victims,
        blocked)``: pulled-in co-members bypassed candidate selection, so
        they get their own guard here — one outranking the preemptor or
        violating an exhausted PDB blocks the WHOLE gang eviction
        (partial eviction is never an option)."""
        keys = {k for v in victims
                if LABEL_POD_GROUP in v.metadata.labels
                and (k := pod_group_key(v)) is not None}
        if not keys:
            return victims, ""
        have = {v.metadata.uid for v in victims}
        extra = []
        pods = list_pods() if list_pods is not None else \
            self.hub.list_pods()
        for p in pods:
            if p.metadata.uid in have or not p.spec.node_name:
                continue
            if pod_group_key(p) in keys:
                extra.append(p)
        if extra and preemptor is not None:
            outranking = [p for p in extra
                          if p.priority() >= preemptor.priority()]
            if outranking:
                return victims, (f"gang co-member {outranking[0].key()} "
                                 "outranks the preemptor")
            try:
                pdbs = self.hub.list_pdbs()
            except Unavailable:
                raise
            # the original victims evict in the same flush, so they draw
            # the PDB budgets down first — a co-member is only safe
            # against what remains, not against a fresh budget
            flags = self._pdb_violation_flags(victims + extra,
                                              pdbs)[len(victims):]
            if any(flags):
                protected = extra[flags.index(True)]
                return victims, (f"gang co-member {protected.key()} is "
                                 "protected by an exhausted PDB")
        return victims + extra, ""

    def _reprieve_by_resources(self, victims: list[Pod], pod: Pod,
                               row: int, free_mat: np.ndarray) -> list[Pod]:
        """The reference's reprieve pass, host-side: walk the victim set
        most-important-first (oldest first at equal priority) and re-add
        any victim whose eviction is NOT needed for the preemptor's
        resource fit (default_preemption.go:219's re-add loop). Pure
        arithmetic — the kmin prefix can contain useless small victims
        (e.g. freshly-bound tiny pods sorted youngest-first) that must
        never be evicted. ``free_mat`` is one hoisted free_matrix() copy
        per failure batch. The effective free mirrors the sweep's fit
        base: nominated reservations subtracted, the pod's OWN nomination
        handed back."""
        mirror = self._get_mirror()
        free = np.asarray(free_mat[row], np.float32)
        req = np.asarray(self._res_row_cached(pod), np.float32)
        nom = getattr(mirror, "_nominated_req_of_row", {}).get(row)
        if nom is not None:
            free = free - np.asarray(nom, np.float32)
        if pod.status.nominated_node_name \
                and mirror.row_of(pod.status.nominated_node_name) == row:
            free = free + req
        needed = np.maximum(req - free, 0.0)
        freed = np.zeros_like(req)
        rows = {}
        for v in victims:
            rows[v.metadata.uid] = self._res_row_cached(v, freed=True)
            freed = freed + rows[v.metadata.uid]
        kept: list[Pod] = list(victims)
        # most important first: priority desc, oldest first
        for v in sorted(victims,
                        key=lambda q: (-q.priority(),
                                       q.metadata.creation_timestamp)):
            if len(kept) <= 1:
                break
            trial = freed - rows[v.metadata.uid]
            if np.all(trial >= needed):
                freed = trial
                kept.remove(v)
        return kept

    def _collect_victims(self, prio: int, snapshot, mirror, caps):
        """(victims_by_row, cumsum [N, K+1, C], vic_cols [C], cols) for
        preemptors of ``prio``, all host arrays, or None when nothing is
        evictable. The cols back full-width freed-vector expansion
        (find_candidates' dry-run).

        Per-node victims sort ascending by importance (evict
        least-important first): priority asc, then start time desc.
        Nodes with no victims are skipped: the sweep only selects rows
        with 1 <= kmin <= len(victims), and an empty row can never win.

        INCREMENTAL across bursts: per-row victim lists and cumsum rows
        are keyed on each NodeInfo's generation, so a burst 200ms after
        the last one recomputes only the rows commits touched (~2-4% at
        the PreemptionAsync shape) — the full 20k-victim rebuild per burst
        was the dominant preemption host cost. The state lives on the
        host: the batched path reads it there (_host_kmin), and only the
        single-pod device sweep uploads it (find_candidates), so a burst
        pays no device scatter and meets no new scatter bucket to
        compile. The cumsum carries only the columns victims actually
        free (see ops.preempt.preempt_sweep)."""
        st = self._vic_state.get(prio)
        if (st is not None and st["mirror"] is mirror
                and st["n"] == caps.nodes):
            upd = self._update_victims(st, prio, snapshot, mirror)
            if upd is not _REBUILD:
                self._sweep["victim_state_updates"] += 1
                return upd
        self._sweep["victim_state_rebuilds"] += 1
        return self._rebuild_victims(prio, snapshot, mirror, caps)

    def _res_row_of(self, pi) -> np.ndarray:
        """Victim freed-amount row (floored — it adds back to capacity),
        via the (uid, freed=True) cache key space."""
        key = (pi.pod.metadata.uid, True)
        rr = self._res_rows.get(key)
        if rr is None:
            rr = np.asarray(self._get_mirror()._res_row(
                pi.request, capacity=True), np.float32)
            self._res_rows[key] = rr
        return rr

    @staticmethod
    def _victim_sort_key(pi):
        return (pi.pod.priority(), -pi.pod.metadata.creation_timestamp)

    def _state_tuple(self, st):
        if not st["victims_by_row"]:
            return None
        return (st["victims_by_row"], st["cumsum_host"], st["vic_cols"],
                st["cols_np"])

    def _rebuild_victims(self, prio: int, snapshot, mirror, caps):
        victims_by_row = {}
        row_gen: dict[int, int] = {}
        k_max = 0
        for info in snapshot.node_info_list:
            row = mirror.row_of(info.name)
            if row < 0:
                continue
            row_gen[row] = info.generation
            vs = [pi for pi in info.pods if pi.pod.priority() < prio]
            if not vs:
                continue
            vs.sort(key=self._victim_sort_key)
            victims_by_row[row] = vs
            k_max = max(k_max, len(vs))
        if self._res_rows_mirror is not mirror:
            self._res_rows.clear()
            self._res_rows_mirror = mirror
        if len(self._res_rows) > 200_000:
            self._res_rows.clear()
        if k_max == 0:
            st = {"mirror": mirror, "n": caps.nodes, "row_gen": row_gen,
                  "victims_by_row": {}, "k_cap": 0, "cols": (),
                  "cols_np": None, "pods_pos": 0, "c_pad": 0,
                  "incols_mask": None, "cumsum_host": None,
                  "vic_cols": None}
            self._save_vic_state(prio, st)
            return None
        # k headroom (min 8): commits between bursts add victims per row;
        # a k_cap growth reshapes the cumsum and recompiles the sweep
        # program mid-phase, which the headroom absorbs
        k_cap = 8
        while k_cap < k_max:
            k_cap *= 2
        # cumulative freed request per victim prefix (vectorized: the
        # per-victim python accumulation was the preemption hot spot at
        # 20k victims — one np.cumsum per node + a uid-keyed res-row cache)
        n = caps.nodes
        res_rows = self._res_rows
        # one flat [V_total, R] stack of every victim's res row, in
        # (node, victim-rank) order — the cumsum/scatter below is fully
        # vectorized (the per-row numpy loop was ~40% of burst host time
        # at 5k nodes)
        flat_rows: list[np.ndarray] = []
        row_ids = np.empty((len(victims_by_row),), np.int64)
        k_arr = np.empty((len(victims_by_row),), np.int64)
        for i, (row, vs) in enumerate(victims_by_row.items()):
            row_ids[i] = row
            k_arr[i] = len(vs)
            for pi in vs:
                key = (pi.pod.metadata.uid, True)
                rr = res_rows.get(key)
                if rr is None:
                    rr = np.asarray(mirror._res_row(
                        pi.request, capacity=True), np.float32)
                    res_rows[key] = rr
                flat_rows.append(rr)
        stacked_all = np.stack(flat_rows)                     # [V, R]
        active = set(np.nonzero(stacked_all.any(axis=0))[0].tolist())
        active.add(int(F.COL_PODS))
        cols = sorted(active)
        c_pad = 4
        while c_pad < len(cols):
            c_pad *= 2
        pods_pos = cols.index(int(F.COL_PODS))
        cols_np = np.asarray(cols, np.int64)
        # float64 accumulation: the GLOBAL running total over ~20k victims
        # exceeds float32's 2^24 integer-exact range (MiB-scale rows), and
        # cs[take] - base would cancel catastrophically, flipping boundary
        # fit decisions in the sweep; per-node differences cast back to
        # f32 exactly (they're node-local sums, far below 2^24)
        cs = np.cumsum(stacked_all[:, cols_np], axis=0,
                       dtype=np.float64)                      # [V, C]
        offsets = np.concatenate(([0], np.cumsum(k_arr)))[:-1]
        base = np.where((offsets > 0)[:, None],
                        cs[np.maximum(offsets - 1, 0)], 0.0)  # [NR, C]
        j = np.arange(1, k_cap + 1)
        # prefix j clamps to the row's victim count: padding prefixes
        # repeat the full-eviction sum ("no extras")
        jk = np.minimum(j[None, :], k_arr[:, None])           # [NR, K]
        take = offsets[:, None] + jk - 1
        vals = (cs[take] - base[:, None, :]).astype(np.float32)
        vals[..., pods_pos] = jk
        cumsum = np.zeros((n, k_cap + 1, c_pad), np.float32)
        # padding columns alias col 0 in vic_cols; +BIG so they never bind
        cumsum[:, :, len(cols):] = 3.0e38
        cumsum[row_ids, 1:, : len(cols)] = vals
        # padding entries MUST alias an ACTIVE column (cols[0]), never a
        # blanket column 0: aliasing an inactive column would add it to the
        # kernel's col_freed mask (dropping it from the base-only check)
        # while the +BIG padding cumsum makes the subset check vacuous for
        # it — silently deleting that resource constraint from the sweep
        vic_cols = np.full((c_pad,), cols_np[0], np.int32)
        vic_cols[: len(cols)] = cols_np
        incols_mask = np.zeros((stacked_all.shape[1],), bool)
        incols_mask[cols_np] = True
        st = {"mirror": mirror, "n": n, "row_gen": row_gen,
              "victims_by_row": victims_by_row, "k_cap": k_cap,
              "cols": tuple(cols), "cols_np": cols_np,
              "pods_pos": pods_pos, "c_pad": c_pad,
              "incols_mask": incols_mask, "cumsum_host": cumsum,
              "vic_cols": vic_cols}
        self._save_vic_state(prio, st)
        return self._state_tuple(st)

    def _save_vic_state(self, prio: int, st: dict) -> None:
        self._vic_state[prio] = st
        while len(self._vic_state) > 4:     # bound distinct-priority states
            self._vic_state.pop(next(iter(self._vic_state)))

    def _update_victims(self, st: dict, prio: int, snapshot, mirror):
        """Refresh only rows whose NodeInfo generation moved: their
        cumsum slices are rewritten in place. Returns the state tuple
        (or None when nothing is evictable), or _REBUILD when the static
        shape no longer fits (k_cap overflow, a new active resource
        column, node set shrank)."""
        row_gen = st["row_gen"]
        vbr = st["victims_by_row"]
        k_cap = st["k_cap"]
        dirty: list[int] = []
        seen = 0
        for info in snapshot.node_info_list:
            row = mirror.row_of(info.name)
            if row < 0:
                continue
            seen += 1
            g = info.generation
            if row_gen.get(row) == g:
                continue
            vs = [pi for pi in info.pods if pi.pod.priority() < prio]
            if len(vs) > k_cap:
                return _REBUILD
            row_gen[row] = g
            vs.sort(key=self._victim_sort_key)
            if vs:
                vbr[row] = vs
            else:
                vbr.pop(row, None)
            dirty.append(row)
        if seen != len(row_gen):
            # nodes left the snapshot: stale rows would keep serving
            # cumsum entries — rare enough that a rebuild is fine
            return _REBUILD
        if not dirty:
            return self._state_tuple(st)
        if st["cumsum_host"] is None:
            # state was the "nothing evictable" marker; first victims
            # appeared -> allocate via a rebuild
            return _REBUILD
        cols_np, pods_pos = st["cols_np"], st["pods_pos"]
        c_pad, incols = st["c_pad"], st["incols_mask"]
        n_cols = len(cols_np)
        block = np.zeros((len(dirty), k_cap + 1, c_pad), np.float32)
        block[:, :, n_cols:] = 3.0e38
        # vectorized over ALL dirty rows at once (one flat victim stack +
        # segment prefix-sums) — a per-row python loop here cost 100-250ms
        # after a 2048-pod batch dirtied ~40% of the cluster
        flat: list[np.ndarray] = []
        k_arr = np.zeros((len(dirty),), np.int64)
        for i, row in enumerate(dirty):
            vs = vbr.get(row)
            if not vs:
                continue
            k_arr[i] = len(vs)
            for pi in vs:
                flat.append(self._res_row_of(pi))
        if flat:
            stacked = np.stack(flat)                          # [V, R]
            if stacked[:, ~incols].any():
                return _REBUILD     # a victim frees a column the compiled
                                    # sweep doesn't carry
            # float64 accumulation + per-row rebase: see _rebuild_victims
            cs = np.cumsum(stacked[:, cols_np], axis=0,
                           dtype=np.float64)                  # [V, C]
            offsets = np.concatenate(([0], np.cumsum(k_arr)))[:-1]
            base = np.where((offsets > 0)[:, None],
                            cs[np.maximum(offsets - 1, 0)], 0.0)
            j = np.arange(1, k_cap + 1)
            jk = np.minimum(j[None, :], np.maximum(k_arr, 1)[:, None])
            # clamp: a victimless TRAILING dirty row has offset == V, and
            # its jk floor of 1 would index cs[V] out of bounds; the
            # garbage it reads is overwritten by the k_arr==0 zeroing
            take = np.minimum(offsets[:, None] + jk - 1, len(flat) - 1)
            vals = (cs[take] - base[:, None, :]).astype(np.float32)
            vals[..., pods_pos] = jk
            vals[k_arr == 0] = 0.0      # rows whose victims all vanished
            block[:, 1:, :n_cols] = vals
        st["cumsum_host"][dirty] = block
        return self._state_tuple(st)

    def _assemble_candidates(self, pod: Pod, kmin, victims_by_row,
                             snapshot, mirror, free_mat, pdbs,
                             exclude_rows: set | None = None,
                             limit: int | None = None) -> list[Candidate]:
        """kmin rows -> reprieved Candidates, with the reference's
        randomized percentage-bounded sampling (preemption.go:307
        GetOffsetAndNumCandidates). Shared by the single-pod resource_only
        path and batch_preempt so their semantics cannot diverge."""
        rows = [row for row, vs in victims_by_row.items()
                if (exclude_rows is None or row not in exclude_rows)
                and kmin[row] != NONE and 1 <= kmin[row] <= len(vs)]
        if not rows:
            return []
        rows.sort()
        num_nodes = len(snapshot.node_info_list)
        want = max(num_nodes * MIN_CANDIDATE_NODES_PERCENTAGE // 100,
                   MIN_CANDIDATE_NODES_ABSOLUTE)
        if limit is not None:
            want = min(want, limit)
        off = self._rng.randrange(len(rows))
        picked = [rows[(off + i) % len(rows)]
                  for i in range(min(want, len(rows)))]
        out = []
        for row in picked:
            vs = self._reprieve_by_resources(
                [pi.pod for pi in victims_by_row[row][: int(kmin[row])]],
                pod, row, free_mat)
            out.append(Candidate(
                node_name=mirror.name_of_row(row) or "", row=row,
                victims=vs,
                pdb_violations=self._pdb_violations(vs, pdbs)))
        return out

    def begin_batch_preempt(self, jobs, snapshot) -> tuple:
        """Dispatch ONE sweep for a burst of fit-only preemptors of equal
        priority WITHOUT blocking on the device: the kmin results stay
        device-resident until finish_batch_preempt pulls them, so the
        scheduling drain keeps dispatching while the sweep computes
        (the device half of prepareCandidateAsync, kep 4832).

        Returns (handle | None, immediate): ``immediate`` resolves pods
        that never needed a sweep (ineligible, nothing evictable)."""
        self.cache_snapshot = snapshot.node_info_map
        mirror = self._get_mirror()
        caps = self._get_caps()
        immediate: dict[str, tuple] = {}
        eligible = []
        jobs = list(jobs)
        self._sweep["preemptors_swept"] += len(jobs)
        for qp in jobs:
            ok, why = self.pod_eligible_to_preempt_others(qp.pod)
            if ok:
                eligible.append(qp)
            else:
                immediate[qp.uid] = (None, Status.unschedulable(
                    f"not eligible for preemption: {why}",
                    plugin="DefaultPreemption"))
        self._sweep["ineligible"] += len(jobs) - len(eligible)
        if not eligible:
            return None, immediate
        prio = eligible[0].pod.priority()
        prep = self._collect_victims(prio, snapshot, mirror, caps)
        if prep is None:
            self._sweep["no_candidates"] += len(eligible)
            immediate.update(
                {qp.uid: (None, Status.unschedulable(
                    "no preemption candidates",
                    plugin="DefaultPreemption")) for qp in eligible})
            return None, immediate
        victims_by_row = prep[0]
        return (eligible, victims_by_row, self._vic_state[prio], mirror,
                snapshot), immediate

    def _host_static_ok(self, pod: Pod, node_name: str) -> bool:
        """Host mirror of the device pipeline's commit-invariant filters
        (models.pipeline.static_filters) for one (pod, node): validity,
        NodeName, NodeUnschedulable, TaintToleration, NodeAffinity,
        NodePorts. Evaluated lazily on candidate-window rows only."""
        from kubernetes_tpu.api.labels import (
            find_untolerated_taint,
            pod_matches_node_selector_and_affinity,
        )
        from kubernetes_tpu.api.objects import Taint

        info = self.cache_snapshot.get(node_name)
        if info is None or info.node is None:
            return False
        node = info.node
        if pod.spec.node_name and pod.spec.node_name != node_name:
            return False
        taints = list(node.spec.taints)
        if node.spec.unschedulable:
            # the NodeUnschedulable plugin's simulated taint
            from kubernetes_tpu.backend.mirror import TAINT_UNSCHEDULABLE

            taints.append(Taint(key=TAINT_UNSCHEDULABLE, value="",
                                effect="NoSchedule"))
        if find_untolerated_taint(taints, pod.spec.tolerations) is not None:
            return False
        if not pod_matches_node_selector_and_affinity(pod, node):
            return False
        for c in pod.spec.containers:
            for p in c.ports:
                if p.host_port and info.used_ports.conflicts(
                        p.host_ip or "0.0.0.0", p.protocol or "TCP",
                        p.host_port):
                    return False
        return True

    def _host_kmin(self, pod: Pod, st: dict, mirror, free_mat: np.ndarray
                   ) -> np.ndarray:
        """[N] i32 minimal victim-prefix making ``pod`` fit per node,
        NONE where eviction cannot help — the HOST evaluation of
        ops.preempt.preempt_sweep's resource half over the incremental
        cumsum. A few ms of numpy at 8,192 nodes, so finish_batch_preempt
        runs it once per distinct (request, own nominated row) of a
        burst: a device sweep here would queue behind the drain's
        in-flight launches and cost 100-1000ms of wall per burst
        (measured), pure numpy never touches the device.
        Static filters are NOT folded in — the caller checks them lazily
        on visited window rows via _host_static_ok."""
        cumsum = st["cumsum_host"]                    # [N, K+1, C_pad]
        cols_np = st["cols_np"]
        n_cols = len(cols_np)
        base = free_mat.copy()
        nom = getattr(mirror, "_nominated_req_of_row", {})
        for row, vec in nom.items():
            base[row] = base[row] - vec
        req = self._res_row_cached(pod)
        nnn = pod.status.nominated_node_name
        if nnn:
            own = mirror.row_of(nnn)
            if own >= 0:
                base[own] = base[own] + req
        # allocatable bound: rows where the request can never fit
        off, size = mirror.node_codec._f32_off["allocatable"]
        alloc = mirror.node_f32[:, off:off + size]
        unresolvable = (req[None, :] > alloc).any(axis=1)
        col_freed = np.zeros((base.shape[1],), bool)
        col_freed[cols_np] = True
        ok_rest = np.all((req[None, :] <= base) | col_freed[None, :],
                         axis=1)
        eff = base[:, None, cols_np] + cumsum[:, :, :n_cols]
        fit = ok_rest[:, None] & np.all(req[cols_np][None, None, :] <= eff,
                                        axis=2)      # [N, K+1]
        kmin = fit.argmax(axis=1).astype(np.int32)
        ok = fit.any(axis=1) & ~unresolvable
        return np.where(ok, kmin, np.int32(NONE))

    def finish_batch_preempt(self, handle) -> dict:
        """Assign nodes/victims for a burst, entirely host-side: numpy
        kmin over the incremental cumsum, rotation-sampled candidate
        windows (GetOffsetAndNumCandidates, preemption.go:307), lazy
        static filtering, reprieve. Burst-local row exclusion: two
        preemptors never target the same capacity, nor the capacity an
        earlier sweep nominated that the mirror does not reserve yet.
        {uid: (nominated_node | None, Status)}."""
        eligible, victims_by_row, st, mirror, snapshot = handle
        self.cache_snapshot = snapshot.node_info_map
        out: dict[str, tuple] = {}
        free_mat = mirror.free_matrix()
        pdbs = self.hub.list_pdbs()
        self._unreserved = {
            uid: row for uid, row in self._unreserved.items()
            if not mirror.holds_nomination(uid)
            and self.nominator.node_of(uid) is not None}
        used_rows: set[int] = set(self._unreserved.values())
        # kmin reads only the request row and the row of the pod's own
        # nomination (handed back to base): free_mat, the victim state and
        # the nominated overlay stay fixed for this call, and the burst's
        # own nominations act later, through used_rows. So preemptors of
        # equal requests share one read-only kmin and its rows; the memo
        # dies with the call, since free_mat and the overlay move between
        # calls. Everything after it stays per pod, in order.
        kmins: dict[tuple[bytes, int], tuple[np.ndarray, np.ndarray]] = {}
        for qp in eligible:
            nnn = qp.pod.status.nominated_node_name
            key = (self._res_row_cached(qp.pod).tobytes(),
                   mirror.row_of(nnn) if nnn else -1)
            memo = kmins.get(key)
            if memo is None:
                kmin = self._host_kmin(qp.pod, st, mirror, free_mat)
                memo = kmins[key] = (
                    kmin, np.nonzero((kmin != NONE) & (kmin >= 1))[0])
                self._sweep["kmin_computed"] += 1
            else:
                self._sweep["kmin_reused"] += 1
            kmin, rows = memo
            window: list[tuple[int, int]] = []
            if len(rows):
                off = self._rng.randrange(len(rows))
                for i in range(len(rows)):
                    row = int(rows[(off + i) % len(rows)])
                    vs = victims_by_row.get(row)
                    k = int(kmin[row])
                    if (vs is None or row in used_rows or k > len(vs)
                            or not self._host_static_ok(
                                qp.pod, mirror.name_of_row(row) or "")):
                        continue
                    window.append((row, k))
                    if len(window) >= MAX_VERIFY_CANDIDATES:
                        break
            candidates = []
            for row, k in window:
                vs = self._reprieve_by_resources(
                    [pi.pod for pi in victims_by_row[row][:k]],
                    qp.pod, row, free_mat)
                candidates.append(Candidate(
                    node_name=mirror.name_of_row(row) or "", row=row,
                    victims=vs,
                    pdb_violations=self._pdb_violations(vs, pdbs)))
            try:
                candidates = self.call_extenders(qp.pod, candidates)
            except Exception as e:  # noqa: BLE001 — non-ignorable
                # extender failure: abort THIS preemptor's attempt as an
                # error (retried with error backoff), not 'no candidates'
                out[qp.uid] = (None, Status.error(
                    f"preemption extender: {e}",
                    plugin="DefaultPreemption"))
                self._sweep["extender_errors"] += 1
                continue
            if not candidates:
                out[qp.uid] = (None, Status.unschedulable(
                    "no preemption candidates",
                    plugin="DefaultPreemption"))
                self._sweep["no_candidates"] += 1
                continue
            best = self.select_candidate(candidates)
            if self.metrics is not None:
                self.metrics.preemption_attempts.inc()
                self.metrics.preemption_victims.observe(len(best.victims))
            self.prepare_candidate(best, qp.pod)
            self.nominator.add(qp.pod, best.node_name)
            used_rows.add(best.row)
            self._unreserved[qp.uid] = best.row
            out[qp.uid] = (best.node_name, Status())
            self._sweep["nominated"] += 1
        return out

    def batch_preempt(self, jobs, snapshot) -> dict:
        """Synchronous begin+finish (the pre-async path and tests),
        timed into select_s."""
        t0 = time.perf_counter()
        try:
            handle, immediate = self.begin_batch_preempt(jobs, snapshot)
            if handle is not None:
                immediate.update(self.finish_batch_preempt(handle))
            return immediate
        finally:
            self.select_s += time.perf_counter() - t0

    # ---------------- the whole PostFilter flow ----------------

    def host_preempt(self, pod: Pod, snapshot) -> tuple[str | None, Status]:
        """Rung-bottom SERIAL preemption (ISSUE 15): pure host-side
        candidate selection + the queued eviction path, for the fallback
        ladder's bottom rung — a fully device-dead scheduler used to PARK
        preemptors (the device sweep was the only candidate source), so
        it could never free capacity. Covers the static-predicate +
        resource-fit subset over the snapshot; topology preemptors stay
        parked for the device retry (the host path cannot evaluate their
        terms). Victim ordering and candidate selection reuse the
        evaluator's exact keys (_victim_sort_key, candidate_key), so
        where both paths apply they pick the same node."""
        from kubernetes_tpu.api.labels import (
            find_untolerated_taint,
            pod_matches_node_selector_and_affinity,
        )
        from kubernetes_tpu.api.resources import pod_request

        self.cache_snapshot = snapshot.node_info_map
        ok, why = self.pod_eligible_to_preempt_others(pod)
        if not ok:
            return None, Status.unschedulable(
                f"not eligible for preemption: {why}",
                plugin="DefaultPreemption")
        req = pod_request(pod)
        prio = pod.priority()
        pdbs = self.hub.list_pdbs()
        candidates: list[Candidate] = []
        for ni in snapshot.node_info_list:
            node = ni.node
            if node is None or node.spec.unschedulable:
                continue
            if not pod_matches_node_selector_and_affinity(pod, node):
                continue
            if find_untolerated_taint(node.spec.taints,
                                      pod.spec.tolerations) is not None:
                continue
            lower = sorted((pi for pi in ni.pods
                            if pi.pod.priority() < prio),
                           key=self._victim_sort_key)
            if not lower:
                continue
            alloc = ni.allocatable
            free_cpu = alloc.milli_cpu - ni.requested.milli_cpu
            free_mem = alloc.memory - ni.requested.memory
            free_eph = (alloc.ephemeral_storage
                        - ni.requested.ephemeral_storage)
            free_scalar = {k: alloc.scalar.get(k, 0)
                           - ni.requested.scalar.get(k, 0)
                           for k in set(alloc.scalar)
                           | set(ni.requested.scalar)
                           | set(req.scalar)}
            victims: list[Pod] = []

            def _fits() -> bool:
                if (alloc.allowed_pod_number > 0
                        and len(ni.pods) - len(victims) + 1
                        > alloc.allowed_pod_number):
                    return False
                return (req.milli_cpu <= free_cpu
                        and req.memory <= free_mem
                        and req.ephemeral_storage <= free_eph
                        and all(v <= free_scalar.get(k, 0)
                                for k, v in req.scalar.items()))

            # minimal prefix, least-important victims first (the resource
            # fixed point of remove-all-then-reprieve)
            for pi in lower:
                if _fits():
                    break
                victims.append(pi.pod)
                free_cpu += pi.request.milli_cpu
                free_mem += pi.request.memory
                free_eph += pi.request.ephemeral_storage
                for k, v in pi.request.scalar.items():
                    free_scalar[k] = free_scalar.get(k, 0) + v
            if not _fits():
                continue
            if not victims:
                continue        # fits with no eviction: not a preemption
            candidates.append(Candidate(
                node_name=ni.name, row=-1, victims=victims,
                pdb_violations=self._pdb_violations(victims, pdbs)))
        best = self.select_candidate(candidates)
        if best is None:
            return None, Status.unschedulable(
                "no preemption candidates (host mini-path)",
                plugin="DefaultPreemption")
        if self.metrics is not None:
            self.metrics.preemption_attempts.inc()
            self.metrics.preemption_victims.observe(len(best.victims))
        self.prepare_candidate(best, pod)
        self.nominator.add(pod, best.node_name)
        return best.node_name, Status()

    def preempt(self, pod: Pod, snapshot,
                reject_counts=None,
                host_rejects=None) -> tuple[str | None, Status]:
        self.cache_snapshot = snapshot.node_info_map
        ok, why = self.pod_eligible_to_preempt_others(pod)
        if not ok:
            return None, Status.unschedulable(
                f"not eligible for preemption: {why}",
                plugin="DefaultPreemption")
        # fit-only rejection => the resource sweep alone is exact
        from kubernetes_tpu.models.pipeline import FILTER_PLUGINS

        fit_idx = FILTER_PLUGINS.index("NodeResourcesFit")
        resource_only = (
            reject_counts is not None and not host_rejects
            and all(c == 0 for i, c in enumerate(reject_counts)
                    if i != fit_idx))
        candidates = self.find_candidates(pod, snapshot,
                                          resource_only=resource_only)
        pdbs = self.hub.list_pdbs()
        extenders = self.extenders_fn() if self.extenders_fn else []
        has_preempt_ext = any(
            ext.supports_preemption and ext.is_interested(pod)
            for ext in extenders)
        if has_preempt_ext and not resource_only:
            # the reference runs callExtenders AFTER the dry-run's
            # reprieve (preemption.go:335): minimize candidates first so
            # extenders see — and freeze — MINIMAL victim lists. Bounded
            # to MAX_VERIFY_CANDIDATES best-first (the selection order),
            # not positionally: minimization costs device launches, and
            # find_candidates can return one candidate per feasible row
            candidates = sorted(
                candidates,
                key=Evaluator.candidate_key)[:MAX_VERIFY_CANDIDATES]
            candidates = [m for c in candidates
                          if (m := self._minimize_victims(pod, c,
                                                          pdbs)) is not None]
        try:
            candidates = self.call_extenders(pod, candidates)
        except Exception as e:  # noqa: BLE001 — non-ignorable extender
            return None, Status.error(f"preemption extender: {e}",
                                      plugin="DefaultPreemption")
        for _ in range(min(len(candidates), MAX_VERIFY_CANDIDATES)):
            best = self.select_candidate(candidates)
            if best is None:
                break
            if resource_only or best.victims_final:
                final = best        # sweep-exact / extender-final lists:
                                    # already verified (minimized above or
                                    # resource-checked in call_extenders)
            else:
                final = self._minimize_victims(pod, best, pdbs)
            if final is not None:
                if self.metrics is not None:
                    self.metrics.preemption_attempts.inc()
                    self.metrics.preemption_victims.observe(
                        len(final.victims))
                self.prepare_candidate(final, pod)
                self.nominator.add(pod, final.node_name)
                return final.node_name, Status()
            candidates = [c for c in candidates if c is not best]
        return None, Status.unschedulable(
            "no preemption candidates", plugin="DefaultPreemption")


class DefaultPreemption(PostFilterPlugin, PreEnqueuePlugin):
    """PostFilter plugin wrapper (default_preemption.go:133) + the
    PreEnqueue gate (:146): while a pod's async preemption is in flight it
    must not re-enter the activeQ — it would just fail again against a
    node whose victims haven't finished going away."""

    NAME = "DefaultPreemption"

    def __init__(self, evaluator: Evaluator):
        self.evaluator = evaluator

    def name(self) -> str:
        return self.NAME

    def pre_enqueue(self, pod: Pod) -> Status:
        if pod.metadata.uid in self.evaluator.preempting:
            return Status.unschedulable(
                "waiting for the preemption for this pod to be finished",
                plugin=self.NAME, resolvable=False)
        return Status()

    def post_filter(self, state, pod: Pod, diagnosis
                    ) -> tuple[str | None, Status]:
        snapshot = diagnosis.get("snapshot") if diagnosis else None
        if snapshot is None:
            return None, Status.unschedulable("no snapshot in diagnosis",
                                              plugin=self.NAME)
        return self.evaluator.preempt(
            pod, snapshot,
            reject_counts=diagnosis.get("reject_counts"),
            host_rejects=diagnosis.get("host_rejects"))
