"""The Scheduler: event handlers + the batched scheduling loop.

Equivalent of /root/reference/pkg/scheduler/scheduler.go (Scheduler struct,
New, Run) + eventhandlers.go:366 (addAllEventHandlers) + the hot path of
schedule_one.go — with the per-pod serial cycle replaced by the batched
device pipeline: pop a BATCH from the activeQ, refresh the incremental HBM
mirror, run ONE fused filter+score+select launch for the whole batch
(as-if-serial commit scan on device), then assume/reserve/permit/bind each
winner on host and requeue the losers with plugin-attributed diagnoses.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.api.objects import (
    LABEL_POD_GROUP,
    Node,
    Pod,
    PodCondition,
    pod_group_key,
)
from kubernetes_tpu.backend.cache import Cache
from kubernetes_tpu.backend.jobqueue import JobQueue
from kubernetes_tpu.backend.mirror import (
    MI,
    CapacityError,
    Mirror,
)
from kubernetes_tpu.backend.nominator import Nominator
from kubernetes_tpu.backend.queue import PriorityQueue, QueuedPodInfo
from kubernetes_tpu.backend.snapshot import Snapshot
from kubernetes_tpu.framework.preemption import Evaluator
from kubernetes_tpu.config.types import (
    SchedulerConfiguration,
    default_config,
)
from kubernetes_tpu.framework.cycle_state import CycleState
from kubernetes_tpu.framework.interface import (
    ActionType,
    ClusterEvent,
    EventResource,
)
from kubernetes_tpu.framework.runtime import Framework
from kubernetes_tpu.framework.interface import Code
from kubernetes_tpu.framework.waiting import WaitingPod
from kubernetes_tpu import telemetry
from kubernetes_tpu.hub import EventHandlers, Fenced, Hub, Unavailable
from kubernetes_tpu.storage import RvTooOld
from kubernetes_tpu.utils.backoff import Backoff
from kubernetes_tpu.utils.gcguard import guard as gc_guard
from kubernetes_tpu.utils.tracing import FlightRecorder, PodTimelines
from kubernetes_tpu.models.pipeline import (
    ADAPTIVE_PCT,
    ALT_NONE,
    FILTER_PLUGINS,
    BatchResult,
    extract_state_jit,
    launch_batch,
    patch_chain,
    scan_steps_for,
    table_blocks_for,
    warm_patch_chain,
)
from kubernetes_tpu.metrics import AsyncRecorder, SchedulerMetrics
from kubernetes_tpu.ops.features import COL_PODS, Capacities

logger = logging.getLogger("kubernetes_tpu.scheduler")

# a scheduling cycle slower than this logs a phase-by-phase trace
# (schedule_one.go:404's 100ms slow-attempt threshold)
SLOW_CYCLE_SECONDS = 0.1

# outstanding chained launches in run_until_idle's software pipeline: 2 =
# commit batch k-1 while launches k and k+1 queue on the device, which
# hides the device wait entirely when host commit time ~ device time
PIPELINE_DEPTH = 2

# chain-surviving churn bounds: above CHAIN_PATCH_MAX pending patches a
# full resync is cheaper than the scatter (and the pow2 patch buckets are
# pre-warmed only up to this cap — see warm_patch_chain); after
# CHAIN_DELTA_RESYNC accumulated per-pod delta applications the chain is
# resynced once for float hygiene (per-pod rounded-up f32 requests only
# ever UNDERSTATE free, but a delete re-credits at most 1 ulp more than
# the add took for non-representable quantities — bound the drift)
CHAIN_PATCH_MAX = 256
CHAIN_DELTA_RESYNC = 100_000

# poison-pod quarantine: a pod in this many faulted batches (or raising
# in its own serial host-fallback evaluation) is parked out of the
# scheduling population with escalating backoff instead of wedging peers
QUARANTINE_STRIKES = 3
QUARANTINE_BASE_S = 5.0
QUARANTINE_CAP_S = 300.0

# brownout (overload self-protection, entered by _evaluate_brownout):
# the effective batch shrinks to
# max(batch_size // BROWNOUT_BATCH_DIVISOR, BROWNOUT_BATCH_FLOOR) (never
# above the configured batch), the drift sentinel stretches its cadence
# by BROWNOUT_DRIFT_STRETCH, and best-effort tenants (weight <
# BROWNOUT_BESTEFFORT_WEIGHT) are parked in the jobqueue
BROWNOUT_BATCH_DIVISOR = 4
BROWNOUT_BATCH_FLOOR = 8
BROWNOUT_DRIFT_STRETCH = 4.0
BROWNOUT_BESTEFFORT_WEIGHT = 0.25


class DeviceFault(RuntimeError):
    """The fused device launch produced untrustworthy output (guard
    reduction tripped: NaN scores or a poisoned usage state). Raised by
    ``_finish`` before any commit; contained by the fallback ladder."""

A = ActionType
R = EventResource


def _node_update_action(old: Node, new: Node) -> ActionType:
    """Which parts of the node changed (eventhandlers.go nodeSchedulingPropertiesChange)."""
    action = ActionType(0)
    if old.metadata.labels != new.metadata.labels:
        action |= A.UPDATE_NODE_LABEL
    if old.spec.taints != new.spec.taints \
            or old.spec.unschedulable != new.spec.unschedulable:
        action |= A.UPDATE_NODE_TAINT
    if old.status.allocatable != new.status.allocatable:
        action |= A.UPDATE_NODE_ALLOCATABLE
    return action or A.UPDATE_NODE_CONDITION


class Scheduler:
    def __init__(self, hub: Hub,
                 config: Optional[SchedulerConfiguration] = None,
                 caps: Optional[Capacities] = None,
                 now=time.time, registry=None, mesh=None):
        self.hub = hub
        self.config = config or default_config()
        self.now = now
        profile = self.config.profiles[0]
        self._profile_name = profile.scheduler_name
        self.cache = Cache(now=now)
        self.snapshot = Snapshot()
        self.caps = caps or Capacities(
            nodes=self.config.node_capacity,
            pods=self.config.pod_table_capacity)
        # multi-chip: a jax.sharding.Mesh with a 'nodes' axis shards the
        # resident node table row-wise (SURVEY §5.7/§5.8); every device
        # launch this scheduler makes — batched pipeline, usage chain,
        # preemption sweeps — then runs SPMD over the mesh, placements
        # bit-identical to single-device (tests/test_multichip.py).
        self.mesh = mesh
        self.mirror = Mirror(caps=self.caps, mesh=mesh)
        # fencing: set by run()/start() when an elector gates the loop;
        # every bind/status-patch then carries the elector's epoch so a
        # deposed incarnation's in-flight writes are rejected (Fenced)
        self._elector = None
        # per-binder-thread fencing context: the epoch a bind carries is
        # captured when the bind is SUBMITTED, not when it executes — a
        # deposed-then-re-elected leader must not launder a stale
        # placement through its newer epoch
        self._bind_fence = threading.local()
        # chaos seam: a DeviceChaos (kubernetes_tpu.chaos) hooks the
        # pack/launch path here to provoke the fallback ladder under test
        self.fault_injector = None
        # repr of the newest exception the containment ladder swallowed:
        # measurement paths (perf/harness, chip_smoke) refuse a run that
        # left the device path and print this instead of carrying on
        self.last_device_fault: Optional[str] = None
        self.nominator = Nominator()
        self.preemption = Evaluator(
            hub, lambda: self.mirror, lambda: self.caps,
            self._filters_for, self.nominator)
        from kubernetes_tpu.plugins.dra import DynamicResources
        from kubernetes_tpu.plugins.gang import GangScheduling

        self._dra = DynamicResources(hub)
        # the gang coordinator is shared across profiles like the DRA
        # manager: quorum counting must see every profile's reservations
        self._gang = GangScheduling(hub=hub,
                                    mirror_fn=lambda: self.mirror,
                                    now=now)
        # the multi-tenant job-queue layer in front of the activeQ; pods
        # without tenant/gang labels never touch it (jobqueue.active
        # gates the per-cycle release step)
        self.jobqueue = JobQueue(self.config.tenants, now=now,
                                 bound_fn=self._gang.bound_count)
        extra = {"binder": self._fenced_bind, "hub": hub,
                 "preemption_evaluator": self.preemption,
                 # shared across profiles (SharedDRAManager analog): one
                 # assume overlay must see every profile's allocations
                 "dra_shared": self._dra,
                 "gang_shared": self._gang}
        # one resolved framework per profile (profile/profile.go:47 Map);
        # frameworkForPod routes each pod by spec.schedulerName
        self.frameworks = {
            p.scheduler_name: Framework(p, registry=registry,
                                        extra_args=extra)
            for p in self.config.profiles}
        self.framework = self.frameworks[profile.scheduler_name]
        # one shared queue: QueueSort must agree across profiles (the
        # reference validates this); PreEnqueue gates run through the POD's
        # profile, queueing-hint registrations merge across profiles
        merged_hints = {}
        for fw in self.frameworks.values():
            merged_hints.update(fw.events_to_register())
        if not self.config.gate("SchedulerQueueingHints"):
            # gate off: keep the event registrations but drop the hint fns
            # — any matching event requeues (pre-hints upstream behavior)
            from kubernetes_tpu.framework.interface import (
                ClusterEventWithHint,
            )

            merged_hints = {
                name: [ClusterEventWithHint(event=r.event) for r in regs]
                for name, regs in merged_hints.items()}
        self.queue = PriorityQueue(
            less_fn=self.framework.queue_sort_less,
            sort_key_fn=self.framework.queue_sort_key,
            pre_enqueue=lambda pod: self._fw_for(
                pod).run_pre_enqueue_plugins(pod),
            queueing_hints=merged_hints,
            initial_backoff=self.config.pod_initial_backoff_seconds,
            max_backoff=self.config.pod_max_backoff_seconds,
            now=now)
        for fw in self.frameworks.values():
            self._gang.register_waiting_map(fw.waiting_pods)
        self.metrics = SchedulerMetrics(
            pending_fn=self.queue.pending_counts)
        self._gang.metrics = self.metrics
        # fenced evictions/nomination-clears: the evaluator's queued hub
        # writes carry the epoch their flush runs under, so a deposed
        # leader's backlog is rejected instead of landing after failover
        self.preemption.fencing_fn = self._fencing_args
        self.preemption.fenced_metric = (
            lambda verb: self.metrics.fenced_writes.inc(verb=verb))
        # the always-on flight recorder: every cycle's fine-grained
        # phases into a bounded ring + the phase/plugin histograms
        # (utils/tracing.FlightRecorder); per-pod lifecycle timelines
        # behind /debug/pod. flight_recorder_capacity=0 disables.
        self.flight = FlightRecorder(
            phase_hist=self.metrics.phase_duration,
            plugin_hist=self.metrics.plugin_duration,
            capacity=getattr(self.config, "flight_recorder_capacity", 256),
            export_path=getattr(self.config, "trace_export_path", None),
            export_max_bytes=getattr(self.config,
                                     "trace_export_max_bytes", 0),
            now=now, gc_pause_hist=self.metrics.gc_pause)
        self.timelines = PodTimelines(
            capacity=getattr(self.config, "timelines_capacity", 4096),
            now=now)
        # placement FEATURE export (the replay-training substrate) is
        # opt-in on top of the export itself: phase-timing export users
        # must not pay the feature kernels + extra D2H + line growth
        self._export_feats = (self.flight.exporting and getattr(
            self.config, "trace_export_features", False))
        # placement ALTERNATIVE export (top-K candidate node scores, the
        # regret counterfactual substrate): same opt-in discipline — it
        # compiles a [B, K] top_k into every launch and rides the
        # existing per-cycle pull
        self._export_alts = (self.flight.exporting and getattr(
            self.config, "trace_export_alts", False))
        if self.flight.enabled:
            for fw in self.frameworks.values():
                fw.plugin_timer = self.flight.plugin_observe
            # every collector pause, from whichever thread it ran on,
            # as scheduler_gc_pause_seconds and the gc_pause view
            gc_guard.watch(self.flight)
        # the device-launch profiler (telemetry.profiler): XLA compiles
        # per bucket shape, recompile attribution to re-bucket churn,
        # per-shape walltime, live HBM buffer bytes. Rides the flight
        # recorder's enable switch — one observability budget.
        self.profiler = None
        if self.flight.enabled:
            from kubernetes_tpu.telemetry.profiler import DeviceProfiler

            self.profiler = DeviceProfiler(metrics=self.metrics, now=now)
        # optional fleet collector (telemetry.fleet.FleetView) attached
        # by the operator/harness; serving exposes /debug/fleet and the
        # merged /metrics/fleet exposition when set
        self.fleet = None
        # SLO watchdog + incident autopsy (telemetry/watchdog.py,
        # telemetry/autopsy.py): breach rules polled at the end of every
        # maintenance window; containment sites raise incidents directly
        # through telemetry.incident(). The watchdog always runs (a
        # handful of comparisons per window); black-box bundle capture
        # needs config.autopsy_dir.
        from kubernetes_tpu.telemetry.watchdog import Watchdog

        self.autopsy = None
        _autopsy_dir = getattr(self.config, "autopsy_dir", None)
        if _autopsy_dir:
            from kubernetes_tpu.telemetry.autopsy import AutopsyStore

            self.autopsy = AutopsyStore(
                _autopsy_dir,
                rate_limit_s=getattr(self.config,
                                     "autopsy_rate_limit_s", 30.0),
                now=now, metrics=self.metrics)
        self.watchdog = Watchdog(
            self, store=self.autopsy,
            interval_s=getattr(self.config, "watchdog_interval_s", 5.0),
            now=now)
        # gate opener of last resort: a flush that deleted nothing (empty
        # or already-gone victim sets) fires no cluster event, so the
        # evaluator re-activates those preemptors directly
        self.preemption.activate_fn = self.queue.activate
        self.recorder = AsyncRecorder(now=now)
        self.preemption.metrics = self.metrics
        # per-profile launch configuration
        self._profile_cfg = {
            name: {"filters": fw.enabled_filters(),
                   "weights": fw.score_weights(),
                   "fit": fw.fit_scoring(),
                   # the batched fit-only preemption fast path is only
                   # semantics-preserving when DefaultPreemption is the
                   # profile's ONLY PostFilter plugin
                   "batch_preempt_ok": [n for n, _ in
                                        fw.points["post_filter"]]
                   == ["DefaultPreemption"],
                   # fused device DRA allocation only applies to profiles
                   # that enable the DynamicResources filter — a profile
                   # with it disabled must keep scheduling claim pods
                   # unfiltered, exactly as the host path did
                   "dra_filter": "DynamicResources" in {
                       n for n, _ in fw.points["filter"]},
                   # the profile-gated learned scorer's checkpoint
                   # manager (plugins/learned.py); None unless the
                   # profile enables the LearnedScore plugin — the
                   # launch then compiles the MLP term out entirely
                   "learned": fw.instance("LearnedScore"),
                   # device gang packing only engages for profiles that
                   # run the GangScheduling plugin at all — without it
                   # gang labels are inert and members are plain pods
                   "gang_plugin": any(
                       n == "GangScheduling"
                       for pt in ("filter", "permit")
                       for n, _ in fw.points[pt])}
            for name, fw in self.frameworks.items()}
        # device-side gang packing (ops/gang.pack_gangs): whole PodGroups
        # placed in one fused launch; off = every gang takes the host
        # Permit-quorum path (the differential-test arm)
        self._gang_device = bool(getattr(
            self.config, "gang_device_packing", True))
        # explicit tie-break seed (config) threaded into every launch as
        # a DYNAMIC scalar: paired A/B runs share a seed so placement
        # diffs attribute to the scorer, not the coin; 0 = historical
        self._tie_seed = np.uint32(
            getattr(self.config, "tie_break_seed", 0))
        self._enabled_filters = self.framework.enabled_filters()
        from kubernetes_tpu.extender import HTTPExtender

        self._extenders = [HTTPExtender(c) for c in self.config.extenders]
        # preemption candidates pass through ProcessPreemption
        # (preemption.go:335 callExtenders)
        self.preemption.extenders_fn = lambda: self._extenders
        self._has_host_filters = any(fw.has_host_filters()
                                     for fw in self.frameworks.values())
        gates = [fw.host_gates() for fw in self.frameworks.values()]
        self._host_gates = (None if any(g is None for g in gates)
                            else [g for gs in gates for g in gs])
        self._has_host_scores = any(fw.has_host_scores()
                                    for fw in self.frameworks.values())
        sgates = [fw.host_score_gates() for fw in self.frameworks.values()]
        self._host_score_gates = (None if any(g is None for g in sgates)
                                  else [g for gs in sgates for g in gs])
        # pods popped but deferred to a later batch (host-serial volume
        # conflicts — see _defer_host_conflicts); still in-flight queue-wise
        self._deferred: list[QueuedPodInfo] = []
        self.stats = {"scheduled": 0, "unschedulable": 0, "errors": 0,
                      "batches": 0, "attempts": 0,
                      "parked_unreachable": 0, "fenced": 0,
                      "device_fallbacks": 0, "quarantined": 0,
                      "drift_repairs": 0, "drift_full_lists": 0,
                      "drift_incremental": 0,
                      "gang_device_launches": 0, "gang_fallbacks": 0,
                      "slice_rebalances": 0, "foreign_stashed": 0,
                      "foreign_adopted": 0,
                      "brownout_enters": 0, "brownout_exits": 0,
                      "chain_patches": 0, "chain_patch_rows": 0,
                      "chain_patch_fallbacks": 0}
        # horizontal scale-out: when run() is handed a SliceManager the
        # replica drains only pods whose namespace (gang: the GROUP's
        # namespace) hashes into its owned ring slots. Everything else
        # waits in the foreign pen — cheap Pod refs, no queue/cache
        # residency — until a rebalance re-homes the slice here or the
        # true owner binds it. None = single-replica mode, zero filter.
        self._slices = None
        self._slice_gen = -1
        self._foreign: dict[str, Pod] = {}
        # poison-pod quarantine: uid -> {"qp", "until", "reason"};
        # strike/quarantine counts survive release so a re-offender's
        # backoff keeps escalating
        self._quarantine: dict[str, dict] = {}
        self._fault_strikes: dict[str, int] = {}
        self._quarantine_counts: dict[str, int] = {}
        # drift sentinel cadence (0 disables); strikes gate the
        # full-rebuild last resort. _drift_rv is the journal revision
        # the last report was consistent at: steady-state passes diff
        # O(changes) after it instead of re-LISTing the cluster, and
        # fall back to the full diff only on RvTooOld (compacted gap)
        self.drift_check_interval = 30.0
        self._last_drift_check = 0.0
        self._drift_strikes = 0
        self._drift_rv: int | None = None
        # scheduler brownout (overload self-protection): a sustained run
        # of hub flow-control rejections (429s — the hub's queue-wait
        # SLO breaches surface as rejected-timeout 429s through the same
        # counter) trips a load-shedding mode: the effective batch
        # shrinks, the drift sentinel stretches its cadence, and
        # best-effort tenants park in the jobqueue. Exits after
        # brownout_clear_windows consecutive clean ~1s windows.
        self.brownout = False
        self._brownout_clean = 0
        self._brownout_throttled_seen = 0.0
        self._last_brownout_eval = 0.0
        self._drift_interval_base: float | None = None
        # degraded mode: the hub is unreachable (transport Unavailable).
        # Work parks with backoff instead of erroring; assumed pods are
        # preserved (their confirm events cannot arrive); the informer's
        # relist diff re-converges everything after reconnect.
        self._hub_down = False
        # expired assumed pods awaiting their requeue check (the hub may
        # be unreachable when they expire; see _drain_assumed_requeue)
        self._assumed_requeue: list[Pod] = []
        # device-resident (free, nonzero_requested) chain: the post-launch
        # usage state of the NEWEST dispatched launch. While no external
        # event has touched the cluster state, the next no-topology batch can
        # launch against this chain WITHOUT a host snapshot/mirror re-sync —
        # the batched analog of the cache staying hot between cycles
        # (cache.go:361 assume). Any event not caused by our own commits
        # invalidates it (set to None) and forces a full re-sync.
        self._chain: Optional[tuple] = None
        self._chain_epoch = 0
        # pipelined scheduling waves (config.pipelined_waves): chain
        # patching + off-thread commit + immediate preemptor re-dispatch.
        # Off = the strict-alternation differential arm.
        self._pipelined = bool(getattr(config, "pipelined_waves", True))
        # chain-surviving churn bookkeeping. Instead of invalidating the
        # device chain on every informer event, handlers register the
        # event's EFFECT and the next dispatch scatters it into the chain
        # (models/pipeline.patch_chain): _chain_dirty names nodes whose
        # row must be absolutely repacked from the live cache (node
        # add/update/delete — applied after in-flight waves flush, the
        # conservative form of "touched node intersects an in-flight
        # wave's packed set"); _chain_deltas accumulates commutative
        # (d_free, d_nzr) per node from foreign pod binds/deletes —
        # deltas compose with in-flight device commits in either order,
        # so they need NO flush. Both clear on invalidate and right
        # after a full mirror sync (which subsumes them).
        self._chain_dirty: set[str] = set()
        self._chain_deltas: dict[str, list[np.ndarray]] = {}
        self._chain_delta_count = 0
        self._patch_warmed = False
        # off-thread commit: wave N's blocking D2H pull runs on this
        # one-thread pool so it overlaps wave N+1's device time. The
        # commit thread does ONLY jax.device_get (+ the chaos seam) —
        # host mutation (assume/bind/queue/timeline) stays on the
        # single-mutator loop thread, preserving the _wrap threading
        # model; exceptions surface in _finish via fut.result() and ride
        # the existing _finish_contained blast-radius ladder.
        self._commit_pool: Optional[ThreadPoolExecutor] = (
            ThreadPoolExecutor(max_workers=1, thread_name_prefix="commit")
            if self._pipelined else None)
        # preemptor re-probes ride the next wave: after an eviction flush
        # fires, nominated reservations already protect the slots, so the
        # evaluator re-activates the flushed preemptors immediately
        # instead of letting them wait out backoff until victim-deletion
        # events land (framework/preemption.Evaluator.flush_evictions)
        self.preemption.activate_flushed = self._pipelined
        # preemption dry-runs read the LIVE chain when one exists: under
        # pipelining the mirror's host free matrix lags by the in-flight
        # waves, and a dry-run against it would over-evict
        self.preemption.live_free_fn = (
            lambda: self._chain[0] if (self._pipelined
                                       and self._chain is not None)
            else None)
        # percentageOfNodesToScore rotating offset, persisted across
        # launches (schedule_one.go:620 nextStartNodeIndex); device scalar
        self._pct_start = None
        # threading model: ONE mutator thread at a time. The coarse lock
        # serializes the scheduling loop against event handlers invoked from
        # foreign threads; the binder pool's own hub writes dispatch events
        # back into _deferred_events instead (processed on the loop thread),
        # so waiting on a bind future while holding the lock cannot deadlock.
        self._lock = threading.RLock()
        self._binder: Optional[ThreadPoolExecutor] = None
        self._binder_tids: set[int] = set()
        if self.config.async_binding:
            self._binder = ThreadPoolExecutor(
                max_workers=self.config.binding_workers,
                thread_name_prefix="binder",
                initializer=lambda: self._binder_tids.add(
                    threading.get_ident()))
        self._inflight_binds: list[tuple] = []
        self._bind_backlog: list[tuple] = []
        self._pod_rv: dict[str, int] = {}   # newest applied pod revision
        self._rv_tombstones: deque = deque()
        self._deferred_events: deque = deque()
        self._last_backoff_flush = 0.0
        self._last_unsched_flush = 0.0
        # mirrored-counter watermarks: external monotonic counts (hub
        # client watch resumes/relists, DRA CEL errors) flow into the
        # registry's true Counters by DELTA
        self._mirrored_counts: dict[str, float] = {}
        self._last_journal_mirror = 0.0
        self._daemon: Optional[threading.Thread] = None
        self._stop: Optional[threading.Event] = None
        self._register_handlers()

    # ------------- event handlers (eventhandlers.go:366) -------------

    def _wrap(self, fn):
        """Route events raised by the binder pool's own API writes to the
        deferred queue (replayed on the loop thread); for every other
        caller, apply inline under the scheduler lock when it's free
        and defer when it's contended. Blocking on a contended lock
        here deadlocks scale-out: two in-process replicas share one
        hub, so replica A's bind delivers this event on a thread that
        sits inside A's locked drain while OUR loop holds our lock
        delivering into A — both hands full, neither lets go. The
        deferred queue replays on the loop thread either way; per-pod
        rv dedup absorbs the cross-thread reordering this admits."""
        def handler(*args):
            if threading.get_ident() in self._binder_tids:
                self._deferred_events.append((fn, args))
                return
            if self._lock.acquire(blocking=False):
                try:
                    fn(*args)
                finally:
                    self._lock.release()
            else:
                self._deferred_events.append((fn, args))
        return handler

    def _process_deferred_events(self) -> None:
        while self._deferred_events:
            fn, args = self._deferred_events.popleft()
            fn(*args)

    def _pod_event_stale(self, pod: Pod) -> bool:
        """Hub dispatch happens outside the hub lock, so two threads'
        events for one pod can arrive out of commit order (the binder's
        deferred bind-update vs the loop's own later patch). Drop any
        event older than the newest revision already applied."""
        uid = pod.metadata.uid
        rv = pod.metadata.resource_version
        if rv <= self._pod_rv.get(uid, -1):
            return True
        self._pod_rv[uid] = rv
        return False

    def _register_handlers(self) -> None:
        w = self._wrap
        self.hub.watch_nodes(EventHandlers(
            on_add=w(self._on_node_add),
            on_update=w(self._on_node_update),
            on_delete=w(self._on_node_delete)))
        # pods ride the on_event shape: the full JournalEvent carries
        # the commit's TraceContext, which the timeline join needs (the
        # typed trio would drop it); dedup/relist-diff still apply
        # upstream on both transports
        self.hub.watch_pods(EventHandlers(
            on_event=w(self._on_pod_event)))
        self.hub.watch_namespaces(EventHandlers(
            on_add=w(self._on_ns_set),
            on_update=w(lambda old, new: self._on_ns_set(new)),
            on_delete=w(self._on_ns_delete)))
        # volume objects: pure requeue signals (no device state involved)
        self.hub.watch_pvcs(EventHandlers(
            on_add=w(lambda o: self.queue.move_all_to_active_or_backoff(
                ClusterEvent(R.PVC, A.ADD), None, o)),
            on_update=w(lambda old, new:
                        self.queue.move_all_to_active_or_backoff(
                            ClusterEvent(R.PVC, A.UPDATE), old, new))))
        self.hub.watch_resource_slices(EventHandlers(
            on_add=w(lambda o: self.queue.move_all_to_active_or_backoff(
                ClusterEvent(R.RESOURCE_SLICE, A.ADD), None, o)),
            on_delete=w(lambda o: self.queue.move_all_to_active_or_backoff(
                ClusterEvent(R.RESOURCE_SLICE, A.DELETE), o, None))))
        self.hub.watch_resource_claims(EventHandlers(
            on_add=w(lambda o: self.queue.move_all_to_active_or_backoff(
                ClusterEvent(R.RESOURCE_CLAIM, A.ADD), None, o)),
            on_update=w(lambda old, new:
                        self.queue.move_all_to_active_or_backoff(
                            ClusterEvent(R.RESOURCE_CLAIM, A.UPDATE),
                            old, new)),
            on_delete=w(lambda o: self.queue.move_all_to_active_or_backoff(
                ClusterEvent(R.RESOURCE_CLAIM, A.DELETE), o, None))))
        self.hub.watch_pvs(EventHandlers(
            on_add=w(lambda o: self.queue.move_all_to_active_or_backoff(
                ClusterEvent(R.PV, A.ADD), None, o)),
            on_update=w(lambda old, new:
                        self.queue.move_all_to_active_or_backoff(
                            ClusterEvent(R.PV, A.UPDATE), old, new))))
        self.hub.watch_csi_capacities(EventHandlers(
            on_add=w(lambda o: self.queue.move_all_to_active_or_backoff(
                ClusterEvent(R.CSI_STORAGE_CAPACITY, A.ADD), None, o)),
            on_update=w(lambda old, new:
                        self.queue.move_all_to_active_or_backoff(
                            ClusterEvent(R.CSI_STORAGE_CAPACITY, A.UPDATE),
                            old, new))))
        self.hub.watch_pod_groups(EventHandlers(
            on_add=w(lambda g: self._on_group_set(g, A.ADD)),
            on_update=w(lambda old, new: self._on_group_set(new, A.UPDATE)),
            on_delete=w(self._on_group_delete)))

    def _on_group_set(self, group, action) -> None:
        """A PodGroup arrived/changed: the job queue may now release its
        orphaned members, the gang coordinator refreshes min_member and
        timeout, and parked members get a requeue chance."""
        self.jobqueue.set_group(group)
        self._gang.set_group(group)
        self.queue.move_all_to_active_or_backoff(
            ClusterEvent(R.POD_GROUP, action), None, group)

    def _on_group_delete(self, group) -> None:
        self.jobqueue.remove_group(group.key())
        self._gang.remove_group(group.key())

    def _invalidate_chain(self) -> None:
        """Drop the device-resident usage chain and bump the epoch so a
        dispatch that raced with the invalidation (e.g. a bind failure
        drained while packing) does not re-install a stale chain. Pending
        chain patches die with the chain — the full resync that follows
        subsumes them."""
        self._chain = None
        self._chain_epoch += 1
        self._chain_dirty.clear()
        self._chain_deltas.clear()

    def _chain_note_node(self, name: str) -> None:
        """A node add/update/delete touched the cluster: instead of
        invalidating the chain, mark the node's row for an absolute
        repack from the live cache at next dispatch (chain-surviving
        churn). Falls back to whole-chain invalidation when pipelining is
        off, no chain exists, or the pending patch set outgrows the
        pre-warmed scatter buckets (a resync is cheaper then anyway)."""
        if not self._pipelined or self._chain is None:
            self._invalidate_chain()
            return
        # an absolute repack includes every pod on the node — pending
        # deltas for it are subsumed
        self._chain_deltas.pop(name, None)
        self._chain_dirty.add(name)
        if len(self._chain_dirty) + len(self._chain_deltas) \
                > CHAIN_PATCH_MAX:
            self.stats["chain_patch_fallbacks"] += 1
            self._invalidate_chain()

    def _chain_note_pod(self, pod: Pod, sign: int) -> None:
        """A FOREIGN bound pod appeared (+1) or vanished (-1): accumulate
        its request as a commutative (free, nzr) delta against its node's
        chain row. Deltas compose with in-flight waves' device commits in
        either order (the chain already carries every dispatched commit),
        so unlike node repacks they apply without a pipeline flush. Pods
        with host ports route to the absolute-repack path instead: the
        mirror's port columns must move with them, and a row repack is
        the only operation that does that."""
        if not self._pipelined or self._chain is None:
            self._invalidate_chain()
            return
        node = pod.spec.node_name
        if node in self._chain_dirty:
            return                    # repack at apply time covers it
        from kubernetes_tpu.api.resources import pod_request

        if self.mirror.batch_has_host_ports([pod]):
            self._chain_note_node(node)
            return
        try:
            row = self.mirror._res_row(pod_request(pod)).copy()
        except CapacityError:
            self._invalidate_chain()
            return
        row[COL_PODS] = 1.0
        nz = pod_request(pod, non_zero=True)
        acc = self._chain_deltas.get(node)
        if acc is None:
            acc = self._chain_deltas[node] = [
                np.zeros_like(row), np.zeros((2,), np.float32)]
        # free MOVES OPPOSITE the pod: an added pod consumes its request
        acc[0] -= np.float32(sign) * row
        acc[1] += np.float32(sign) * np.asarray(
            [nz.milli_cpu, nz.memory / MI], np.float32)
        self._chain_delta_count += 1
        if len(self._chain_dirty) + len(self._chain_deltas) \
                > CHAIN_PATCH_MAX \
                or self._chain_delta_count > CHAIN_DELTA_RESYNC:
            self.stats["chain_patch_fallbacks"] += 1
            self._chain_delta_count = 0
            self._invalidate_chain()

    def _on_ns_set(self, ns) -> None:
        self._invalidate_chain()
        self.cache.set_namespace(ns.metadata.name, ns.metadata.labels)

    def _on_ns_delete(self, ns) -> None:
        self._invalidate_chain()
        self.cache.remove_namespace(ns.metadata.name)

    def _on_node_add(self, node: Node) -> None:
        self._chain_note_node(node.metadata.name)
        self.cache.add_node(node)
        self.queue.move_all_to_active_or_backoff(
            ClusterEvent(R.NODE, A.ADD), None, node)

    def _on_node_update(self, old: Node, new: Node) -> None:
        self._chain_note_node(new.metadata.name)
        if old.metadata.name != new.metadata.name:
            self._chain_note_node(old.metadata.name)
        self.cache.update_node(old, new)
        self.queue.move_all_to_active_or_backoff(
            ClusterEvent(R.NODE, _node_update_action(old, new)), old, new)

    def _on_node_delete(self, node: Node) -> None:
        self._chain_note_node(node.metadata.name)
        self.cache.remove_node(node)
        self.queue.move_all_to_active_or_backoff(
            ClusterEvent(R.NODE, A.DELETE), node, None)

    @staticmethod
    def _terminal(pod: Pod) -> bool:
        return pod.status.phase in ("Succeeded", "Failed")

    def _filters_for(self, pod: Pod | None = None) -> tuple[bool, ...]:
        """Enabled device-filter slots for the pod's profile (the
        preemption dry-run must see the same filter set the pod's own
        scheduling cycle uses)."""
        if pod is not None:
            cfg = self._profile_cfg.get(pod.spec.scheduler_name)
            if cfg is not None:
                return cfg["filters"]
        return self._enabled_filters

    def _fw_for(self, pod: Pod) -> Framework:
        """frameworkForPod (schedule_one.go:371): by spec.schedulerName."""
        return self.frameworks.get(pod.spec.scheduler_name, self.framework)

    def _ours(self, pod: Pod) -> bool:
        return pod.spec.scheduler_name in self.frameworks

    def _owns_pod(self, pod: Pod) -> bool:
        """Scale-out slice filter: does this replica's owned ring slice
        cover the pod? Single-replica mode (no SliceManager) owns
        everything. Gang members hash by their GROUP's namespace —
        ``pod_group_key`` is ``namespace/name``, and members share the
        group's namespace — so a gang can never straddle replicas."""
        sm = self._slices
        if sm is None:
            return True
        gang = pod_group_key(pod)
        ns = (gang.split("/", 1)[0] if gang is not None
              else pod.metadata.namespace)
        return sm.owns_namespace(ns)

    def _stash_foreign(self, pod: Pod) -> None:
        """Pen a pending pod another replica owns: dropped from our
        queues (it may have been ours before a rebalance), kept as a
        bare Pod ref so a later rebalance can adopt it without a
        relist. The pen self-cleans on bind/delete events."""
        uid = pod.metadata.uid
        self._foreign[uid] = pod
        self.queue.delete(pod)
        self.nominator.delete(uid)
        if self.jobqueue.active and self.jobqueue.holds(uid):
            self.jobqueue.remove(pod)
        self.stats["foreign_stashed"] += 1

    def _quarantine_holds(self, pod: Pod) -> bool:
        """A quarantined pod must not re-enter the queue through an
        informer add/update — a controller status patch or relist replay
        would otherwise reset its escalating backoff. The release path
        re-fetches hub truth, so nothing else to track here."""
        return pod.metadata.uid in self._quarantine

    def _enqueue_fresh(self, pod: Pod) -> None:
        """Route a pending pod to its queue: tenant/gang pods go through
        the job-queue layer (DRR + quota + gang gating), everything else
        straight to the activeQ — two dict probes for plain pods."""
        if self.jobqueue.wants(pod) \
                and not self.jobqueue.was_admitted(pod.metadata.uid):
            self.jobqueue.add(pod)
        else:
            self.queue.add(pod)

    def _note_bound_pod(self, pod: Pod) -> None:
        """Bound-pod observation for the gang/tenant bookkeeping (quorum
        counting across failover, quota replay after restart)."""
        if LABEL_POD_GROUP in pod.metadata.labels:
            self._gang.note_bound(pod)
        if self.jobqueue.wants(pod):
            self.jobqueue.remove(pod)       # no longer queued here
            self.jobqueue.note_bound(pod)

    def _on_pod_event(self, ev) -> None:
        """Pod watch dispatch (JournalEvent-shaped): join the commit's
        wire trace stamp into the pod timeline, then run the typed
        handler. Events without a stamp (LIST replays, pre-telemetry
        peers) flow identically — hop data degrades, never the event."""
        if ev.type == "delete":
            self._on_pod_delete(ev.old)
            return
        if self.flight.enabled:
            self._stamp_wire_trace(ev)
        if ev.type == "add":
            self._on_pod_add(ev.new)
        else:
            self._on_pod_update(ev.old, ev.new)

    def _stamp_wire_trace(self, ev) -> None:
        """The cross-wire timeline join (telemetry.trace): ``created``
        from the pod's add commit, ``bound`` from the bind commit,
        ``acked`` from the kubelet's status-Running commit, and
        ``kubelet_recv`` from the ack's trace-baggage annotation (the
        bound event's arrival stamp after its relay hops) — one
        end-to-end hub -> relay -> scheduler -> bind -> ack timeline
        per pod, served at /debug/pod."""
        from kubernetes_tpu.telemetry.trace import (
            ACK_TRACE_ANNOTATION,
            parse_ack_trace,
        )

        pod, tr, tl = ev.new, ev.trace, self.timelines
        if not self._ours(pod):
            return
        if ev.type == "add":
            if tr is not None and not pod.spec.node_name:
                tl.wire_stamp(pod, "created", tr.ts, tr.origin, tr.hops)
            return
        old = ev.old
        if tr is not None and pod.spec.node_name \
                and (old is None or not old.spec.node_name):
            tl.wire_stamp(pod, "bound", tr.ts, tr.origin, tr.hops)
        if pod.status.phase == "Running" \
                and (old is None or old.status.phase != "Running"):
            if tr is not None:
                tl.wire_stamp(pod, "acked", tr.ts, tr.origin, tr.hops)
            baggage = pod.metadata.annotations.get(ACK_TRACE_ANNOTATION)
            if baggage:
                bt = parse_ack_trace(baggage)
                if bt is not None:
                    tl.wire_stamp(pod, "kubelet_recv", bt.ts,
                                  bt.origin, bt.hops)

    def _on_pod_add(self, pod: Pod) -> None:
        if self._pod_event_stale(pod):
            return
        if pod.spec.node_name:
            self._foreign.pop(pod.metadata.uid, None)
            if not self.cache.is_assumed_pod(pod):
                # a pod WE placed is already in the chain (its launch
                # committed it on device); only foreign binds move it
                self._chain_note_pod(pod, +1)
            self.cache.add_pod(pod)
            self._note_bound_pod(pod)
            self.queue.move_all_to_active_or_backoff(
                ClusterEvent(R.ASSIGNED_POD, A.ADD), None, pod)
        elif not self._terminal(pod) and self._ours(pod) \
                and not self._quarantine_holds(pod):
            # foreign schedulerName pods are another scheduler's business
            # (schedule_one.go:371); foreign SLICE pods belong to a peer
            # replica — penned, not queued
            if not self._owns_pod(pod):
                self._stash_foreign(pod)
                return
            # restart/replay: re-seed nominations from status so
            # reservations survive a scheduler restart
            if pod.status.nominated_node_name:
                self.nominator.add(pod, pod.status.nominated_node_name)
            if self.flight.enabled:
                self.timelines.event(pod, "enqueued")
            self._enqueue_fresh(pod)

    def _on_pod_update(self, old: Pod, new: Pod) -> None:
        if self._pod_event_stale(new):
            return
        if new.spec.node_name:
            self._foreign.pop(new.metadata.uid, None)
            if not self.cache.is_assumed_pod(new):
                if old.spec.node_name:
                    # bound-pod mutation: the chain moves by the request
                    # DIFFERENCE (labels-only updates cancel to zero)
                    self._chain_note_pod(old, -1)
                    self._chain_note_pod(new, +1)
                else:
                    self._chain_note_pod(new, +1)
            self.nominator.delete(new.metadata.uid)
            if old.spec.node_name:
                self.cache.update_pod(old, new)
                action = (A.UPDATE_POD_LABEL
                          if old.metadata.labels != new.metadata.labels
                          else A.UPDATE_POD_SCALE_DOWN)
                self.queue.move_all_to_active_or_backoff(
                    ClusterEvent(R.ASSIGNED_POD, action), old, new)
            else:
                # freshly bound (possibly by us): informer truth confirms
                self.cache.add_pod(new)
                self.queue.delete(new)
                self._note_bound_pod(new)
                self.queue.move_all_to_active_or_backoff(
                    ClusterEvent(R.ASSIGNED_POD, A.ADD), old, new)
        elif not self._terminal(new) and self._ours(new) \
                and not self._quarantine_holds(new):
            if not self._owns_pod(new):
                self._stash_foreign(new)
                return
            if new.metadata.uid in self._foreign:
                # adopted by an update that arrived after a rebalance
                # made the pod ours (label change re-hashing its gang,
                # or a pen refresh): queue it like a fresh add
                del self._foreign[new.metadata.uid]
                self.stats["foreign_adopted"] += 1
                self._enqueue_fresh(new)
                return
            self.nominator.update(new)
            if self.jobqueue.active \
                    and self.jobqueue.holds(new.metadata.uid):
                self.jobqueue.update(new)
            else:
                self.queue.update(old, new)

    def _on_pod_delete(self, pod: Pod) -> None:
        # deletes always win: tombstone at max rv so a straggling update
        # for the dead pod can't resurrect it in the cache; tombstones age
        # out of a bounded FIFO instead of a wholesale clear
        uid = pod.metadata.uid
        self._foreign.pop(uid, None)
        was_quarantined = self._quarantine.pop(uid, None) is not None
        self._fault_strikes.pop(uid, None)
        self._quarantine_counts.pop(uid, None)
        if self.jobqueue.active and self.jobqueue.wants(pod):
            # credit the tenant's quota reservation; drop queued copies
            self.jobqueue.remove(pod)
        gang = pod_group_key(pod)
        if gang is not None:
            if pod.spec.node_name:
                self._gang.note_unbound(pod)
            if was_quarantined:
                # the poisoned member is gone: the rest of the gang may
                # schedule again once NO member remains quarantined
                # (re-offense re-poisons)
                self._gang.release_poison(gang, uid)
        self._pod_rv[uid] = 2 ** 62
        self._rv_tombstones.append(uid)
        if len(self._rv_tombstones) > 50_000:
            self._pod_rv.pop(self._rv_tombstones.popleft(), None)
        # a pod parked at Permit WAIT holds an assumed reservation: free it
        # now (the reference rejects waiting pods from the delete handler)
        if pod.spec.resource_claims:
            from kubernetes_tpu.plugins.dra import release_pod_claims

            try:
                release_pod_claims(self.hub, pod)
            except Unavailable:
                # raised on the informer thread: must not kill the
                # reflector; claim reservations reconcile on relist
                self._note_hub_down()
        wp = None
        for fw in self.frameworks.values():
            wp = fw.waiting_pods.remove(uid)
            if wp is not None:
                break
        if wp is not None:
            self._fw_for(wp.qp.pod).run_unreserve_plugins(
                wp.state, wp.qp.pod, wp.node_name)
            assumed = wp.qp.pod.clone()
            assumed.spec.node_name = wp.node_name
            # guard like _undo_commit: a foreign bind may have CONFIRMED
            # this reservation through the informer before the delete
            # arrived — forget_pod would raise on a confirmed pod, and
            # the assigned-pod branch below already removes it
            if self.cache.is_assumed_pod(assumed):
                self.cache.forget_pod(assumed)
                # the reservation WAS committed on device by its launch:
                # hand the freed request back to the chain
                self._chain_note_pod(assumed, -1)
            self.queue.done(uid)
        self.nominator.delete(uid)
        if pod.spec.node_name:
            self._chain_note_pod(pod, -1)
            self.cache.remove_pod(pod)
            self.queue.move_all_to_active_or_backoff(
                ClusterEvent(R.ASSIGNED_POD, A.DELETE), pod, None)
        else:
            self.queue.delete(pod)

    # ------------- degraded mode (hub unreachable) -------------

    def hub_degraded(self) -> bool:
        """True while the hub transport is down. A RemoteHub knows its
        own state; for in-process wrappers (ChaosHub) the flag set by the
        last failed call stands until a probe succeeds."""
        connected = getattr(self.hub, "connected", None)
        if connected is not None:
            return not connected
        return self._hub_down

    def _note_hub_down(self) -> None:
        if not self._hub_down:
            logger.warning(
                "hub unreachable: entering degraded mode (parking work)")
            telemetry.incident(self, "hub_degraded",
                               reason="hub unreachable; parking work")
        self._hub_down = True

    def _park_unreachable(self, qp: QueuedPodInfo) -> None:
        """Park a pod the hub outage interrupted: error-class backoff so
        retries pace themselves, but NO condition patch (it would need
        the hub) and no error accounting — the pod did nothing wrong."""
        qp.unschedulable_plugins = set()
        qp.consecutive_errors_count += 1
        self.stats["parked_unreachable"] += 1
        self.queue.add_unschedulable_if_not_present(qp)

    def _park_batch_unreachable(self, runnable: list[QueuedPodInfo]
                                ) -> None:
        """Hub outage during pack/dispatch: park the whole batch and
        keep the loop alive. Anything _dispatch deferred came out of
        this same runnable list, so clearing _deferred cannot strand a
        pod."""
        self._note_hub_down()
        self._invalidate_chain()
        self._deferred = []
        for qp in runnable:
            self._park_unreachable(qp)

    def _fencing_args(self) -> tuple:
        """Extra positional args for fenced hub writes: (epoch,
        lease_name) while an elector gates this scheduler, () otherwise
        (single-scheduler deployments stay unfenced)."""
        el = self._elector
        return () if el is None else (el.epoch, el.lease_name)

    def _fenced_bind(self, pod: Pod, node_name: str) -> None:
        """The binder client handed to DefaultBinder: Hub.bind carrying
        our fencing epoch, so an in-flight bind submitted before we were
        deposed is rejected (Fenced) instead of double-placing the pod.
        Inside a binding cycle the epoch captured at submission wins —
        re-election must not refresh a stale decision's token."""
        fargs = getattr(self._bind_fence, "args", None)
        if fargs is None:
            fargs = self._fencing_args()
        self.hub.bind(pod, node_name, *fargs)

    def _patch_condition_best_effort(self, pod: Pod,
                                     condition: PodCondition,
                                     nominated_node: str | None = None
                                     ) -> None:
        """Condition patches are observability, not correctness: in
        degraded mode (or when fenced) they are dropped — and COUNTED,
        so operators can see lost status — not allowed to wedge the
        loop."""
        try:
            # positional: RemoteHub's RPC proxies take *args only
            self.hub.patch_pod_condition(pod, condition, nominated_node,
                                         *self._fencing_args())
        except Unavailable:
            self._note_hub_down()
            self.metrics.condition_patches_dropped.inc(
                reason="unavailable")
        except Fenced:
            self.stats["fenced"] += 1
            self.metrics.fenced_writes.inc(verb="patch_pod_condition")
            self.metrics.condition_patches_dropped.inc(reason="fenced")

    def _flush_evictions_safe(self) -> None:
        # only a flush with queued work is a phase (this runs every
        # cycle): with none it is these two attribute reads, not an
        # empty coalescing window around an empty flush (20 us a call,
        # and loop time no span held)
        if not self.preemption.has_pending():
            return
        sp = self.flight.span("eviction_flush")
        try:
            # evictions fire only over durably-bound state: a victim
            # whose own bind still rides the binder backlog would be
            # deleted BEFORE its bind lands, losing the pod (the
            # bind-after-delete fails and the deleted pod can't
            # requeue). The strict path orders wait-drain before
            # flush for the same reason (schedule_one_batch).
            self._drain_bind_results(wait=True)
            # the queue's coalescing window batches the wave's delete
            # events into ONE requeue pass (in-process hubs dispatch
            # them inline on this thread); the whole wave — deletes AND
            # requeue reaction — lands under the single eviction_flush
            # phase observation below, never per-delete
            with self.queue.coalescing():
                self.preemption.flush_evictions()
        except Unavailable:
            self._note_hub_down()
        finally:
            sp.end()

    # ------------- fault containment (the self-healing ladder) -------------
    #
    # The ladder, top to bottom: (1) the fused device launch; (2) on any
    # device-path exception (XLA error, guard-reduction NaN, re-bucket
    # non-convergence, a plugin raising during pack) the batch degrades
    # to the serial host Filter/Score path — peers keep scheduling THIS
    # cycle, and the device path is retried fresh on the next batch;
    # (3) a pod that raises in its own serial evaluation, or keeps
    # appearing in faulted batches (QUARANTINE_STRIKES), is bisected out
    # into the quarantine set with escalating backoff, a hub Event, and
    # a metric. The daemon never dies because the accelerator path did.

    def _finish_contained(self, inflight: tuple) -> None:
        """_finish with blast-radius containment: an exception commits
        nothing further and routes the batch's still-pending pods down
        the ladder instead of escaping the loop."""
        try:
            self._finish(inflight)
        except Unavailable:
            self._park_batch_unreachable(self._still_pending(inflight[0]))
        except Exception as e:  # noqa: BLE001 — the containment seam
            self._contain_batch_fault(inflight[0], e)

    def _still_pending(self, runnable: list[QueuedPodInfo]
                       ) -> list[QueuedPodInfo]:
        """The subset of a faulted batch that no commit path has touched
        yet (a _finish that raised midway may have assumed — or even
        bound-and-confirmed — some pods already, or parked others; none
        of those may be re-driven)."""
        return [qp for qp in runnable
                if self.cache.get_pod(qp.pod) is None
                and not self.queue.is_parked(qp.uid)]

    def _contain_batch_fault(self, runnable: list[QueuedPodInfo],
                             exc: BaseException) -> None:
        """Rung 2 of the ladder: the device path failed for this batch.
        Strike every member (poison attribution), invalidate the usage
        chain, and degrade the survivors to the host path."""
        self.stats["device_fallbacks"] += 1
        self.metrics.device_fallbacks.inc()
        self.last_device_fault = repr(exc)
        self._invalidate_chain()
        logger.warning(
            "device path failed for a %d-pod batch (%r); degrading to "
            "the host fallback path", len(runnable), exc, exc_info=exc)
        telemetry.incident(self, "device_fallback",
                           reason=repr(exc), pods=len(runnable))
        pending = self._still_pending(runnable)
        # pods _dispatch deferred before raising (profile split, host
        # volume conflicts) are still in flight via _deferred — the next
        # pop drives them; driving them here too would double-place
        deferred = {qp.uid for qp in self._deferred}
        pending = [qp for qp in pending if qp.uid not in deferred]
        for qp in pending:
            self._fault_strikes[qp.uid] = \
                self._fault_strikes.get(qp.uid, 0) + 1
        self._host_fallback_batch(pending)

    def _host_fallback_batch(self, qps: list[QueuedPodInfo]) -> None:
        """The degraded scheduling path: serial host-side Filter/Score
        over the snapshot (resources, taints, node selector/affinity,
        host ports, unschedulable marks, plus the host plugin filters
        and scores). Serial evaluation IS the bisection: a pod that
        raises poisons only itself and is quarantined; its batch peers
        keep scheduling. Pods needing topology kernels are parked to
        retry the device path next cycle (the host path has no fused
        affinity state)."""
        if not qps:
            return
        # drain in-flight binds BEFORE the phase clock starts: the drain
        # records its own binder_drain observation, and both phases are
        # HOST_PHASES — timing it here too would double-count the wall
        # time in host_tail_share
        try:
            self._drain_bind_results(wait=True)
        except Unavailable:
            self._park_batch_unreachable(qps)
            return
        # the fallback's serial host-path cost feeds the host_fallback
        # phase histogram: scheduler_device_fallbacks_total says how
        # OFTEN the ladder fired, this says what each firing COST
        with self.flight.span("host_fallback"):
            self._host_fallback_batch_inner(qps)

    def _host_fallback_batch_inner(self, qps: list[QueuedPodInfo]) -> None:
        # the fallback evaluates on host: re-enable the host DRA filter
        # for every pod (device routing only holds for a device launch)
        self._dra.set_device_routed(())
        try:
            self.cache.update_snapshot(self.snapshot)
        except Unavailable:
            self._park_batch_unreachable(qps)
            return
        committed: dict[str, object] = {}     # node -> Resource committed
        committed_pods: dict[str, int] = {}
        for qp in qps:
            if self._fault_strikes.get(qp.uid, 0) >= QUARANTINE_STRIKES:
                self._quarantine_pod(
                    qp, f"{self._fault_strikes[qp.uid]} batch faults")
                continue
            try:
                node, plugins = self._host_place_one(qp, committed,
                                                     committed_pods)
            except Unavailable:
                self._note_hub_down()
                self._park_unreachable(qp)
                continue
            except Exception as e:  # noqa: BLE001 — the poison seam:
                # this pod's own spec/plugins raised in SERIAL evaluation,
                # so the attribution is exact — quarantine it alone
                self._quarantine_pod(qp, f"host fallback raised: {e!r}")
                continue
            if node is None:
                # rung-bottom preemption mini-path (ISSUE 15): a fully
                # device-dead scheduler must still be able to evict —
                # serial host candidate selection + the queued eviction
                # flush; the nomination rides the unschedulable park so
                # the retry (still on the host path if the device stays
                # dead) claims the vacated room
                nominated = self._host_preempt_fallback(qp, plugins)
                if nominated:
                    self.stats["preemptions"] = self.stats.get(
                        "preemptions", 0) + 1
                self._park_unschedulable(
                    qp, plugins, "host fallback: no feasible node",
                    nominated=nominated)
            elif node == "":
                # topology pod: the host path cannot evaluate it — park
                # error-class and let the next cycle retry the device path
                self._error(qp, "device path failed; topology pod awaits "
                                "device retry")
            else:
                from kubernetes_tpu.api.resources import pod_request

                r = committed.get(node)
                if r is None:
                    committed[node] = pod_request(qp.pod).clone()
                else:
                    r.add(pod_request(qp.pod))
                committed_pods[node] = committed_pods.get(node, 0) + 1
                self._fault_strikes.pop(qp.uid, None)
                self._commit(qp, node)

    def _host_place_one(self, qp: QueuedPodInfo, committed: dict,
                        committed_pods: dict
                        ) -> tuple[Optional[str], set[str]]:
        """One pod through the host predicates + scores. Returns
        (node_name, set()) on success, (None, rejecting_plugins) when
        infeasible, ("", set()) when the pod needs the device's topology
        kernels (affinity/anti-affinity/spread — not evaluable here)."""
        from kubernetes_tpu.api.labels import (
            find_untolerated_taint,
            label_selector_matches,
            pod_matches_node_selector_and_affinity,
        )
        from kubernetes_tpu.api.resources import pod_request

        pod = qp.pod
        if self.mirror.batch_has_topology([pod]):
            return "", set()
        req = pod_request(pod)
        infos = self.snapshot.node_info_list
        fw = self._fw_for(pod)
        host_mask = host_scores = None
        qp.host_reject_counts = {}
        if (self._has_host_filters or self._has_host_scores) \
                and self._host_relevant(pod):
            state = CycleState()
            host_mask, counts, early = fw.run_host_filters(state, pod,
                                                           infos)
            if counts:
                qp.host_reject_counts = counts
            if early is not None:
                return None, set(counts) or {early.plugin or "HostFilter"}
            if self._has_host_scores:
                host_scores = fw.run_host_scores(state, pod, infos)
        ports = [(p.host_ip, p.protocol, p.host_port)
                 for c in pod.spec.containers for p in c.ports
                 if p.host_port > 0]
        rejects: set[str] = set(qp.host_reject_counts)
        best = None
        best_score = float("-inf")
        for i, ni in enumerate(infos):
            node = ni.node
            if node is None:
                continue
            if host_mask is not None and not host_mask[i]:
                continue
            if node.spec.unschedulable:
                rejects.add("NodeUnschedulable")
                continue
            if not pod_matches_node_selector_and_affinity(pod, node):
                rejects.add("NodeAffinity")
                continue
            if find_untolerated_taint(node.spec.taints,
                                      pod.spec.tolerations) is not None:
                rejects.add("TaintToleration")
                continue
            if any(ni.used_ports.conflicts(*p) for p in ports):
                rejects.add("NodePorts")
                continue
            # symmetry guard: an EXISTING pod's required anti-affinity
            # must not be violated by this placement; non-hostname
            # domains span other nodes, which only the device kernels
            # track — send such pods back to the device path
            sym_block = False
            for pi in ni.pods_with_required_anti_affinity:
                for term in pi.required_anti_affinity_terms:
                    if label_selector_matches(term.label_selector,
                                              pod.metadata.labels) \
                            and pi.pod.metadata.namespace \
                            == pod.metadata.namespace:
                        if term.topology_key != "kubernetes.io/hostname":
                            return "", set()
                        sym_block = True
            if sym_block:
                rejects.add("InterPodAffinity")
                continue
            alloc = ni.allocatable
            extra = committed.get(ni.name)
            free_cpu = alloc.milli_cpu - ni.requested.milli_cpu \
                - (extra.milli_cpu if extra else 0)
            free_mem = alloc.memory - ni.requested.memory \
                - (extra.memory if extra else 0)
            free_eph = alloc.ephemeral_storage \
                - ni.requested.ephemeral_storage \
                - (extra.ephemeral_storage if extra else 0)
            n_pods = len(ni.pods) + committed_pods.get(ni.name, 0)
            if req.milli_cpu > free_cpu or req.memory > free_mem \
                    or req.ephemeral_storage > free_eph \
                    or (alloc.allowed_pod_number > 0
                        and n_pods + 1 > alloc.allowed_pod_number):
                rejects.add("NodeResourcesFit")
                continue
            if any(v > alloc.scalar.get(k, 0)
                   - ni.requested.scalar.get(k, 0)
                   - (extra.scalar.get(k, 0) if extra else 0)
                   for k, v in req.scalar.items()):
                rejects.add("NodeResourcesFit")
                continue
            # LeastAllocated over cpu+memory — the host analog of the
            # default fit scoring, enough to spread a degraded batch —
            # plus any configured host score plugins
            score = 0.0
            if alloc.milli_cpu > 0:
                score += (free_cpu - req.milli_cpu) / alloc.milli_cpu
            if alloc.memory > 0:
                score += (free_mem - req.memory) / alloc.memory
            if host_scores is not None:
                score += host_scores[i]
            if score > best_score:
                best, best_score = ni.name, score
        if best is None:
            return None, rejects or {"NodeResourcesFit"}
        return best, set()

    def _host_preempt_fallback(self, qp: QueuedPodInfo,
                               plugins: set[str]) -> Optional[str]:
        """The host fallback's preemption rung: serial candidate
        selection over the snapshot (Evaluator.host_preempt) when the
        rejection is preemption-resolvable. Returns the nominated node
        name, or None when preemption does not apply / found nothing."""
        pod = qp.pod
        if pod.priority() <= 0 \
                or pod.metadata.uid in self.preemption.preempting:
            return None
        # only fit-class rejections are resolvable by eviction; host
        # plugin rejects (volumes, claims) and pure static rejects are
        # not — matching the device path's Unresolvable discipline
        if plugins and "NodeResourcesFit" not in plugins:
            return None
        if not self._fw_for(pod).points["post_filter"]:
            return None         # profile disabled preemption
        try:
            node, _status = self.preemption.host_preempt(pod,
                                                         self.snapshot)
        except Unavailable:
            self._note_hub_down()
            return None
        except Exception as e:  # noqa: BLE001 — the mini-path must
            # never take the whole fallback batch down with it
            logger.warning("host preemption mini-path failed for %s: %r",
                           pod.key(), e)
            return None
        return node

    def _park_unschedulable(self, qp: QueuedPodInfo, plugins: set[str],
                            msg: str, nominated: Optional[str] = None
                            ) -> None:
        """Unschedulable park with plugin attribution. Full PostFilter
        preemption is a device sweep the fallback path must not re-enter;
        the host mini-path's nomination (if any) rides the condition
        patch so the preemptor's reservation survives the park."""
        if self.flight.enabled:
            self.timelines.diagnose(qp.pod, {}, qp.host_reject_counts
                                    or {p: -1 for p in plugins}, msg)
            self.timelines.event(qp.pod, "unschedulable", msg)
        qp.unschedulable_plugins = plugins or {"NodeResourcesFit"}
        qp.unschedulable_count += 1
        qp.consecutive_errors_count = 0
        self.stats["unschedulable"] += 1
        self.metrics.schedule_attempts.inc(
            result="unschedulable", profile=qp.pod.spec.scheduler_name)
        self._patch_condition_best_effort(qp.pod, PodCondition(
            type="PodScheduled", status="False", reason="Unschedulable",
            message=msg), nominated)
        if nominated:
            # park the FRESH object so the packed nominated_row sees
            # status.nominatedNodeName next attempt (same re-fetch
            # discipline as _park_failed)
            try:
                stored = self.hub.get_pod(qp.uid)
            except Unavailable:
                self._note_hub_down()
                stored = None
            if stored is not None:
                qp.pod = stored
        self.queue.add_unschedulable_if_not_present(qp)

    # ------------- poison-pod quarantine -------------

    def _quarantine_pod(self, qp: QueuedPodInfo, reason: str) -> None:
        """Park a pod that keeps faulting its batch: out of the queue,
        escalating backoff, hub Event + metric so operators see it."""
        uid = qp.uid
        n = self._quarantine_counts.get(uid, 0) + 1
        self._quarantine_counts[uid] = n
        backoff = min(QUARANTINE_CAP_S, QUARANTINE_BASE_S * (2 ** (n - 1)))
        self._quarantine[uid] = {"qp": qp, "until": self.now() + backoff,
                                 "reason": reason}
        self._fault_strikes.pop(uid, None)
        self.queue.done(uid)
        self.stats["quarantined"] += 1
        self.metrics.quarantines.inc(reason="poison")
        self.metrics.quarantined_pods.set(float(len(self._quarantine)))
        if self.flight.enabled:
            self.timelines.event(qp.pod, "quarantined",
                                 f"{backoff:.0f}s: {reason}")
        gang = pod_group_key(qp.pod)
        if gang is not None:
            # a poisoned member poisons the WHOLE gang: members reject at
            # PreFilter/Reserve and any assembling reservation rolls back
            # — a gang placed around its poisoned member would violate
            # all-or-nothing (released with this pod's quarantine)
            self._gang.poison(gang, reason, uid)
        logger.error("quarantining pod %s for %.0fs (offense %d): %s",
                     qp.pod.key(), backoff, n, reason)
        telemetry.incident(self, "quarantine", reason=reason,
                           pod=qp.pod.key(), offense=n)
        try:
            self.hub.record_event(
                "Pod", qp.pod.key(), "Quarantined",
                f"poison-pod quarantine ({backoff:.0f}s, offense {n}): "
                f"{reason}")
        except Unavailable:
            self._note_hub_down()

    def _release_quarantined(self) -> None:
        """Maintenance tick: return served-out quarantine entries to the
        queue (re-offense re-quarantines with doubled backoff)."""
        if not self._quarantine:
            self.metrics.quarantined_pods.set(0.0)
            return
        now = self.now()
        for uid, entry in list(self._quarantine.items()):
            if entry["until"] > now:
                continue
            try:
                stored = self.hub.get_pod(uid)
            except Unavailable:
                self._note_hub_down()
                continue            # retry on the next tick
            entry = self._quarantine.pop(uid)
            gang = pod_group_key(entry["qp"].pod)
            if gang is not None:
                self._gang.release_poison(gang, uid)
            if stored is not None and not stored.spec.node_name \
                    and not self._terminal(stored):
                self._enqueue_fresh(stored)
        self.metrics.quarantined_pods.set(float(len(self._quarantine)))

    def quarantined_uids(self) -> set[str]:
        """Introspection for tests/serving: pods currently quarantined."""
        return set(self._quarantine)

    # ------------- capacity re-bucketing -------------

    def _grow(self, err: CapacityError) -> None:
        """Double the exceeded capacity and rebuild the mirror (the
        re-bucketing strategy from the Mirror docstring; kernels recompile
        once per bucket)."""
        field = {"ext_resources": "ext_resources"}.get(err.field, err.field)
        if not hasattr(self.caps, field):
            raise err
        cur = getattr(self.caps, field)
        new = max(cur * 2, 8)
        while new < err.needed:
            new *= 2
        self.caps = dataclasses.replace(self.caps, **{field: new})
        prev = self.mirror
        self.mirror = Mirror(caps=self.caps, mesh=self.mesh)
        # sticky-bucket continuity: the fresh mirror keeps the old one's
        # shape high-water marks, so re-bucketing doesn't re-learn d_cap/
        # g_cap from scratch and flap the compiled programs again
        self.mirror.adopt_hysteresis(prev)
        self.snapshot = Snapshot()
        self._invalidate_chain()
        self.cache.update_snapshot(self.snapshot)
        # NO sync here: the caller's retry loop re-syncs, so a second field
        # overflowing during the rebuild raises inside the try (and grows
        # again) instead of escaping the loop from this except-handler.

    # ------------- the batched scheduling cycle -------------

    def _pop_runnable(self) -> tuple:
        """Pop up to batch_size pods and apply skipPodSchedule
        (schedule_one.go:380: deleted or already assumed). Pods deferred
        from the previous batch (host-serial volume conflicts) go first —
        they are still in flight from their original pop. A pop that
        yields runnable pods opens their cycle's trace (returned third)
        and reports its own queue_pop span on it the moment it ends; an
        empty pop is no phase."""
        sp = self.flight.span("queue_pop")
        deferred, self._deferred = self._deferred, []
        batch = deferred + self.queue.pop_batch(
            self._effective_batch() - len(deferred))
        runnable: list[QueuedPodInfo] = []
        for i, qp in enumerate(batch):
            try:
                stored = self.hub.get_pod(qp.uid)
            except Unavailable:
                # hub unreachable mid-pop: park the whole batch (vetted
                # pods included — their binds would only fail) and let
                # backoff pace the retry; nothing errors, nothing is lost
                self._note_hub_down()
                for rest in runnable + batch[i:]:
                    self._park_unreachable(rest)
                sp.end(report=False)
                return len(batch), [], None
            if stored is None or stored.metadata.deletion_timestamp:
                self.queue.done(qp.uid)
                continue
            if self.cache.is_assumed_pod(qp.pod):
                self.queue.done(qp.uid)
                continue
            if self._fault_strikes.get(qp.uid, 0) >= QUARANTINE_STRIKES:
                # repeat offender re-entering via error backoff (e.g. a
                # pod whose reserve plugin keeps raising): bisect it out
                # before it faults another batch
                self._quarantine_pod(
                    qp, f"{self._fault_strikes[qp.uid]} batch/commit "
                        "faults")
                continue
            runnable.append(qp)
        if not runnable:
            sp.end(report=False)
            return len(batch), runnable, None
        tr = self.flight.begin(sp.t0, len(runnable))
        if self.flight.enabled:
            # one clock read stamps the whole batch's pop events; the
            # stamping is the pop's own work, inside its span
            tl, t_pop = self.timelines, self.now()
            for qp in runnable:
                tl.event(qp.pod, "popped", f"attempt {qp.attempts}",
                         t=t_pop)
        sp.end(tr=tr)
        return len(batch), runnable, tr

    def _chain_eligible(self, pods: list[Pod]) -> bool:
        """Can this batch launch against the device-resident usage chain
        without a host snapshot/mirror re-sync? Requires: a live chain (no
        external event since the newest dispatch) and a launch that reads
        nothing the skipped sync would refresh — no topology kernels (pod
        table), no batch host ports (port tables), and no host-filter work
        (host plugins read the snapshot, so it must be fresh)."""
        return (self._chain is not None
                and not self.mirror.table_has_topology()
                and not self.mirror.batch_has_topology(pods)
                and not self.mirror.batch_has_host_ports(pods)
                and not (self._has_host_filters
                         and any(self._host_relevant(p) for p in pods)))

    def _apply_chain_patches(self, flush_pending=None) -> bool:
        """Fold the pending churn patches into the live device chain
        (chain-surviving churn, models/pipeline.patch_chain). Deltas
        commute with in-flight waves' device commits, so they scatter
        straight in; absolute node repacks read cache truth, so when any
        are pending the in-flight waves flush FIRST — the conservative
        form of "invalidate only when a touched node intersects an
        in-flight wave's packed set" (every in-flight wave's packed set
        came from the pre-event mirror, so a flush is the cheap safe
        answer; per-wave set intersection would save a flush only on the
        churn-while-deep-pipeline overlap, which the bench shows is
        rare). Returns False when the chain must fall back to a full
        resync (mirror capacity overflow, vanished rows, a flush fault
        that invalidated the chain) — the caller dispatches unchained."""
        if not self._chain_dirty and not self._chain_deltas:
            return True
        if self._chain is None:
            self._chain_dirty.clear()
            self._chain_deltas.clear()
            return False
        if self._chain_dirty and flush_pending is not None:
            flush_pending()
            if self._chain is None:     # a flush fault killed the chain
                self._chain_dirty.clear()
                self._chain_deltas.clear()
                return False
        # snapshot + clear AFTER the flush: events the flush delivered
        # inline (eviction deletes, binder confirms) registered more
        # patches, and this application must carry them too
        dirty = sorted(self._chain_dirty)
        deltas = [(nm, acc) for nm, acc in self._chain_deltas.items()
                  if nm not in self._chain_dirty]
        self._chain_dirty.clear()
        self._chain_deltas.clear()
        set_rows: list[tuple] = []
        add_rows: list[tuple] = []
        try:
            for name in dirty:
                patched = self.mirror.patch_node(
                    name, self.cache.node_info(name))
                if patched is not None:
                    set_rows.append(patched)
            for name, (dfree, dnzr) in deltas:
                row = self.mirror.row_of(name)
                if row < 0:
                    # a delta for a node the mirror never packed: the
                    # chain has no row to move — resync is the only
                    # consistent answer
                    self.stats["chain_patch_fallbacks"] += 1
                    self._invalidate_chain()
                    return False
                add_rows.append((row, dfree, dnzr))
        except CapacityError:
            self.stats["chain_patch_fallbacks"] += 1
            self._invalidate_chain()
            return False
        if set_rows or add_rows:
            free, nzr = self._chain
            self._chain = patch_chain(free, nzr, set_rows, add_rows)
            self.stats["chain_patches"] += 1
            self.stats["chain_patch_rows"] += len(set_rows) + len(add_rows)
        return True

    def _dispatch(self, runnable: list[QueuedPodInfo], chained: bool,
                  tr, flush_pending=None) -> Optional[tuple]:
        """Pack + launch one batch (async dispatch; no host<->device block).
        Returns (runnable, BatchResult) or None if every pod was routed to
        the failure path during packing. ``tr`` is the cycle's trace, open
        since its pop (_pop_runnable); _finish records it (the dispatched
        tuple carries it through the pipelined drain). ``flush_pending``
        commits a still-in-flight previous launch before any fallback
        re-sync, so a chained dispatch that has to re-bucket never syncs a
        cache missing the previous batch's placements."""
        span = self.flight.span
        t_cycle0 = self.now()
        # chain-surviving churn: fold pending informer patches into the
        # live chain BEFORE this launch packs against it. On fallback
        # (patch set too large, mirror capacity overflow) the chain is
        # invalidated and this dispatch takes the full-sync path.
        if chained and (self._chain_dirty or self._chain_deltas):
            with span("chain_patch", tr):
                if not self._apply_chain_patches(flush_pending):
                    chained = False
        epoch = self._chain_epoch
        if len(self.frameworks) > 1:
            # one profile per launch: enabled filters / weights / scoring
            # strategy are per-profile launch configuration
            prof = runnable[0].pod.spec.scheduler_name
            same = [qp for qp in runnable
                    if qp.pod.spec.scheduler_name == prof]
            if len(same) != len(runnable):
                self._deferred.extend(
                    qp for qp in runnable
                    if qp.pod.spec.scheduler_name != prof)
                runnable = same
        else:
            prof = self._profile_name
        pcfg = self._profile_cfg[prof]
        if self._has_host_filters:
            runnable = self._defer_host_conflicts(runnable)
            if not runnable:
                return None
        if self.fault_injector is not None:
            # chaos seam: may raise (device launch error, forced
            # CapacityError, poison-pod exception) — contained by the
            # fallback ladder exactly like a real device fault
            self.fault_injector.on_pack([qp.pod for qp in runnable])
        self.stats["batches"] += 1
        self.stats["attempts"] += len(runnable)
        # what the pop could not know: the pods left after the profile
        # and host-conflict splits, and whether the launch chains
        tr.pods = len(runnable)
        tr.chained = chained
        state = self._chain if chained else None
        need_sync = not chained
        for attempt in range(16):  # one capacity field may grow per attempt
            try:
                if need_sync:
                    if flush_pending is not None:
                        flush_pending()
                        flush_pending = None
                    # snapshot_sync in its two pieces, each also the
                    # view that names it
                    with span("snapshot_sync", tr, view="snapshot_cache"):
                        self.cache.update_snapshot(self.snapshot)
                    with span("snapshot_sync", tr, view="mirror_sync"):
                        terms_s0 = self.mirror.slot_terms_s
                        self.mirror.sync(self.snapshot)
                        # a full sync subsumes every pending chain patch:
                        # handlers mutate the cache synchronously before
                        # registering, and the sync read that cache
                        self._chain_dirty.clear()
                        self._chain_deltas.clear()
                        # the mirror's own view of this sync, reported
                        # just before its parent: seconds in the terms
                        # arm of _pack_pod_slot (0.0 = no pod with terms)
                        self.flight.observe_view(
                            "slot_pack_terms",
                            self.mirror.slot_terms_s - terms_s0)
                with span("pack", tr):
                    full_s0 = self.mirror.pack_full_s
                    self.mirror.set_nominated(self.nominator.by_node())
                    spec = self.mirror.prepare_launch(
                        [qp.pod for qp in runnable],
                        self.config.batch_size)
                    # the mirror's own view of this pack, reported just
                    # before its parent: seconds on the rows the packed-row
                    # cache did not serve (0.0 = every row a hit)
                    self.flight.observe_view(
                        "pack_full", self.mirror.pack_full_s - full_s0)
                break
            except CapacityError as e:
                if flush_pending is not None:
                    # commit in-flight launches against the OLD mirror NOW:
                    # _grow replaces self.mirror with an empty re-bucketed
                    # one, and a later flush would resolve their node rows
                    # against it (name_of_row -> None for every row)
                    flush_pending()
                    flush_pending = None
                self._grow(e)          # invalidates the chain
                state = None
                need_sync = True
        else:
            raise RuntimeError("mirror re-bucketing did not converge")

        # learned scorer (profile-gated): poll the checkpoint's mtime at
        # snapshot-sync time — a stat when unchanged, a load + H2D push
        # when an offline trainer published a new version. Params then
        # ride this launch as one more weighted term; a reload mid-run
        # never recompiles (same architecture = same jit signature).
        learned_params = None
        mgr = pcfg["learned"]
        if mgr is not None:
            with span("learned_score", tr):
                mgr.maybe_reload()
                learned_params = mgr.params()
            # reloads = swaps AFTER the initial load (the manager's
            # count); errors delta-mirrored like other external counts
            # the generation label rides the delta at reload time:
            # promoted-vs-manual publishes stay distinguishable in the
            # fleet scrape (generation 0 = manual)
            self._mirror_count(f"learned_reloads:{prof}", mgr.reloads,
                               self.metrics.learned_reloads,
                               profile=prof,
                               generation=str(mgr.generation))
            w = getattr(mgr, "_watcher", None)
            if w is not None:
                self._mirror_count(f"learned_errs:{prof}", w.load_errors,
                                   self.metrics.learned_load_errors,
                                   profile=prof)
            self.metrics.learned_checkpoint_version.set(
                float(mgr.version if learned_params is not None else 0),
                profile=prof)

        # batched DRA allocator: pack this batch's claim tensors and fuse
        # the device verdict into the launch (ops/dra.py + the dra arg of
        # schedule_batch). Pods whose claims sit outside the device-
        # expressible subset stay on the host filter path — applies()
        # keeps returning True for exactly those. Gated on the profile
        # actually enabling the DynamicResources filter (the batch is
        # single-profile by this point).
        if pcfg["dra_filter"] \
                and any(qp.pod.spec.resource_claims for qp in runnable):
            # claim state must be as settled as the host path saw it:
            # in-flight binding cycles write allocations (PreBind), so
            # land them before the in-use mask packs
            self._drain_bind_results(wait=True)
            with span("pack", tr) as dra_pack:
                dra_batch, dra_stats = self._dra.build_device_batch(
                    [qp.pod for qp in runnable], self.mirror.row_of,
                    self.caps.nodes, spec.pblobs.f32.shape[0])
            spec.dra = dra_batch
            for qp in runnable:
                if qp.pod.spec.resource_claims:
                    # stale attribution from a previous attempt must not
                    # survive into this cycle's diagnosis
                    qp.host_reject_counts = {}
            # dra_mask_compile = selector compilation + inventory
            # refresh; dra_device_eval = the per-cycle claim/in-use
            # tensor pack. Both are VIEWS (excluded from the cycle-total
            # arithmetic) whose seconds the allocator measured; the wall
            # time itself is the `pack` span above.
            tr.add("dra_mask_compile", dra_stats["compile_s"])
            tr.add("dra_device_eval",
                   dra_pack.secs - dra_stats["compile_s"])

        # commit mode: the parallel-rounds auction whenever the launch has
        # no topology work and no batch pod carries host ports (in-batch
        # port conflicts are impossible without batch host ports; node-side
        # conflicts are in the static masks the auction honors); the exact
        # as-if-serial scan otherwise (see pipeline._rounds_commit)
        # percentageOfNodesToScore (schedule_one.go:668): when set, the
        # rotating feasible-subset selection only exists in the serial
        # scan, so the auction (which scores all nodes by design) is gated
        # off. None/100 = score everything — the TPU-native stance (SURVEY
        # §2.7 P2); an explicit 0 = the reference's adaptive percentage.
        raw = self.config.percentage_of_nodes_to_score
        pct = (0 if raw is None or raw >= 100
               else ADAPTIVE_PCT if raw == 0 else raw)
        # topology launches may join the auction when the batch's
        # topology work is SOFT-only (preferred weights / ScheduleAnyway
        # spread, ISSUE 15): soft terms are scores, so either commit
        # engine can carry them fused. Engine choice is a backend
        # heuristic like pipeline.scan_unroll: on accelerators the
        # auction's few big fused rounds beat B sequential scan steps;
        # on CPU the soft-serial scan's small per-step kernels beat the
        # auction's bandwidth-bound [B, N] rounds.
        soft_auction = spec.topo_soft and jax.default_backend() != "cpu"
        use_auction = (not pct
                       and (not spec.enable_topology or soft_auction)
                       and not self.mirror.batch_has_host_ports(
                           [qp.pod for qp in runnable])
                       and pcfg["filters"][FILTER_PLUGINS.index(
                           "NodeResourcesFit")])
        host_ok = host_score = None
        if self._has_host_filters or self._has_host_scores \
                or self._extenders:
            with span("host_plugins", tr):
                host_ok, host_score = self._run_host_plugins(runnable)
        fit_strategy, fit_shape = pcfg["fit"]
        # export-pull flags captured ONCE: the launch compiles against
        # them and the commit thread pulls against them, so they must be
        # the same observation (a rotation-disabled export mid-cycle
        # must not desync the pull list from the launch outputs)
        exporting = self.flight.exporting
        want_feats = self._export_feats and exporting
        want_alts = self._export_alts and exporting
        if state is None:
            # seed the usage chain from the freshly synced mirror so every
            # launch carries explicit state: one jit signature for chained
            # and unchained dispatches (see pipeline.extract_state_jit)
            state = extract_state_jit(spec.cblobs, self.caps)
        disp = span("device_dispatch", tr)
        out: BatchResult = launch_batch(
            spec, self.mirror.well_known(), pcfg["weights"], self.caps,
            pcfg["filters"], serial_scan=not use_auction, state=state,
            host_ok=host_ok, host_score=host_score,
            fit_strategy=fit_strategy, fit_shape=fit_shape, pct_nodes=pct,
            # seeded with a concrete 0 (not None) so every launch shares one
            # arg pytree and therefore one trace/compile
            pct_start=(self._pct_start if self._pct_start is not None
                       else np.int32(0)) if pct else None,
            learned=learned_params, tie_seed=self._tie_seed,
            # chosen-node feature rows only materialize while the
            # feature export is opted in AND the export file is still
            # open (a failed rotation disables the export; the feature
            # kernels must not keep running for output nobody pulls)
            with_feats=want_feats, with_alts=want_alts)
        if self.fault_injector is not None:
            out = self.fault_injector.on_result(out)
        if pct:
            # device-resident rotation carry; stays async (never sync'd to
            # host), consumed as the next launch's seed
            self._pct_start = out.pct_start
        # the chain advances to this launch's post-batch state UNLESS an
        # invalidation raced in while we were packing (epoch check); later
        # external events reset it via the handlers
        if epoch == self._chain_epoch:
            self._chain = (out.free, out.nzr)
            if self._pipelined and not self._patch_warmed:
                # pre-compile every patch-scatter bucket for this chain
                # shape, once per scheduler: churn patches must never
                # trigger an XLA compile mid-drain
                self._patch_warmed = True
                warm_patch_chain(out.free, out.nzr, CHAIN_PATCH_MAX)
        # device-launch profiler: the jit call above traced (and, on a
        # new bucket shape, COMPILED) synchronously before dispatching,
        # so reading the executable-cache size here attributes any
        # growth to exactly this launch's shape
        pshape = None
        compiled = False
        prof = self.profiler
        if prof is not None:
            from kubernetes_tpu.telemetry.profiler import (
                shape_key,
                tree_nbytes,
            )

            pshape = shape_key(
                self.caps, spec.pblobs.f32.shape[0],
                spec.enable_topology, spec.d_cap, spec.g_cap,
                not use_auction, spec.dra is not None,
                learned_params is not None, want_feats,
                alts=want_alts, soft=spec.topo_soft, active=spec.active)
            compiled = prof.note_launch(
                pshape, len(runnable),
                None if use_auction else scan_steps_for(
                    len(runnable), spec.pblobs.f32.shape[0]),
                table_blocks_for(spec.table_hi, spec.cblobs.pods_i32.shape[0])
                if spec.enable_topology else None)
            if compiled or prof.launches == 1:
                # buffer footprints are bucket-static: re-measure only
                # when a compile (= a bucket/flag change) happened
                prof.note_buffers({
                    "cluster": tree_nbytes(spec.cblobs),
                    "pods": tree_nbytes(spec.pblobs),
                    "dra": tree_nbytes(spec.dra),
                    "learned": tree_nbytes(learned_params)})
        # off-thread commit: the wave's blocking D2H pull rides the
        # commit thread from HERE, so it overlaps whatever the loop (and
        # the device) does next; _finish harvests the future. The flags
        # tuple snapshots what the launch actually compiled so the pull
        # list matches its outputs.
        flags = (learned_params is not None, exporting,
                 want_feats, want_alts)
        fut = (self._commit_pool.submit(self._pull_launch, out, flags, tr)
               if self._commit_pool is not None else None)
        # the span closes on the hand-over: the profiler's note and the
        # submit are the dispatch's own work (a commit_pull that began
        # meanwhile is the commit thread's, an overlap phase), and so is
        # letting go of the launch's inputs, which a frame that frees
        # them on return does in nobody's phase (0.07 ms)
        del spec, state
        disp.end()
        t_done = disp.t1
        return (runnable, out, t_done, t_done - t_cycle0, tr,
                flags, pshape, compiled, fut)

    # ------------- device-side gang packing (ISSUE 12) -------------
    #
    # A whole PodGroup as ONE device problem: the batch's gang units are
    # packed into a single fused launch (ops/gang.pack_gangs) — static
    # filters, member-capacity-per-node, an all-or-nothing feasibility
    # reduction, and topology-close domain packing, gangs committed
    # as-if-serial inside the launch. A unit that clears the verdict
    # commits through the fenced binder as one atomic host step
    # (reserve-all -> bind-all); the Permit quorum machinery survives
    # only as the host-fallback path for gangs the kernel cannot express
    # (topology terms, heterogeneous members, claims/volumes, active
    # nominations) and as rung 2 of the ladder on any device fault.

    def _gang_unit_fallback_reason(self, key: str,
                                   qps: list[QueuedPodInfo]
                                   ) -> Optional[str]:
        """None = the unit is device-packable; otherwise the reason it
        must take the host Permit path (the fallback metric's label)."""
        group = self._gang.group_of(key)
        if group is None:
            return "no_group"
        if self._gang._poison_reason(key) is not None:
            return "poisoned"
        pods = [qp.pod for qp in qps]
        prof = pods[0].spec.scheduler_name
        if any(p.spec.scheduler_name != prof for p in pods[1:]):
            return "profiles"
        pcfg = self._profile_cfg.get(prof)
        if pcfg is None or not pcfg.get("gang_plugin"):
            return "no_plugin"
        # every member present in THIS batch places together; the unit
        # is packable only if that completes the quorum (bound members
        # count — failover admits the tail of a half-bound gang)
        need = max(group.min_member - self._gang.bound_count(key), 0)
        if len(pods) < need:
            return "partial"
        if self.mirror.batch_has_topology(pods):
            return "topology"
        if self.mirror.batch_has_host_ports(pods):
            return "ports"
        if any(p.spec.resource_claims or p.spec.volumes for p in pods):
            return "host_filters"
        if any(ext.is_interested(p) for ext in self._extenders
               for p in pods):
            return "extender"
        if max((p.priority() for p in pods), default=0) > 0:
            # a preempting gang the packer would reject anyway (the
            # memoized capacity bound, still fresh by content token,
            # already proves < need) goes STRAIGHT to the host path —
            # paying a pack launch + pipeline flush every retry cycle
            # while victims drain is what regressed GangPreemption
            cached = self._gang._cap_cache.get(key)
            if cached is not None and cached[1] < len(pods):
                try:
                    if cached[0] == self._gang.cap_token(self.mirror,
                                                         pods[0]):
                        return "infeasible_preempting"
                except CapacityError:
                    return "capacity"
        try:
            from kubernetes_tpu.api.resources import pod_request

            row0 = self.mirror._res_row(pod_request(pods[0])).tobytes()
            if any(self.mirror._res_row(pod_request(p)).tobytes() != row0
                   for p in pods[1:]):
                # the packer places request-IDENTICAL members (one
                # representative row per gang)
                return "hetero"
        except CapacityError:
            return "capacity"   # normal path re-buckets and retries
        return None

    def _split_gang_units(self, runnable: list[QueuedPodInfo]
                          ) -> tuple[list, list[QueuedPodInfo]]:
        """Partition a popped batch into device-packable gang units and
        the rest (plain pods + fallback-path gang members)."""
        by_key: dict[str, list[QueuedPodInfo]] = {}
        for qp in runnable:
            key = pod_group_key(qp.pod)
            if key is not None:
                by_key.setdefault(key, []).append(qp)
        if not by_key:
            return [], runnable
        units: list[tuple[str, list[QueuedPodInfo]]] = []
        taken: set[str] = set()
        unit_prof = None
        for key, qps in by_key.items():
            reason = self._gang_unit_fallback_reason(key, qps)
            if reason is None:
                prof = qps[0].pod.spec.scheduler_name
                if unit_prof is None:
                    unit_prof = prof
                elif prof != unit_prof:
                    # one enabled-filter set per launch: units of another
                    # profile ride the normal path this cycle
                    reason = "profiles_mixed"
            if reason is None:
                units.append((key, qps))
                taken.update(qp.uid for qp in qps)
            else:
                self.stats["gang_fallbacks"] += 1
                self.metrics.gang_fallbacks.inc(reason=reason)
        if not units:
            return [], runnable
        return units, [qp for qp in runnable if qp.uid not in taken]

    def _schedule_gang_units(self, runnable: list[QueuedPodInfo],
                             flush_pending=None) -> list[QueuedPodInfo]:
        """Route the batch's device-packable gang units through the
        fused packer; returns what the normal path still owns. Faults
        degrade the units to the host Permit path (the ladder), never
        kill the cycle."""
        if not self._gang_device or not runnable:
            return runnable
        units, rest = self._split_gang_units(runnable)
        if not units:
            return rest
        if flush_pending is not None:
            # commit in-flight pipelined launches first: their results
            # are what the usage chain (or the re-synced mirror) must
            # already reflect, and a rollback among them invalidates it
            flush_pending()
        # fault containment is PER CHUNK: a fault in chunk k may only
        # degrade chunk k's still-uncommitted members and the chunks
        # not yet dispatched — units chunk 0 already committed are mid
        # bind and must never re-enter any scheduling path
        fallback: list[QueuedPodInfo] = []
        for i in range(0, len(units), self.GANG_PACK_BUCKET):
            chunk = units[i:i + self.GANG_PACK_BUCKET]
            later = units[i + self.GANG_PACK_BUCKET:]
            try:
                fallback.extend(self._dispatch_gang_chunk(chunk))
            except Unavailable:
                self._note_hub_down()
                self._invalidate_chain()
                chunk_qps = [qp for _key, qps in chunk for qp in qps]
                for qp in self._still_pending(chunk_qps):
                    self._park_unreachable(qp)
                for _key, qps in later:
                    for qp in qps:
                        self._park_unreachable(qp)
                return rest + fallback
            except Exception as e:  # noqa: BLE001 — containment seam:
                # the Permit-quorum path still schedules these gangs
                self.stats["device_fallbacks"] += 1
                self.metrics.device_fallbacks.inc()
                self.last_device_fault = repr(e)
                self._invalidate_chain()
                degraded = chunk + later
                logger.warning(
                    "gang device path failed for %d unit(s) (%r); "
                    "degrading to the host Permit path", len(degraded), e,
                    exc_info=e)
                for _key, _qps in degraded:
                    self.stats["gang_fallbacks"] += 1
                    self.metrics.gang_fallbacks.inc(reason="device_fault")
                chunk_qps = [qp for _key, qps in chunk for qp in qps]
                fallback.extend(self._still_pending(chunk_qps))
                fallback.extend(qp for _key, qps in later for qp in qps)
                return rest + fallback
        return rest + fallback

    # gang-pack launch bucket: FIXED so every wave (warmup, first storm
    # wave, tail) runs ONE compiled program per cluster shape — a
    # units-count-sized pow2 bucket put a fresh XLA compile in the first
    # measured wave of every gang bench. Wider waves chunk (the chunks
    # chain their usage state, still O(1) launches per gang).
    GANG_PACK_BUCKET = 16

    def _dispatch_gang_chunk(self, units: list) -> list[QueuedPodInfo]:
        """ONE fused packing launch for a chunk of gang units + the
        atomic host commit of every unit that cleared the verdict.
        Returns members that must fall back to the normal path (a
        preempting gang the packer found infeasible)."""
        import jax.numpy as jnp

        from kubernetes_tpu.ops.features import PodBlobs
        from kubernetes_tpu.ops.gang import pack_gangs_jit

        launch = self.flight.span("gang_device")
        # chain-surviving churn: pending patches fold in before the pack
        # reads the chain (the caller already flushed the pipeline, so
        # no flush closure is needed for absolute repacks)
        if self._chain is not None \
                and (self._chain_dirty or self._chain_deltas):
            with self.flight.span("chain_patch"):
                self._apply_chain_patches()
        epoch = self._chain_epoch
        state = self._chain
        need_sync = state is None
        reps = [qps[0].pod for _key, qps in units]
        g_bucket = self.GANG_PACK_BUCKET
        for _attempt in range(16):
            try:
                if need_sync:
                    self.cache.update_snapshot(self.snapshot)
                    self.mirror.sync(self.snapshot)
                    self._chain_dirty.clear()
                    self._chain_deltas.clear()
                # nominated reservations must be CURRENT: the packer
                # subtracts them (and hands back each gang's own)
                self.mirror.set_nominated(self.nominator.by_node())
                feats = self.mirror.launch_features(reps)
                pfields = self.mirror.pod_fields(feats, False)
                f32, i32 = self.mirror._pack_batch_np(reps, g_bucket,
                                                      pfields)
                break
            except CapacityError as e:
                self._grow(e)
                state = None
                need_sync = True
        else:
            raise RuntimeError("mirror re-bucketing did not converge")
        if self.fault_injector is not None:
            # chaos seam: poison members / forced faults land here and
            # degrade the units to the Permit path via the caller
            self.fault_injector.on_pack(
                [qp.pod for _key, qps in units for qp in qps])
        tk, d_bucket = self.mirror.gang_pack_domain()
        need = np.zeros((g_bucket,), np.int32)
        own_nom = np.zeros((g_bucket, self.caps.nodes), np.int32)
        for i, (_key, qps) in enumerate(units):
            need[i] = len(qps)
            for qp in qps:
                nom = qp.pod.status.nominated_node_name
                if nom:
                    row = self.mirror.row_of(nom)
                    if row >= 0:
                        own_nom[i, row] += 1
        cblobs = self.mirror.to_blobs()
        if state is None:
            state = extract_state_jit(cblobs, self.caps)
        pcfg = self._profile_cfg[reps[0].spec.scheduler_name]
        out = pack_gangs_jit(
            cblobs, PodBlobs(f32=jnp.asarray(f32), i32=jnp.asarray(i32)),
            self.mirror.well_known(), self.caps, need, np.int32(tk),
            d_cap=d_bucket, enabled_filters=pcfg["filters"], active=feats,
            pfields=pfields, ptmpl=self.mirror.pod_template_blobs(),
            state=state, own_nom=jnp.asarray(own_nom))
        self.stats["gang_device_launches"] += 1
        self.metrics.gang_device_launches.inc()
        pshape = None
        prof = self.profiler
        if prof is not None:
            from kubernetes_tpu.telemetry.profiler import shape_key

            # the "gang" row of the shape key: a packer recompile (new
            # domain bucket / caps) is attributed, not "unattributed"
            pshape = shape_key(self.caps, g_bucket, False, d_bucket, 0,
                               True, False, False, False,
                               gang=g_bucket)
            prof.note_launch(pshape, len(units))
        # ONE pull for the whole wave: verdicts + placements + capacity
        # bounds + spans (+ any PreFilter capacity reductions awaiting
        # their ride — the folded gang_capacity D2H)
        cap_pulls = self._gang.take_pending_caps()
        pull = [out.ok, out.alloc, out.cap, out.spans, out.guard]
        pull.extend(arr for _key, _tok, arr in cap_pulls)
        vals = jax.device_get(tuple(pull))
        ok_arr, alloc_arr, cap_arr, spans_arr, guard = vals[:5]
        for (ckey, ctok, _arr), v in zip(cap_pulls, vals[5:]):
            self._gang.resolve_cap(ckey, ctok, int(v))
        launch_s = launch.end()
        if prof is not None and pshape is not None:
            prof.observe_walltime(pshape, launch_s)
        if int(guard):
            raise DeviceFault(
                f"gang pack guard tripped (mask {int(guard):#x}): "
                "poisoned usage state")
        commit = self.flight.span("gang_commit")
        fallback: list[QueuedPodInfo] = []
        alloc_np = np.asarray(alloc_arr)
        try:
            for i, (key, qps) in enumerate(units):
                # the packer's capacity column seeds the PreFilter memo:
                # the fallback bound never re-derives what this launch
                # already proved
                self._gang.note_device_cap(
                    key, self._gang.cap_token(self.mirror, qps[0].pod),
                    int(cap_arr[i]))
                counts = alloc_np[i]
                if bool(ok_arr[i]) and int(counts.sum()) == len(qps):
                    rows = np.repeat(np.arange(counts.shape[0]), counts)
                    names = [self.mirror.name_of_row(int(r))
                             for r in rows]
                    if any(nm is None for nm in names):
                        self.stats["gang_fallbacks"] += 1
                        self.metrics.gang_fallbacks.inc(reason="rows")
                        fallback.extend(qps)
                        continue
                    self._commit_gang_unit(key, qps, names)
                    continue
                if max((qp.pod.priority() for qp in qps), default=0) > 0:
                    # a positive-priority gang may open capacity by
                    # preempting: infeasibility is not provable — the
                    # host path's PostFilter owns it
                    self.stats["gang_fallbacks"] += 1
                    self.metrics.gang_fallbacks.inc(
                        reason="infeasible_preempting")
                    fallback.extend(qps)
                    continue
                group = self._gang.group_of(key)
                quorum = (max(group.min_member
                              - self._gang.bound_count(key), 1)
                          if group is not None else len(qps))
                if len(qps) > quorum:
                    # the packer places ALL present members or none; the
                    # Permit path can still admit the min_member quorum
                    # SUBSET when only that fits — don't park what the
                    # host path would schedule
                    self.stats["gang_fallbacks"] += 1
                    self.metrics.gang_fallbacks.inc(
                        reason="infeasible_partial")
                    fallback.extend(qps)
                    continue
                msg = (f"gang {key}: device packer found no "
                       f"all-or-nothing placement for {len(qps)} "
                       f"member(s) (capacity bound {int(cap_arr[i])})")
                for qp in qps:
                    qp.host_reject_counts = {}
                    self._park_unschedulable(qp, {"GangScheduling"}, msg)
        finally:
            commit.end()
        # the chain advances to the launch's post-batch state unless a
        # rollback/park above invalidated it (epoch check, like
        # _dispatch); parked/fallback units were never debited on device
        if epoch == self._chain_epoch:
            self._chain = (out.free, out.nzr)
        return fallback

    def _commit_gang_unit(self, key: str, qps: list[QueuedPodInfo],
                          node_names: list[str]) -> None:
        """Atomic host commit of one device-placed gang: reserve EVERY
        member first; any failure rolls the whole unit back before a
        single member reaches the binder (all-or-nothing, no Permit
        round-trips — the device verdict is the quorum)."""
        fw = self._fw_for(qps[0].pod)
        reserved: list[tuple] = []
        failure = None
        fail_i = len(qps)
        for i, (qp, node) in enumerate(zip(qps, node_names)):
            fail_i = i
            pod = qp.pod
            assumed = pod.clone()
            assumed.spec.node_name = node
            self.cache.assume_pod(assumed)
            state = CycleState()
            try:
                s = fw.run_reserve_plugins(state, pod, node)
            except Unavailable as e:
                failure = (qp, state, assumed, node,
                           f"reserve: {e}", "unreachable")
                break
            except Exception as e:  # noqa: BLE001 — poison seam, like
                # _commit: strike so a repeat offender quarantines
                self._fault_strikes[qp.uid] = \
                    self._fault_strikes.get(qp.uid, 0) + 1
                failure = (qp, state, assumed, node,
                           f"reserve raised: {e!r}", "")
                break
            if not s.is_success():
                failure = (qp, state, assumed, node,
                           f"reserve: {s.message()}",
                           s.plugin if s.is_rejected() else "")
                break
            reserved.append((qp, state, assumed, node))
        if failure is not None:
            self._gang.stats["rollbacks"] += 1
            self.metrics.gang_rollbacks.inc()
            fqp, fstate, fassumed, fnode, msg, tag = failure
            peer_msg = f"gang {key} rollback: peer {fqp.pod.key()}: {msg}"
            for qp, state, assumed, node in reserved:
                self._undo_commit(
                    qp, state, assumed, node, peer_msg,
                    rejected_by=("" if tag == "unreachable"
                                 else "GangScheduling"),
                    park_unreachable=(tag == "unreachable"))
            self._undo_commit(
                fqp, fstate, fassumed, fnode, msg,
                rejected_by=(tag if tag not in ("", "unreachable")
                             else ""),
                park_unreachable=(tag == "unreachable"))
            # members AFTER the failure never reserved, but they are
            # part of the all-or-nothing unit: park them with the same
            # attribution instead of dropping them from the queue
            for qp in qps[fail_i + 1:]:
                if tag == "unreachable":
                    self._park_unreachable(qp)
                else:
                    self._park_unschedulable(qp, {"GangScheduling"},
                                             peer_msg)
            return
        # every member reserved: the device verdict IS the quorum —
        # Permit answers allow for marked uids. Permits run for the
        # WHOLE unit before any member reaches the binder: a failure
        # rolls every member back (all-or-nothing holds through the
        # permit stage too — undoing only the failing member would
        # leave its peers binding as a partial gang).
        self._gang.device_admit(key, {qp.uid for qp, *_rest in reserved})
        verdicts: list[tuple] = []
        failure = None
        try:
            for qp, state, assumed, node in reserved:
                try:
                    s, waits = fw.run_permit_plugins(state, qp.pod, node)
                except Unavailable as e:
                    failure = (qp, f"permit: {e}", "unreachable")
                    break
                except Exception as e:  # noqa: BLE001
                    self._fault_strikes[qp.uid] = \
                        self._fault_strikes.get(qp.uid, 0) + 1
                    failure = (qp, f"permit raised: {e!r}", "")
                    break
                if not s.is_success() and s.code != Code.WAIT:
                    failure = (qp, f"permit: {s.message()}",
                               s.plugin if s.is_rejected() else "")
                    break
                verdicts.append((qp, state, assumed, node, s, waits))
        finally:
            self._gang.clear_device_admit(key)
        if failure is not None:
            self._gang.stats["rollbacks"] += 1
            self.metrics.gang_rollbacks.inc()
            fqp, msg, tag = failure
            peer_msg = f"gang {key} rollback: peer {fqp.pod.key()}: {msg}"
            for qp, state, assumed, node in reserved:
                own = qp.uid == fqp.uid
                if tag == "unreachable":
                    rej = ""
                elif own:
                    rej = tag    # "" (error class) or rejecting plugin
                else:
                    rej = "GangScheduling"
                self._undo_commit(
                    qp, state, assumed, node, msg if own else peer_msg,
                    rejected_by=rej,
                    park_unreachable=(tag == "unreachable"))
            return
        for qp, state, assumed, node, s, waits in verdicts:
            if s.code == Code.WAIT:
                # another permit plugin wants the wait room: honor it
                fw.waiting_pods.add(WaitingPod(qp, node, state, waits,
                                               self.now()))
            else:
                self._start_binding(qp, state, assumed, node)
        self._gang.stats["admitted"] += 1
        self._gang.stats["device_admitted"] += 1
        self.metrics.gang_admitted.inc()

    def _host_relevant(self, pod: Pod) -> bool:
        if self._host_gates is None:
            return True
        if self._has_host_scores and (
                self._host_score_gates is None
                or any(g(pod) for g in self._host_score_gates)):
            # host scoring applies to this pod (per-plugin applies()
            # probes — a host scorer must not re-route PLAIN pods
            # through the per-node Python score loop)
            return True
        if any(ext.is_interested(pod) for ext in self._extenders):
            return True
        return any(gate(pod) for gate in self._host_gates)

    def _defer_host_conflicts(self, runnable: list[QueuedPodInfo]
                              ) -> list[QueuedPodInfo]:
        """Host plugins can't see in-batch commits (their filters run once
        per batch against the snapshot), so two pods whose host verdicts
        can influence each other — a shared write-restricted volume, a
        ReadWriteOncePod claim, an unbound PVC both want — must not share a
        batch: keep the first, defer the rest to the next batch."""
        from kubernetes_tpu.plugins.dra import dra_serial_keys
        from kubernetes_tpu.plugins.volume import host_serial_keys

        seen: set[str] = set()
        keep: list[QueuedPodInfo] = []
        for qp in runnable:
            if not qp.pod.spec.volumes \
                    and not qp.pod.spec.resource_claims:
                keep.append(qp)
                continue
            keys = (host_serial_keys(self.hub, qp.pod)
                    | dra_serial_keys(self.hub, qp.pod))
            if keys & seen:
                self._deferred.append(qp)
            else:
                seen |= keys
                keep.append(qp)
        return keep

    def _run_host_plugins(self, runnable: list[QueuedPodInfo]):
        """Host Filter (and Score) plugins per pod over the synced snapshot;
        returns (host_ok [B, N] | None, host_score [B, N] | None) aligned to
        mirror rows. Plugins PreFilter-Skip irrelevant pods, so this is a
        few dict probes per pod for volume-less workloads."""
        relevant = [
            (i, qp) for i, qp in enumerate(runnable)
            if self._host_relevant(qp.pod)]
        if not relevant:
            return None, None
        ext_names = ext_rows = None
        if self._extenders:
            ext_names = [ni.node.metadata.name
                         for ni in self.snapshot.node_info_list]
            ext_rows = {n: self.mirror.row_of(n) for n in ext_names}
        # host plugins read the HUB (claims, pod placements): every
        # outstanding binding cycle must land first or a conflict check
        # could miss a just-bound pod
        self._drain_bind_results(wait=True)
        infos = self.snapshot.node_info_list
        host_ok = None
        host_score = None
        rows = None
        b_cap = self.config.batch_size
        n_cap = self.caps.nodes

        def node_rows():
            nonlocal rows
            if rows is None:
                rows = np.array([self.mirror.row_of(ni.name)
                                 for ni in infos], np.int64)
            return rows

        for i, qp in relevant:
            qp.host_reject_counts = {}
            state = CycleState()
            fw = self._fw_for(qp.pod)
            mask, counts, early = fw.run_host_filters(state, qp.pod, infos)
            if counts:
                qp.host_reject_counts = counts
            if early is not None:
                if host_ok is None:
                    host_ok = np.ones((b_cap, n_cap), bool)
                host_ok[i, :] = False
                continue
            if mask is not None and not all(mask):
                if host_ok is None:
                    host_ok = np.ones((b_cap, n_cap), bool)
                r = node_rows()
                bad = r[~np.asarray(mask, bool)]
                host_ok[i, bad[bad >= 0]] = False
            scores = (fw.run_host_scores(state, qp.pod, infos)
                      if self._has_host_scores else None)
            if scores is not None:
                if host_score is None:
                    host_score = np.zeros((b_cap, n_cap), np.float32)
                r = node_rows()
                ok = r >= 0
                host_score[i, r[ok]] = np.asarray(scores, np.float32)[ok]
            if ext_names is not None:
                host_ok, host_score = self._run_extenders(
                    qp, i, ext_names, ext_rows, host_ok, host_score,
                    b_cap, n_cap)
        return (jnp.asarray(host_ok) if host_ok is not None else None,
                jnp.asarray(host_score) if host_score is not None else None)

    def _run_extenders(self, qp, i, names, name_row, host_ok, host_score,
                       b_cap, n_cap):
        """Legacy HTTP extenders (extender.go:248 Filter, :319
        Prioritize): verdicts AND into the host mask, weighted scores add
        into the aggregate; an unreachable ignorable extender is skipped,
        a non-ignorable one fails the pod for this cycle."""
        from kubernetes_tpu.extender import ExtenderError

        interested = [ext for ext in self._extenders
                      if ext.is_interested(qp.pod)]
        if not interested:
            return host_ok, host_score
        candidates = list(names)
        for ext in interested:
            try:
                nodes = None
                if not ext.cfg.node_cache_capable:
                    # non-nodeCacheCapable: ship full node objects
                    # (extender.go:258 Nodes vs NodeNames)
                    nodes = [info.node for name in candidates
                             if (info := self.snapshot.node_info_map.get(
                                 name)) is not None]
                passed, failed = ext.filter(qp.pod, candidates, nodes)
                scores = ext.prioritize(qp.pod, candidates, nodes)
            except ExtenderError as e:
                if ext.cfg.ignorable:
                    continue
                qp.host_reject_counts[ext.name] = len(candidates)
                if host_ok is None:
                    host_ok = np.ones((b_cap, n_cap), bool)
                host_ok[i, :] = False
                logger.warning("extender failed: %s", e)
                return host_ok, host_score
            rejected = set(failed) | (set(candidates) - set(passed))
            if rejected:
                qp.host_reject_counts[ext.name] = (
                    qp.host_reject_counts.get(ext.name, 0) + len(rejected))
                if host_ok is None:
                    host_ok = np.ones((b_cap, n_cap), bool)
                for name in rejected:
                    row = name_row.get(name, -1)
                    if row >= 0:
                        host_ok[i, row] = False
                candidates = [n for n in candidates if n not in rejected]
            if scores:
                if host_score is None:
                    host_score = np.zeros((b_cap, n_cap), np.float32)
                for name, sc in scores.items():
                    row = name_row.get(name, -1)
                    if row >= 0:
                        host_score[i, row] += sc
        return host_ok, host_score

    def _pull_launch(self, out: BatchResult, flags: tuple,
                     tr=None) -> tuple:
        """The commit-thread half of _finish: ONE blocking D2H pull of the
        launch's verdict tensors (rows + guard + the flag-gated
        learned-magnitude / export tensors — a second device_get would be
        a second full round trip). Under pipelined waves this runs on the
        commit thread so the transfer wait — the wave's actual
        serialization — overlaps the next wave's device time. It touches
        NO host state (the single-mutator invariant: assume/bind/queue
        mutation stays on the loop thread) and takes no locks; exceptions
        (including the chaos commit_pull seam) surface in _finish via
        fut.result() and ride the normal containment ladder. Returns
        (vals, t_ready) — t_ready timestamps verdict availability (the
        honest end of the device span). With ``tr`` (off-thread) this
        thread's own wall inside the pull is the cycle's overlapped
        commit_pull span, reported HERE, on this thread, when the pull
        ends; inline, the caller's device_launch span covers it."""
        if tr is None:
            return self._pull_verdicts(out, flags), self.now()
        with self.flight.span("commit_pull", tr) as pull:
            vals = self._pull_verdicts(out, flags)
        return vals, pull.t1

    def _pull_verdicts(self, out: BatchResult, flags: tuple) -> tuple:
        learned_on, exporting, want_feats, want_alts = flags
        fi = self.fault_injector
        if fi is not None:
            hook = getattr(fi, "on_commit_pull", None)
            if hook is not None:
                hook()          # chaos seam: may raise
        pull = [out.node_row, out.guard]
        if learned_on:
            pull.append(out.learned_mag)
        if exporting:
            pull.append(out.score)
            if want_feats:
                pull.append(out.chosen_feat)
            if want_alts:
                pull.append(out.alt_row)
                pull.append(out.alt_score)
        return jax.device_get(tuple(pull))

    def _finish(self, inflight: tuple) -> None:
        """Pull one dispatched launch's results and commit/fail each pod."""
        (runnable, out, t_dispatched, pack_s, tr, flags,
         pshape, compiled, fut) = inflight
        learned_on, exporting, want_feats, want_alts = flags
        # re-attach the cycle's trace: the pipelined drain may have
        # dispatched k+1 (opening its trace) before finishing k
        self.flight.resume(tr)
        span = self.flight.span
        n = len(runnable)
        # device_launch is the loop thread's ACTUAL blocked time — the
        # wave's serial cost — reported the moment the verdicts are in
        # hand, before the commit loop. Off-thread commit: the pull has
        # been running on the commit thread since dispatch (its own
        # commit_pull span overlapped loop-thread work); a commit-thread
        # exception re-raises HERE and rides the same _finish_contained
        # blast-radius ladder an inline fault would. Pipelining off: the
        # pull runs inline and the loop is blocked for all of it.
        with span("device_launch", tr):
            vals, t_ready = (fut.result() if fut is not None
                             else self._pull_launch(out, flags))
        # PreFilter gang-capacity reductions cannot ride the commit
        # thread's pull (they register on the loop thread, possibly
        # after dispatch); rare — gang PreFilter only — so they get
        # their own small transfer when present
        cap_pulls = self._gang.take_pending_caps()
        if cap_pulls:
            cvals = jax.device_get(
                tuple(arr for _key, _tok, arr in cap_pulls))
            for (ckey, ctok, _arr), v in zip(cap_pulls, cvals):
                self._gang.resolve_cap(ckey, ctok, int(v))
        rows_arr, guard = vals[0], vals[1]
        k = 2
        lmag = None
        if learned_on:
            lmag = vals[k]
            k += 1
        scores_arr = feats_arr = alt_rows_arr = alt_scores_arr = None
        if exporting:
            scores_arr = vals[k]
            k += 1
            if want_feats:
                feats_arr = vals[k]
                k += 1
            if want_alts:
                alt_rows_arr = vals[k]
                alt_scores_arr = vals[k + 1]
        if int(guard):
            # the launch's own guard reduction tripped: NaN scores or a
            # poisoned usage chain — nothing below can be trusted; the
            # containment wrapper degrades this batch to the host path
            raise DeviceFault(
                f"launch guard tripped (mask {int(guard):#x}): "
                f"{'NaN scores ' if int(guard) & 1 else ''}"
                f"{'poisoned usage state' if int(guard) & 2 else ''}")
        if lmag is not None:
            # observed only AFTER the guard check: a NaN-poisoned
            # checkpoint must not corrupt the magnitude histogram's sum
            # forever (Histogram.observe accumulates the raw value)
            self.metrics.learned_magnitude.observe(float(lmag))
        rows = np.asarray(rows_arr)[:n].tolist()
        # the device span ends when the verdict pull completed (t_ready,
        # stamped by whichever thread ran it) — under pipelining the loop
        # may harvest the future long after, and that host overlap time
        # must not masquerade as device time
        launch_s = max(t_ready - t_dispatched, 0.0)
        if exporting:
            # export v2/v3 placement rows: (pod, chosen node, aggregate
            # score[, chosen-node feature vector when
            # trace_export_features][, top-K alternative node scores
            # when trace_export_alts]) — the replay dataset's substrate,
            # already pulled with rows+guard above. Failed attempts
            # export node=None (time-to-bind anchors).
            placements = []
            for i, (qp, row) in enumerate(zip(runnable, rows)):
                rec = {"pod": qp.pod.key(), "uid": qp.uid}
                if row >= 0:
                    rec["node"] = self.mirror.name_of_row(row)
                    rec["score"] = round(float(scores_arr[i]), 4)
                    if feats_arr is not None:
                        rec["feat"] = [round(float(v), 5)
                                       for v in feats_arr[i]]
                    if alt_rows_arr is not None:
                        # the chosen node's own entry RIDES ALONG when
                        # top_k surfaced it: on the auction path the
                        # alt scores are end-state attributed while
                        # "score" is the decision-round win — regret
                        # must compare chosen vs alternatives on ONE
                        # basis, so the offline consumer prefers the
                        # chosen node's in-list score as its value
                        alt = []
                        for ar, asc in zip(alt_rows_arr[i],
                                           alt_scores_arr[i]):
                            if int(ar) < 0 or float(asc) <= ALT_NONE / 2:
                                continue
                            nm = self.mirror.name_of_row(int(ar))
                            if nm:
                                alt.append([nm, round(float(asc), 4)])
                        rec["alt"] = alt
                else:
                    rec["node"] = None
                # the wire-trace stamps known at commit time (the
                # "created" hub-commit stamp and its hop count join
                # offline analysis to the cluster's commit clock; the
                # ack stamps land later via /debug/pod)
                wire = self.timelines.wire_of(qp.uid)
                if wire:
                    rec["wire"] = wire
                placements.append(rec)
            tr.placements = placements
        t1 = self.now()
        # reject attribution is only read on failure; skipping the [B, P]
        # pull when every pod placed keeps the host<->device link to one
        # tiny [B] row vector. NOTE: an on-device gather of just the
        # failed rows measured SLOWER — a gather is a compute op that
        # queues behind the already-dispatched next launch, while
        # device_get of a materialized array is a pure transfer
        fail_is = [i for i in range(n) if rows[i] < 0]
        rejects = None
        if fail_is:
            # the rows/guard pull above is inseparable from the device
            # wait (folded into device_launch); this one is a pure
            # post-compute transfer — the honest D2H measurement
            with span("d2h_pull", tr):
                rejects, dra_rej = jax.device_get((out.reject_counts,
                                                   out.dra_reject))
            rejects = np.asarray(rejects)
            # fused DRA rejections fold into host_reject_counts so
            # diagnosis, requeue hints, and the preemption fast-path
            # gate behave exactly as they did on the host filter path
            for i in fail_is:
                c = int(dra_rej[i])
                if c:
                    runnable[i].host_reject_counts["DynamicResources"] = c
        n_fail = len(fail_is)
        last = span("commit", tr)
        try:
            for qp, row in zip(runnable, rows):
                if row >= 0:
                    self._commit(qp, self.mirror.name_of_row(row))
            if fail_is:
                last.end()
                last = span("failure_handling", tr)
                self._handle_failures([(runnable[i], rejects[i].tolist())
                                       for i in fail_is])
            # the cycle's own accounts ride its last span (what runs
            # after a cycle's last span is nobody's phase): all but the
            # record itself, which flushes the spans and so follows them
            commit_s = self.now() - t1
            cycle_s = pack_s + launch_s + commit_s
            if self.profiler is not None and pshape is not None:
                self.profiler.observe_walltime(pshape, launch_s)
                if compiled:
                    # attribution view: this cycle's launch walltime was
                    # (mostly) an XLA compile — the stall MixedChurn's
                    # re-bucketing pays, now visible per phase
                    tr.add("device_compile", launch_s)
            tr.scheduled = n - n_fail
            tr.failed = n_fail
            m = self.metrics
            m.algorithm_duration.observe(launch_s)
            m.batch_duration.observe(cycle_s)
            m.extension_point_duration.observe(
                pack_s, extension_point="PreFilter")
            m.extension_point_duration.observe(
                launch_s, extension_point="Filter")
            m.extension_point_duration.observe(
                commit_s, extension_point="Reserve")
            per_pod = cycle_s / max(n, 1)
            if n - n_fail:
                m.attempt_duration.observe(per_pod, n=n - n_fail,
                                           result="scheduled")
            if n_fail:
                m.attempt_duration.observe(per_pod, n=n_fail,
                                           result="unschedulable")
        finally:
            last.end()
        self.flight.record(tr)
        tr.log_if_slow(cycle_s, SLOW_CYCLE_SECONDS, logger,
                       pods=n, scheduled=n - n_fail)

    def schedule_one_batch(self) -> int:
        """Pop up to batch_size pods, run one device launch, commit results.
        Returns the number of pods attempted (0 = queue idle)."""
        with self._lock:
            self._process_deferred_events()
            self._process_waiting()
            if self.jobqueue.active:
                self.jobqueue.release(self.queue, self._effective_batch())
            popped, runnable, tr = self._pop_runnable()
            if popped == 0:
                self._drain_bind_results(wait=True)
                self._flush_evictions_safe()
                self._process_deferred_events()
                return 0
            if runnable:
                # device-packable gang units commit through their own
                # fused launch first; the normal path keeps the rest
                runnable = self._schedule_gang_units(runnable)
            if runnable:
                try:
                    inflight = self._dispatch(
                        runnable, self._chain_eligible(
                            [qp.pod for qp in runnable]), tr)
                except Unavailable:
                    self._park_batch_unreachable(runnable)
                    inflight = None
                except Exception as e:  # noqa: BLE001 — containment seam
                    self._contain_batch_fault(runnable, e)
                    inflight = None
                if inflight is not None:
                    self._finish_contained(inflight)
            self._drain_bind_results(wait=True)
            # async preemption: victims queued by PostFilter are evicted
            # here, OUTSIDE the cycle (prepareCandidateAsync's analog)
            self._flush_evictions_safe()
            self._process_deferred_events()
            return popped

    def _commit(self, qp: QueuedPodInfo, node_name: str) -> None:
        """assume -> reserve -> permit (schedule_one.go:142); the binding
        cycle (prebind/bind) then runs on the binder pool
        (schedule_one.go:124's per-pod goroutine) and completes via
        _drain_bind_results. A WAIT permit parks the pod in the
        waitingPodsMap with its reservation held."""
        pod = qp.pod
        assumed = pod.clone()
        assumed.spec.node_name = node_name
        if self.cache.get_pod(assumed) is not None \
                and not self.cache.is_assumed_pod(assumed):
            # the pod is already in the cache CONFIRMED: a sibling
            # replica's bind landed through our informer between the
            # pop and this commit (scale-out post-rebalance race).
            # assume_pod would raise ("already in cache") and take the
            # whole device batch down the host-fallback ladder — the
            # pod is placed and theirs; drop our attempt exactly like
            # _undo_commit's foreign-confirm path
            if self.flight.enabled:
                self.timelines.event(
                    qp.pod, "foreign_bound",
                    f"confirmed on "
                    f"{self.cache.get_pod(assumed).spec.node_name} "
                    f"by a sibling replica (pre-commit)")
            self._invalidate_chain()
            self.queue.done(qp.uid)
            return
        self.cache.assume_pod(assumed)
        state = CycleState()
        fw = self._fw_for(pod)
        # binding a pod with (anti)affinity terms makes the mirror's pod
        # table stale: the chain must not skip the sync that packs it
        if self.mirror.batch_has_topology([pod]):
            self._invalidate_chain()
        try:
            s = fw.run_reserve_plugins(state, pod, node_name)
        except Unavailable as e:
            # reserve plugins read the hub (DRA claims): an outage here
            # must not wedge the rest of the batch in-flight — undo the
            # assume and park this pod like any other unreachable write
            self._undo_commit(qp, state, assumed, node_name,
                              f"reserve: {e}", park_unreachable=True)
            return
        except Exception as e:  # noqa: BLE001 — a raising out-of-tree
            # plugin must not strand the assume (the pod would be a
            # phantom placement forever); error path + strike so a
            # repeat offender quarantines
            self._fault_strikes[qp.uid] = \
                self._fault_strikes.get(qp.uid, 0) + 1
            self._undo_commit(qp, state, assumed, node_name,
                              f"reserve raised: {e!r}")
            return
        if not s.is_success():
            # a REJECTING reserve (e.g. DRA "devices vanished" — the
            # designed same-batch capacity race) is unschedulable with
            # plugin attribution, not a scheduler error; only raising
            # plugins land on the error path
            self._undo_commit(qp, state, assumed, node_name,
                              f"reserve: {s.message()}",
                              rejected_by=(s.plugin if s.is_rejected()
                                           else ""))
            return
        try:
            s, waits = fw.run_permit_plugins(state, pod, node_name)
        except Unavailable as e:
            self._undo_commit(qp, state, assumed, node_name,
                              f"permit: {e}", park_unreachable=True)
            return
        except Exception as e:  # noqa: BLE001 — same containment as
            # reserve: undo the assume, error path, strike
            self._fault_strikes[qp.uid] = \
                self._fault_strikes.get(qp.uid, 0) + 1
            self._undo_commit(qp, state, assumed, node_name,
                              f"permit raised: {e!r}")
            return
        if s.code == Code.WAIT:
            fw.waiting_pods.add(WaitingPod(qp, node_name, state, waits,
                                           self.now()))
            return
        if not s.is_success():
            self._undo_commit(qp, state, assumed, node_name,
                              f"permit: {s.message()}",
                              rejected_by=(s.plugin if s.is_rejected()
                                           else ""))
            return
        self._start_binding(qp, state, assumed, node_name)

    def _undo_commit(self, qp: QueuedPodInfo, state: CycleState,
                     assumed: Pod, node_name: str, msg: str,
                     rejected_by: str = "",
                     park_unreachable: bool = False) -> None:
        """Unreserve + Forget, then requeue: error-class for infrastructure
        failures (schedule_one.go:337's bind-failure path), unschedulable
        with plugin attribution when a plugin REJECTED the pod (permit
        reject/timeout goes through handleSchedulingFailure as
        Unschedulable, schedule_one.go:270). ``park_unreachable`` routes a
        hub-outage failure to the degraded-mode park instead — the bind
        may or may not have landed; the informer's relist decides, and the
        hub's bind-once Conflict guarantees no double-bind either way."""
        try:
            self._fw_for(qp.pod).run_unreserve_plugins(state, qp.pod,
                                                       node_name)
        except Unavailable:
            # hub-side claim state reconciles via informer truth after
            # the outage; the local overlay cleanup below is what matters
            self._note_hub_down()
        if not self.cache.is_assumed_pod(assumed) \
                and self.cache.get_pod(assumed) is not None:
            # the pod is in the cache CONFIRMED, not assumed: another
            # actor's bind landed through our informer while this
            # attempt was failing (scale-out: a sibling replica won a
            # post-rebalance race and add_pod's informer-truth-wins
            # replaced our assumed state; our own bind then answered
            # Conflict). The pod is placed and theirs — forget_pod
            # would raise ("confirmed, cannot forget") and requeueing
            # would re-schedule a bound pod. Drop our claim instead,
            # exactly like _finish_fenced's foreign-confirm path.
            if self.flight.enabled:
                self.timelines.event(
                    qp.pod, "foreign_bound",
                    f"confirmed on "
                    f"{self.cache.get_pod(assumed).spec.node_name} "
                    f"by a sibling replica (undo-commit)")
            self._invalidate_chain()
            self.queue.done(qp.uid)
            return
        self.cache.forget_pod(assumed)
        # the device chain assumed this placement; force a re-sync
        self._invalidate_chain()
        if park_unreachable:
            self._note_hub_down()
            self._park_unreachable(qp)
            return
        if rejected_by:
            if self.flight.enabled:
                self.timelines.diagnose(qp.pod, {}, {rejected_by: -1}, msg)
                self.timelines.event(qp.pod, "unschedulable", msg)
            qp.unschedulable_plugins = {rejected_by}
            qp.unschedulable_count += 1
            qp.consecutive_errors_count = 0
            self.stats["unschedulable"] += 1
            self._patch_condition_best_effort(qp.pod, PodCondition(
                type="PodScheduled", status="False", reason="Unschedulable",
                message=msg))
            self.queue.add_unschedulable_if_not_present(qp)
        else:
            self._error(qp, msg)

    def _extenders_binding(self, pod: Pod, node_name: str):
        """First interested binder extender binds INSTEAD of the bind
        plugins (schedule_one.go:960 extendersBinding). Returns a Status
        or None when no extender claims the pod."""
        from kubernetes_tpu.extender import ExtenderError
        from kubernetes_tpu.framework.interface import Status

        for ext in self._extenders:
            if not ext.is_binder or not ext.is_interested(pod):
                continue
            try:
                ext.bind(pod, node_name)
                # the extender performed the API binding; reflect it in
                # the hub like the Binding POST would (fenced: a deposed
                # leader's delegated bind must be rejected too)
                self._fenced_bind(pod, node_name)
                return Status()
            except Unavailable:
                raise    # transport outage: degraded mode parks the pod
            except Fenced:
                raise    # deposed epoch: _bind_task tags, claim released
            except ExtenderError as e:
                return Status.error(str(e))
            except Exception as e:  # noqa: BLE001
                return Status.error(f"extender bind raised: {e!r}")
        return None

    def _bind_task(self, state: CycleState, pod: Pod, node_name: str,
                   fargs: tuple = None):
        fw = self._fw_for(pod)
        t0 = time.monotonic()
        if fargs is not None:
            # decision-time fencing token (see _fenced_bind)
            self._bind_fence.args = fargs
        try:
            s = fw.run_pre_bind_plugins(state, pod, node_name)
            if s.is_success():
                ext_s = self._extenders_binding(pod, node_name)
                s = ext_s if ext_s is not None \
                    else fw.run_bind_plugins(state, pod, node_name)
        except Unavailable as e:
            # hub outage mid-bind: tagged so _finish_binding parks the
            # pod in degraded mode instead of taking the error path
            from kubernetes_tpu.framework.interface import Status

            s = Status.error(f"hub unavailable: {e}",
                             plugin="HubUnavailable")
        except Fenced as e:
            # we were deposed while this bind was in flight: the hub
            # rejected it, the new leader owns the pod now — tagged so
            # _finish_binding releases our claim without status writes
            from kubernetes_tpu.framework.interface import Status

            s = Status.error(f"fenced: {e}", plugin="Fenced")
        except Exception as e:  # noqa: BLE001 — a raising out-of-tree
            # plugin must not poison the chunk/future (every other pod in
            # it would stay assumed forever)
            from kubernetes_tpu.framework.interface import Status

            s = Status.error(f"bind cycle raised: {e!r}")
        finally:
            self._bind_fence.args = None    # don't leak across chunks
        self.recorder.observe(self.metrics.extension_point_duration,
                              time.monotonic() - t0, extension_point="Bind")
        return s

    def _start_binding(self, qp: QueuedPodInfo, state: CycleState,
                       assumed: Pod, node_name: str) -> None:
        # the fencing token travels WITH the bind from here: the epoch
        # this placement was decided under, not whatever the elector
        # holds when the binder thread finally executes it
        fargs = self._fencing_args()
        if self._binder is None:
            self._finish_binding(qp, state, assumed, node_name,
                                 self._bind_task(state, qp.pod, node_name,
                                                 fargs))
            self._process_deferred_events()
        else:
            # per-pod futures are too fine for python threads; the backlog
            # is chunked across the pool by _submit_bind_backlog
            self._bind_backlog.append((qp, state, assumed, node_name,
                                       fargs))

    def _submit_bind_backlog(self) -> bool:
        """Chunk the bind backlog across the binder pool; whether there
        was any."""
        backlog, self._bind_backlog = self._bind_backlog, []
        if not backlog:
            return False
        workers = max(1, self.config.binding_workers)
        chunk = max(1, -(-len(backlog) // workers))

        def run_chunk(items):
            # on a binder worker, beside the loop: an overlap phase, as
            # commit_pull is on the commit thread
            with self.flight.span("bind_chunk"):
                return [self._bind_task(state, qp.pod, node_name, fargs)
                        for qp, state, assumed, node_name, fargs in items]

        for i in range(0, len(backlog), chunk):
            items = backlog[i:i + chunk]
            self._inflight_binds.append(
                (items, self._binder.submit(run_chunk, items)))
        return True

    def _drain_bind_results(self, wait: bool = False) -> None:
        """Collect finished binding cycles (all of them when ``wait``);
        the binder thread's own hub events replay here, on the loop
        thread, right after each completion."""
        if not self._bind_backlog and not self._inflight_binds:
            return
        queue = self.queue
        scan_s0 = queue.trim_scan_s
        sp = self.flight.span("binder_drain")
        # handing the last launch's binds to the pool is this phase's
        # work too, and a phase even where no chunk has finished yet
        submitted = self._submit_bind_backlog()
        drained = False
        still: list[tuple] = []
        for item in self._inflight_binds:
            items, fut = item
            if wait or fut.done():
                drained = True
                for (qp, state, assumed, node_name, _fargs), s in zip(
                        items, fut.result()):
                    self._finish_binding(qp, state, assumed, node_name, s)
                self._process_deferred_events()
            else:
                still.append(item)
        self._inflight_binds = still
        if drained and self.flight.enabled:
            # the queue's own view of this drain, reported just before
            # its parent: seconds its done() calls spent scanning the
            # in-flight event log (0.0 = none scanned), and the log's
            # two metrics
            self.flight.observe_view("queue_done",
                                     queue.trim_scan_s - scan_s0)
            self.metrics.queue_event_log_entries.set(
                float(queue.event_log_len()))
            self._mirror_count("queue_trims", queue.trim_scans,
                               self.metrics.queue_event_trims)
        sp.end(report=drained or submitted)

    def _finish_binding(self, qp: QueuedPodInfo, state: CycleState,
                        assumed: Pod, node_name: str, s) -> None:
        if not s.is_success():
            if s.plugin == "Fenced":
                self._finish_fenced(qp, state, assumed, node_name)
                return
            self._undo_commit(qp, state, assumed, node_name,
                              f"bind: {s.message()}",
                              park_unreachable=(
                                  s.plugin == "HubUnavailable"))
            return
        self.cache.finish_binding(assumed)
        self.nominator.delete(qp.uid)
        self.queue.done(qp.uid)
        self._fault_strikes.pop(qp.uid, None)
        self._fw_for(qp.pod).run_post_bind_plugins(state, qp.pod, node_name)
        qp.consecutive_errors_count = 0
        self.stats["scheduled"] += 1
        self.metrics.schedule_attempts.inc(
            result="scheduled", profile=qp.pod.spec.scheduler_name)
        self.metrics.pod_scheduling_attempts.observe(qp.attempts)
        if self.flight.enabled:
            # the reference's e2e pod_scheduling_duration_seconds: first
            # attempt -> successful bind, by attempts needed (capped so
            # the label set stays bounded)
            t_bind = self.now()
            if qp.initial_attempt_timestamp is not None:
                self.metrics.pod_e2e_duration.observe(
                    t_bind - qp.initial_attempt_timestamp,
                    attempts=str(min(qp.attempts, 16)))
            self.timelines.event(qp.pod, "bound", node_name, t=t_bind)

    def _finish_fenced(self, qp: QueuedPodInfo, state: CycleState,
                       assumed: Pod, node_name: str) -> None:
        """A deposed leader's in-flight bind was rejected by the fencing
        check: release the optimistic claim quietly. NO condition patch
        (the new leader owns the pod's status — and ours are fenced
        anyway) and no error accounting — the pod did nothing wrong. It
        parks error-class so a later re-election finds it retryable;
        the new leader's bind confirms through the informer and deletes
        it from our queue like any foreign placement."""
        self.stats["fenced"] += 1
        self.metrics.fenced_writes.inc(verb="bind")
        telemetry.incident(self, "fenced_bind",
                           reason="in-flight bind rejected by fencing "
                                  "(leadership deposed)",
                           pod=qp.pod.key(), node=node_name)
        try:
            self._fw_for(qp.pod).run_unreserve_plugins(state, qp.pod,
                                                       node_name)
        except Unavailable:
            self._note_hub_down()
        if not self.cache.is_assumed_pod(assumed):
            # the new leader's bind of this pod already CONFIRMED through
            # our informer (add_pod replaced the assumed state): the pod
            # is theirs, placed and cached — nothing to forget or requeue
            if self.flight.enabled:
                cached = self.cache.get_pod(assumed)
                self.timelines.event(
                    qp.pod, "foreign_bound",
                    f"confirmed on "
                    f"{cached.spec.node_name if cached else '?'} "
                    f"by the new leader (fenced)")
            self.queue.done(qp.uid)
            return
        self.cache.forget_pod(assumed)
        self._invalidate_chain()
        qp.unschedulable_plugins = set()
        qp.consecutive_errors_count += 1
        self.queue.add_unschedulable_if_not_present(qp)

    def _process_waiting(self) -> None:
        """Harvest the waitingPodsMap: fully-allowed pods proceed to the
        binding cycle; rejected/timed-out pods unreserve and requeue
        (waiting_pods_map.go semantics)."""
        ready: list = []
        failed: list = []
        for fw in self.frameworks.values():
            r, f = fw.waiting_pods.harvest(self.now())
            ready.extend(r)
            failed.extend(f)
        for wp in ready:
            assumed = wp.qp.pod.clone()
            assumed.spec.node_name = wp.node_name
            self._start_binding(wp.qp, wp.state, assumed, wp.node_name)
        for wp, s in failed:
            assumed = wp.qp.pod.clone()
            assumed.spec.node_name = wp.node_name
            self._undo_commit(wp.qp, wp.state, assumed, wp.node_name,
                              s.message(), rejected_by=s.plugin or "Permit")

    def _handle_failures(self, failures: list[tuple]) -> None:
        """handleSchedulingFailure (schedule_one.go:1015) for a whole
        batch: record diagnoses, run PostFilter (preemption), patch
        conditions, park. Fit-only rejections of equal priority share ONE
        batched preemption sweep (Evaluator.batch_preempt) — a churn of
        identical preemptors costs one launch, not one per pod, and burst
        members never target the same capacity."""
        fit_idx = FILTER_PLUGINS.index("NodeResourcesFit")
        prepped = []
        any_pf = False
        for qp, reject_counts in failures:
            # NOTE: auction-mode (parallel-rounds) launches attribute
            # reject_counts against END-state capacity, not the state each
            # pod was evaluated under mid-drain (_rounds_commit) — plugin
            # attribution is exact, counts are post-drain. The serial scan
            # is exact per step.
            plugins = {FILTER_PLUGINS[i]
                       for i, c in enumerate(reject_counts) if c > 0}
            plugins |= set(qp.host_reject_counts)
            if self.flight.enabled:
                # /debug/pod diagnosis: which device filter rejected how
                # many nodes (the already-pulled reject_counts), which
                # host plugin rejected (host_reject_counts)
                self.timelines.diagnose(
                    qp.pod,
                    {FILTER_PLUGINS[i]: int(c)
                     for i, c in enumerate(reject_counts) if c > 0},
                    qp.host_reject_counts,
                    "no feasible node (device launch)")
                self.timelines.event(qp.pod, "unschedulable",
                                     ",".join(sorted(plugins)))
            qp.unschedulable_plugins = plugins or {"NodeResourcesFit"}
            qp.unschedulable_count += 1
            qp.consecutive_errors_count = 0
            self.stats["unschedulable"] += 1
            self.metrics.schedule_attempts.inc(
                result="unschedulable", profile=qp.pod.spec.scheduler_name)
            has_pf = bool(self._fw_for(qp.pod).points["post_filter"])
            pcfg = self._profile_cfg.get(qp.pod.spec.scheduler_name, {})
            fit_only = (pcfg.get("batch_preempt_ok", False)
                        and not qp.host_reject_counts
                        and all(c == 0 for i, c in enumerate(reject_counts)
                                if i != fit_idx))
            any_pf = any_pf or has_pf
            prepped.append((qp, reject_counts, plugins, has_pf, fit_only))
        nominated_by_uid: dict[str, str | None] = {}
        if any_pf:
            select_s0 = self.preemption.select_s
            # chained launches skip the per-batch sync; preemption reads
            # the host snapshot + mirror, so refresh (O(1) when clean)
            self.cache.update_snapshot(self.snapshot)
            self.mirror.sync(self.snapshot)
            # batched sweep for fit-only preemptors, grouped by priority
            # grouped by (priority, profile): the sweep applies ONE
            # enabled-filter set per chunk, which is per-profile state
            groups: dict[tuple, list] = {}
            for qp, _rej, _pl, has_pf, fit_only in prepped:
                if has_pf and fit_only:
                    groups.setdefault(
                        (qp.pod.priority(), qp.pod.spec.scheduler_name),
                        []).append(qp)
            for _key, qps in groups.items():
                # NOTE: deferring the sweep harvest across iterations
                # (begin here, finish next cycle) was measured ~2x SLOWER
                # on PreemptionAsync: the extra cycle of nomination latency
                # per burst outweighs the hidden device wait. Synchronous
                # begin+finish it stays.
                try:
                    results = self.preemption.batch_preempt(qps,
                                                            self.snapshot)
                except Unavailable:
                    # outage mid-sweep: no nominations this round; the
                    # parked preemptors retry after backoff
                    self._note_hub_down()
                    results = {}
                for uid, (node, _status) in results.items():
                    nominated_by_uid[uid] = node
                    if node:
                        self.stats["preemptions"] = self.stats.get(
                            "preemptions", 0) + 1
            # the evaluator's own view of the sweeps, reported inside
            # failure_handling: seconds in batch_preempt (0.0 = nothing
            # to sweep)
            self.flight.observe_view(
                "preempt_select", self.preemption.select_s - select_s0)
            if not self.config.gate("SchedulerAsyncPreemption"):
                # gate off: prepare candidates synchronously, inside the
                # failure handling (pre-kep-4832 behavior)
                self._flush_evictions_safe()
        for qp, reject_counts, plugins, has_pf, fit_only in prepped:
            if has_pf and not fit_only:
                state = CycleState()
                try:
                    nominated, _s = self._fw_for(
                        qp.pod).run_post_filter_plugins(
                        state, qp.pod, {"snapshot": self.snapshot,
                                        "reject_counts": reject_counts,
                                        "host_rejects":
                                            qp.host_reject_counts})
                except Unavailable:
                    self._note_hub_down()
                    nominated = None
                if nominated:
                    self.stats["preemptions"] = self.stats.get(
                        "preemptions", 0) + 1
            else:
                nominated = nominated_by_uid.get(qp.uid)
            self._park_failed(qp, plugins, nominated)

    def _park_failed(self, qp: QueuedPodInfo, plugins,
                     nominated: Optional[str]) -> None:
        """Condition patch + park (the tail of handleSchedulingFailure)."""
        self._patch_condition_best_effort(qp.pod, PodCondition(
            type="PodScheduled", status="False", reason="Unschedulable",
            message=f"rejected by {sorted(plugins)}"), nominated)
        # the patch fired while this pod was in-flight (the queue
        # ignores updates for in-flight pods), so park the FRESH
        # object — the packed nominated_row must see
        # status.nominatedNodeName next attempt
        try:
            stored = self.hub.get_pod(qp.uid)
        except Unavailable:
            self._note_hub_down()
            stored = None
        if stored is not None:
            qp.pod = stored
        self.queue.add_unschedulable_if_not_present(qp)

    def _error(self, qp: QueuedPodInfo, msg: str) -> None:
        """Error-class failure: separate backoff counter
        (types.go:394-404) so apiserver-error storms back off."""
        if self.flight.enabled:
            self.timelines.event(qp.pod, "error", msg)
        qp.consecutive_errors_count += 1
        qp.unschedulable_plugins = set()
        self.stats["errors"] += 1
        self.metrics.schedule_attempts.inc(
            result="error", profile=qp.pod.spec.scheduler_name)
        self._patch_condition_best_effort(qp.pod, PodCondition(
            type="PodScheduled", status="False", reason="SchedulerError",
            message=msg))
        self.queue.add_unschedulable_if_not_present(qp)

    # ------------- the daemon (scheduler.go Run + queue flush loops) ----

    def _sync_slices(self) -> None:
        """Converge the queues to the slice map after a rebalance: pods
        in slices we lost move to the foreign pen (the new owner's
        informer already has them), pods in slices we gained move from
        the pen into the queues. One integer compare when nothing
        changed — this runs every loop tick. The jobqueue drains by
        whole unit, so a gang mid-assembly re-homes intact."""
        sm = self._slices
        if sm is None or sm.generation == self._slice_gen:
            return
        with self._lock:
            if sm.generation == self._slice_gen:
                return
            self._slice_gen = sm.generation
            for pod in self.queue.drain_unowned(self._owns_pod):
                self._stash_foreign(pod)
            if self.jobqueue.active:
                for pod in self.jobqueue.drain_unowned(self._owns_pod):
                    self._stash_foreign(pod)
            adopted = [p for p in self._foreign.values()
                       if self._owns_pod(p)]
            n_adopted = 0
            for pod in adopted:
                del self._foreign[pod.metadata.uid]
                if pod.spec.node_name or self._terminal(pod) \
                        or self._quarantine_holds(pod):
                    continue
                self.stats["foreign_adopted"] += 1
                n_adopted += 1
                self._enqueue_fresh(pod)
            # ownership moved: any device-resident chain may reflect
            # binds we are no longer racing for — resync conservatively
            self._invalidate_chain()
            self.stats["slice_rebalances"] += 1
            if n_adopted:
                # pods re-homed here mid-flight: a peer lost its slices
                # (deposed or dead) and this replica inherited live work
                # — the scale-out incident worth a black box
                telemetry.incident(
                    self, "slice_reparent",
                    reason=f"adopted {n_adopted} pending pod(s) on "
                           f"ring generation {sm.generation}",
                    adopted=n_adopted, generation=sm.generation,
                    ring_epoch=sm.ring_epoch)

    def run_maintenance(self) -> None:
        """The background timers the reference runs as goroutines: 1s
        backoff flush, 30s unschedulable-timeout flush (5min park cap,
        scheduling_queue.go:378-386), assumed-pod expiry
        (cache.go:730 cleanupAssumedPods), permit-wait harvesting, bind
        completion, queued evictions."""
        with self._lock:
            self._process_deferred_events()
            self._sync_slices()
            now = self.now()
            if now - self._last_backoff_flush >= 1.0:
                self._last_backoff_flush = now
                self.queue.flush_backoff_completed()
            if now - self._last_unsched_flush >= 30.0:
                self._last_unsched_flush = now
                self.queue.flush_unschedulable_timeout()
                # degraded: do NOT expire assumed pods — their informer
                # confirms cannot arrive while the hub is unreachable;
                # expiring them now would forget real placements and
                # invite double scheduling the moment the hub heals.
                # watches_healthy is checked separately: RPCs can
                # succeed while every watch stream is down, and the
                # confirms ride the streams, not the calls
                if not self.hub_degraded() \
                        and getattr(self.hub, "watches_healthy", True):
                    # expiry removed these from the cache already: they
                    # MUST reach the requeue check eventually, so an
                    # outage mid-loop defers the tail instead of
                    # dropping it (_assumed_requeue drains every tick)
                    self._assumed_requeue.extend(
                        self.cache.cleanup_assumed_pods())
            self._drain_assumed_requeue()
            self._release_quarantined()
            self._process_waiting()
            self._drain_bind_results()
            self._flush_evictions_safe()
            self._process_deferred_events()
            self.recorder.flush(force=False)
            self._probe_hub()
            self._evaluate_brownout()
            self._run_drift_sentinel()
            self.metrics.cache_size.set(self.cache.pod_count(), type="pods")
            self.metrics.cache_size.set(self.cache.assumed_pod_count(),
                                        type="assumed_pods")
            self._export_resilience_metrics()
            # LAST: the watchdog reads the counters/stats everything
            # above just finished updating (self-throttled to
            # watchdog_interval_s, so most ticks cost one comparison)
            self.watchdog.poll()

    def _drain_assumed_requeue(self) -> None:
        """Requeue expired assumed pods whose hub-side object is still
        unbound; retried across ticks because the hub may vanish between
        the expiry and the check."""
        if not self._assumed_requeue:
            return
        still: list[Pod] = []
        for pod in self._assumed_requeue:
            try:
                stored = self.hub.get_pod(pod.metadata.uid)
            except Unavailable:
                self._note_hub_down()
                still.append(pod)
                continue
            if stored is not None and not stored.spec.node_name:
                self.queue.add(stored)
        self._assumed_requeue = still

    def _probe_hub(self) -> None:
        """Degraded-mode recovery probe for in-process hubs (a RemoteHub
        tracks its own transport state; its reads below double as the
        probe). One cheap read per maintenance tick."""
        if not self._hub_down:
            return
        if getattr(self.hub, "connected", None) is not None:
            # the client tracks its own transport state: probing would
            # burn a retried RPC (and the retry budget) per tick while
            # holding the scheduler lock
            self._hub_down = False
            return
        try:
            self.hub.get_pod("__degraded_probe__")
            self._hub_down = False
            logger.info("hub reachable again: leaving degraded mode")
        except Unavailable:
            pass

    def _run_drift_sentinel(self) -> None:
        """The cache comparer (backend/cache/debugger/comparer.go),
        promoted from a SIGUSR2 debug hook to a periodic sentinel: every
        ``drift_check_interval`` diff the scheduler's cache against hub
        truth and auto-repair divergence by TARGETED re-sync (only the
        drifted entries mutate — generation bumps make the incremental
        snapshot/mirror refresh pick up exactly those rows). Persistent
        drift (targeted repair not converging) escalates to the full
        mirror/snapshot rebuild as last resort. Skipped while degraded
        or with dead watch streams: everything would look drifted."""
        if self.drift_check_interval <= 0:
            return
        now = self.now()
        if now - self._last_drift_check < self.drift_check_interval:
            return
        if self.hub_degraded() \
                or not getattr(self.hub, "watches_healthy", True):
            return
        self._last_drift_check = now
        try:
            report = None
            if self._drift_rv is not None:
                # steady state: O(changes) journal diff — ZERO cluster
                # LISTs when nothing (or little) changed
                try:
                    report = self.cache.drift_report(
                        self.hub, since_rv=self._drift_rv)
                    self.stats["drift_incremental"] += 1
                except RvTooOld:
                    report = None   # compacted gap: full diff below
            if report is None:
                report = self.cache.drift_report(self.hub)
                self.stats["drift_full_lists"] += 1
        except Unavailable:
            self._note_hub_down()
            return
        rep_rv = getattr(report, "rv", None)
        self._drift_rv = rep_rv if isinstance(rep_rv, int) else None
        n = report.count()
        if n == 0:
            self._drift_strikes = 0
            return
        self._drift_strikes += 1
        self.metrics.drift_detected.inc(n)
        logger.warning("drift sentinel: %d cache-vs-hub discrepancies "
                       "(strike %d): %s", n, self._drift_strikes,
                       report.render()[:5])
        telemetry.incident(self, "drift",
                           reason=f"{n} cache-vs-hub discrepancies "
                                  f"(strike {self._drift_strikes})",
                           discrepancies=n, strike=self._drift_strikes,
                           sample=report.render()[:5])
        try:
            repaired = self.cache.repair_from_hub(self.hub, report)
        except Unavailable:
            self._note_hub_down()
            return
        self.stats["drift_repairs"] += repaired
        self.metrics.drift_repaired.inc(repaired)
        # the mirror re-packs the repaired rows from the snapshot on the
        # next unchained launch; drop the chain so one happens
        self._invalidate_chain()
        if self._drift_strikes >= 3:
            # targeted repair is not converging: rebuild the device side
            # from scratch (the mirror itself may be corrupt in ways the
            # host diff cannot see)
            logger.error("drift sentinel: persistent drift after %d "
                         "targeted repairs; rebuilding mirror + snapshot",
                         self._drift_strikes)
            self.metrics.drift_rebuilds.inc()
            telemetry.incident(
                self, "drift_rebuild",
                reason=f"persistent drift after "
                       f"{self._drift_strikes} targeted repairs",
                strikes=self._drift_strikes)
            self.mirror = Mirror(caps=self.caps, mesh=self.mesh)
            self.snapshot = Snapshot()
            self.cache.update_snapshot(self.snapshot)
            self._drift_strikes = 0

    # ------------- brownout (overload self-protection) -------------

    def _effective_batch(self) -> int:
        """Pop/release budget for this cycle: the configured batch, or
        the brownout-shrunk batch while shedding load. Launch packing
        keeps its configured capacity hints — the smaller batch pads
        down to an already-warm smaller bucket, so the shrink does not
        force recompiles."""
        cfg = self.config
        if not self.brownout:
            return cfg.batch_size
        return max(cfg.batch_size // BROWNOUT_BATCH_DIVISOR,
                   min(BROWNOUT_BATCH_FLOOR, cfg.batch_size))

    def _evaluate_brownout(self) -> None:
        """Watch the hub client's 429 counter and shed our own load
        while the fabric is saturated: a scheduler that answers flow
        control by hammering full batches at full cadence converts one
        overloaded component into a fleet-wide retry storm. Evaluated
        at most once per second; enters on brownout_throttle_threshold
        throttles in a window, exits after brownout_clear_windows
        consecutive windows with zero new throttles."""
        cfg = self.config
        threshold = getattr(cfg, "brownout_throttle_threshold", 0)
        if threshold <= 0:
            return
        rs = getattr(self.hub, "resilience_stats", None)
        if rs is None:
            return      # in-process hub: no flow-controlled transport
        now = self.now()
        if now - self._last_brownout_eval < 1.0:
            return
        self._last_brownout_eval = now
        throttled = float(rs().get("throttled_429s", 0))
        delta = throttled - self._brownout_throttled_seen
        self._brownout_throttled_seen = throttled
        if not self.brownout:
            if delta >= threshold:
                self._enter_brownout(delta)
            return
        if delta > 0:
            self._brownout_clean = 0
            return
        self._brownout_clean += 1
        if self._brownout_clean >= max(cfg.brownout_clear_windows, 1):
            self._exit_brownout()

    def _enter_brownout(self, rate: float) -> None:
        cfg = self.config
        self.brownout = True
        self._brownout_clean = 0
        self.stats["brownout_enters"] += 1
        # capture the CURRENT cadence, not the constructor default:
        # tests and operators retune drift_check_interval post-init
        self._drift_interval_base = self.drift_check_interval
        if self.drift_check_interval > 0:
            self.drift_check_interval *= BROWNOUT_DRIFT_STRETCH
        parked: list[str] = []
        if self.jobqueue.active:
            parked = self.jobqueue.park_below(BROWNOUT_BESTEFFORT_WEIGHT)
        self.metrics.brownout.set(1.0)
        self.metrics.brownout_transitions.inc(phase="enter")
        logger.warning(
            "brownout ENTER: %d hub throttles in the last window "
            "(threshold %d): batch %d -> %d, drift cadence %.0fs, "
            "parked best-effort tenants %s",
            int(rate), cfg.brownout_throttle_threshold, cfg.batch_size,
            self._effective_batch(), self.drift_check_interval, parked)
        telemetry.incident(
            self, "brownout_enter",
            reason=f"{int(rate)} hub throttles in the last window "
                   f"(threshold {cfg.brownout_throttle_threshold})",
            throttles=int(rate),
            effective_batch=self._effective_batch(), parked=parked)

    def _exit_brownout(self) -> None:
        self.brownout = False
        self._brownout_clean = 0
        self.stats["brownout_exits"] += 1
        if self._drift_interval_base is not None:
            self.drift_check_interval = self._drift_interval_base
            self._drift_interval_base = None
        freed = self.jobqueue.unpark_all()
        self.metrics.brownout.set(0.0)
        self.metrics.brownout_transitions.inc(phase="exit")
        logger.info("brownout EXIT: pressure clear; batch restored to "
                    "%d, unparked tenants %s",
                    self.config.batch_size, freed)

    def brownout_state(self) -> dict:
        """The /debug/fleet brownout surface."""
        return {"active": self.brownout,
                "enters": self.stats["brownout_enters"],
                "exits": self.stats["brownout_exits"],
                "clean_windows": self._brownout_clean,
                "effective_batch": self._effective_batch(),
                "drift_check_interval": self.drift_check_interval,
                "parked_tenants": sorted(
                    getattr(self.jobqueue, "parked", ()))}

    def _export_resilience_metrics(self) -> None:
        """Mirror hub-client and chaos counters into the registry (the
        hub client and chaos layer have no registry of their own)."""
        m = self.metrics
        m.hub_degraded.set(1.0 if self.hub_degraded() else 0.0)
        m.brownout.set(1.0 if self.brownout else 0.0)
        if self._slices is not None:
            m.sched_slices_owned.set(float(len(self._slices.owned)))
            m.foreign_pending_pods.set(float(len(self._foreign)))
            self._mirror_count("slice_rebalances",
                               self.stats["slice_rebalances"],
                               m.slice_rebalances)
        rs = getattr(self.hub, "resilience_stats", None)
        if rs is not None:
            s = rs()
            m.hub_client_retries.set(float(s["retries"]))
            m.hub_client_watch_reconnects.set(
                float(s["watch_reconnects"]))
            m.hub_client_degraded_seconds.set(s["degraded_seconds"])
            self._mirror_count("watch_resumes", s.get("watch_resumes", 0),
                               m.hub_watch_resumes)
            self._mirror_count("watch_relists", s.get("watch_relists", 0),
                               m.hub_watch_relists)
            self._mirror_count("throttled_429s",
                               s.get("throttled_429s", 0),
                               m.hub_client_throttled)
            self._mirror_count("throttle_retries",
                               s.get("throttle_retries", 0),
                               m.hub_client_throttle_retries)
            for codec_name, w in s.get("wire", {}).items():
                self._mirror_count(f"wire_msgs:{codec_name}",
                                   w.get("msgs", 0),
                                   m.wire_codec_messages,
                                   codec=codec_name)
                self._mirror_count(f"wire_sent:{codec_name}",
                                   w.get("bytes_sent", 0),
                                   m.wire_codec_bytes,
                                   codec=codec_name, direction="sent")
                self._mirror_count(f"wire_recv:{codec_name}",
                                   w.get("bytes_recv", 0),
                                   m.wire_codec_bytes,
                                   codec=codec_name, direction="recv")
        for src, n in self._dra.cel_error_stats().items():
            self._mirror_count(f"cel:{src}", n, m.dra_cel_errors,
                               source=src)
        mirror = self.mirror
        for result, n in (("hit", mirror.row_cache_hits),
                          ("miss", mirror.row_cache_misses),
                          ("bypass", mirror.row_cache_bypass)):
            self._mirror_count(f"pack_row_cache:{result}", n,
                               m.pack_row_cache, result=result)
        for result, n in (("hit", mirror.slot_row_hits),
                          ("miss", mirror.slot_row_misses),
                          ("bypass", mirror.slot_row_bypass)):
            self._mirror_count(f"slot_row_cache:{result}", n,
                               m.mirror_slot_row_cache, result=result)
        for result, n in (("packed", mirror.slots_packed),
                          ("kept", mirror.slots_kept),
                          ("released", mirror.slots_released)):
            self._mirror_count(f"mirror_slot:{result}", n,
                               m.mirror_slots, result=result)
        self._mirror_count("mirror_slot_terms", mirror.slots_packed_terms,
                           m.mirror_slot_terms)
        sweep = self.preemption.sweep_stats()
        for result in ("nominated", "no_candidates", "ineligible",
                       "extender_errors"):
            self._mirror_count(f"preemption_sweep:{result}", sweep[result],
                               m.preemption_sweep, result=result)
        for path, key in (("rebuild", "victim_state_rebuilds"),
                          ("update", "victim_state_updates")):
            self._mirror_count(f"preemption_victim_state:{path}", sweep[key],
                               m.preemption_victim_state, path=path)
        self._mirror_count("preemption_stranded",
                           sweep["stranded_activations"],
                           m.preemption_stranded)
        for result in ("computed", "reused"):
            self._mirror_count(f"preemption_kmin:{result}",
                               sweep[f"kmin_{result}"], m.preemption_kmin,
                               result=result)
        refresh = self.cache.snapshot_stats()
        for path in ("noop", "delta", "full"):
            self._mirror_count(f"cache_snapshot_refresh:{path}",
                               refresh[path], m.cache_snapshot_refresh,
                               path=path)
        self._mirror_journal_stats()
        if self.jobqueue.active:
            for tenant, st in self.jobqueue.tenant_stats().items():
                m.tenant_queue_depth.set(float(st["depth"]),
                                         tenant=tenant)
                u = st["usage"]
                m.tenant_quota_used.set(float(u["cpu_milli"]),
                                        tenant=tenant, resource="cpu_milli")
                m.tenant_quota_used.set(float(u["memory"]),
                                        tenant=tenant, resource="memory")
                m.tenant_quota_used.set(float(u["pods"]),
                                        tenant=tenant, resource="pods")
        cs = getattr(self.hub, "chaos_stats", None)
        if cs is not None:
            for kind, v in cs().items():
                # only actual faults: calls_seen/events_relayed are
                # traffic counters, not injections
                if kind.startswith("injected_") or kind == "partitions":
                    m.chaos_injected_faults.set(float(v), kind=kind)

    def _mirror_count(self, key: str, current: float, counter,
                      **labels) -> None:
        """Advance a registry Counter by the delta of an externally-owned
        monotonic count (mirrored gauges would break rate() on restart)."""
        prev = self._mirrored_counts.get(key, 0.0)
        if current > prev:
            counter.inc(current - prev, **labels)
            self._mirrored_counts[key] = current

    def _mirror_journal_stats(self) -> None:
        """Journal depth/watermark gauges, throttled: for a RemoteHub
        this is an RPC, and the maintenance tick runs every loop."""
        now = self.now()
        if now - self._last_journal_mirror < 10.0:
            return
        self._last_journal_mirror = now
        js_fn = getattr(self.hub, "get_journal_stats", None)
        if js_fn is None or self.hub_degraded():
            return
        try:
            js = js_fn()
        except Unavailable:
            return
        for kind, st in js.get("kinds", {}).items():
            self.metrics.hub_journal_depth.set(
                float(st["depth"]), kind=kind)
            self.metrics.hub_journal_compacted_rv.set(
                float(st["compacted_rv"]), kind=kind)
        # a sharded hub (fabric.sharded.ShardedHub) reports per-shard
        # journal state alongside the merged per-kind view
        for shard, st in js.get("shards", {}).items():
            self.metrics.hub_shard_depth.set(
                float(st["depth"]), shard=shard)
            self.metrics.hub_shard_compacted_rv.set(
                float(st["compacted_rv"]), shard=shard)
            self._mirror_count(f"shard_commits:{shard}",
                               st.get("commits", 0),
                               self.metrics.hub_shard_commits,
                               shard=shard)

    def run(self, stop: threading.Event, idle_sleep: float = 0.02,
            elector=None) -> None:
        """Blocking daemon loop (scheduler.go:452 Run): maintenance timers
        + scheduling cycles until ``stop`` is set. With an ``elector``
        (leaderelection.LeaderElector) the loop only schedules while
        holding the lease (server.go:284-317); a non-leader keeps its
        informer state warm but mutates nothing. Exceptions are logged and
        retained (daemon_error); the loop backs off with decorrelated
        jitter (a persistent error must not busy-spin the keep-alive)
        and keeps serving.

        A drain that finds no pod waits on the queue's wake event (set
        when a pod enters the activeQ, or by ``stop()``), at most
        ``idle_sleep``, so the maintenance timers still tick; each such
        wait is counted by how it ended. Events that put no pod in the
        activeQ (node heartbeats, bind echoes) leave it alone. The event
        is cleared before the drain, so a pod that arrives between an
        empty drain and the wait ends the wait."""
        self.daemon_error: Optional[BaseException] = None
        self._elector = elector
        wake = self.queue.wake
        idle_waits = self.metrics.loop_idle_waits
        # a SliceManager is the scale-out elector: leadership over a
        # SLICE of the pending-pod space instead of the whole ring
        self._slices = (elector if getattr(elector, "is_slice_manager",
                                           False) else None)

        def tick_gate() -> bool:
            ok = elector.tick()
            if ok and self._slices is not None:
                self._sync_slices()
            return ok

        crash_bo = Backoff(base=0.5, cap=30.0)
        span = self.flight.span
        try:
            while not stop.is_set():
                # one loop turn: its loop-level spans (LOOP_PHASES) carry
                # this id and, with the cycles' phases, tile the thread
                self.flight.turn += 1
                if elector is not None and not tick_gate():
                    with span("idle_wait"):
                        stop.wait(min(elector.retry_period, 0.5))
                    continue
                try:
                    with span("maintenance"):
                        self.run_maintenance()
                    # the drain renews the lease every batch and aborts the
                    # moment leadership is lost (the reference renews on a
                    # background goroutine; a long drain must not outlive
                    # the lease while still binding pods)
                    on_step = (None if elector is None
                               else (lambda: not tick_gate()))
                    wake.clear()
                    if self.run_until_idle(on_step=on_step) == 0:
                        with span("idle_wait"):
                            woke = wake.wait(idle_sleep)
                            idle_waits.inc(end="event" if woke else "timeout")
                    crash_bo.reset()
                except Exception as e:  # noqa: BLE001 — keep daemon alive
                    logger.exception("scheduling loop error: %s", e)
                    self.daemon_error = e
                    self.metrics.cycle_crashes.inc()
                    with span("idle_wait"):
                        stop.wait(crash_bo.next())
        finally:
            if elector is not None:
                elector.release()

    def start(self, elector=None) -> None:
        """Run the daemon on its own thread (tests/embedding)."""
        if self._daemon is not None:
            return
        self._stop = threading.Event()
        self._daemon = threading.Thread(
            target=self.run, args=(self._stop,),
            kwargs={"elector": elector}, daemon=True,
            name="kubernetes-tpu-scheduler")
        self._daemon.start()

    def stop(self) -> None:
        if self._daemon is None:
            return
        self._stop.set()
        self.queue.wake.set()
        self._daemon.join(timeout=30)
        self._daemon = None
        self._stop = None

    def close(self) -> None:
        """Stop the daemon (if running) and release the binder pool's
        worker threads. The scheduler is unusable afterwards."""
        self.stop()
        if self._binder is not None:
            self._drain_bind_results(wait=True)
            self._process_deferred_events()
            self._binder.shutdown(wait=True)
            self._binder = None
        if self._commit_pool is not None:
            self._commit_pool.shutdown(wait=True)
            self._commit_pool = None
        gc_guard.unwatch(self.flight)
        self.flight.close()

    # ------------- driving -------------

    def run_until_idle(self, max_batches: int = 1000,
                       on_step=None) -> int:
        """Drain the activeQ (tests/bench); returns pods attempted.

        Pipelined: while launch k computes on device, batch k+1 is popped,
        packed, and dispatched against the device-resident usage chain
        (BatchResult.free/.nzr); batch k's host-side commits then overlap
        launch k+1's device time. Falls back to strict launch->commit
        alternation whenever the next batch cannot chain (topology or host
        ports in play, or an external event invalidated the chain).

        ``on_step`` (if given) runs once per loop iteration before the pop —
        the perf harness injects churn pods through it
        (scheduler_perf.go:819 churnOp). A truthy return stops the drain
        (pending work is still committed): with a churn feed the queue may
        never go idle, so the harness signals "measured phase done" here."""
        span = self.flight.span
        with span("lock_wait"):
            self._lock.acquire()
        try:
            with gc_guard:
                total = self._run_until_idle_locked(max_batches, on_step)
                sweep = span("gc_sweep")   # the guard's exit collection
            sweep.end()
            return total
        finally:
            self._lock.release()

    def _run_until_idle_locked(self, max_batches, on_step) -> int:
        total = 0
        # up to PIPELINE_DEPTH launches in flight: chained launches queue
        # back-to-back on the device, so blocking on the OLDEST one after
        # dispatching the newest gives the device a whole iteration of
        # host-side commit work as head start (the batched analog of the
        # reference's scheduling/binding goroutine overlap, P3)
        pending: deque[tuple] = deque()

        def flush_all() -> None:
            while pending:
                self._finish_contained(pending.popleft())

        def flush_to(depth: int) -> None:
            while len(pending) > depth:
                self._finish_contained(pending.popleft())

        span = self.flight.span
        for _ in range(max_batches):
            with span("event_intake") as intake:
                self._process_deferred_events()
                self._process_waiting()
            self._drain_bind_results()
            # the 1s backoff flush must tick DURING a busy drain too (the
            # reference runs it as a goroutine): under continuous load the
            # idle branch never runs and backoff pods would starve
            if intake.t1 - self._last_backoff_flush >= 1.0:
                self._last_backoff_flush = intake.t1
                with span("event_intake"):
                    self.queue.flush_backoff_completed()
                # once-a-second young-gen sweep keeps deferred cyclic
                # garbage bounded during long drains (see utils.gcguard)
                with span("gc_sweep"):
                    gc_guard.idle_sweep()
            if on_step is not None and on_step():
                break
            if self.jobqueue.active:
                # admit tenant/gang work by DRR + quota before the pop
                self.jobqueue.release(self.queue, self._effective_batch())
            popped, runnable, tr = self._pop_runnable()
            if popped == 0:
                flush_all()
                # the flush may have completed a gang quorum (Permit
                # allowed the waiting peers): harvest them into the
                # binding cycle BEFORE deciding the queue is idle, or a
                # drain ends with allowed pods stranded in the wait room
                with span("event_intake"):
                    self._process_waiting()
                if self._pipelined:
                    # the flush may also have planned evictions (the
                    # failed wave's PostFilter ran in _finish): fire them
                    # NOW so the activated preemptor rides the next wave
                    # of this same drain instead of waiting out a backoff
                    # into the next one (its nominated reservation holds
                    # the freed slot either way)
                    self._flush_evictions_safe()
                with span("event_intake"):
                    self.queue.flush_backoff_completed()
                # a drained wait room or a churn event may have refilled
                # the job queue mid-iteration
                if self.jobqueue.active:
                    self.jobqueue.release(self.queue,
                                          self._effective_batch())
                popped, runnable, tr = self._pop_runnable()
                if popped == 0:
                    break
            total += popped
            nxt = None
            if runnable:
                # gang units first: their fused launch chains the usage
                # state the normal launch then builds on
                runnable = self._schedule_gang_units(
                    runnable, flush_pending=flush_all)
            if runnable:
                chained = self._chain_eligible([qp.pod for qp in runnable])
                # a non-chainable batch does NOT drain the pipeline here:
                # _dispatch's own need_sync path flushes lazily (through
                # flush_pending) right before the snapshot sync, so the
                # in-flight waves keep their device head start and
                # pipelining resumes at full depth after the host-path
                # batch commits
                try:
                    nxt = self._dispatch(runnable, chained, tr,
                                         flush_pending=flush_all)
                except Unavailable:
                    self._park_batch_unreachable(runnable)
                    nxt = None
                except Exception as e:  # noqa: BLE001 — containment seam:
                    # commit what was already in flight first (their
                    # launches predate the fault), then degrade this batch
                    flush_all()
                    self._contain_batch_fault(runnable, e)
                    nxt = None
                if nxt is not None:
                    pending.append(nxt)
                    # pipeline-depth observability: how many waves were
                    # in flight right after this dispatch (tr is tuple
                    # element 4) — the stall detector for satellite runs
                    nxt[4].depth = len(pending)
            # keep up to PIPELINE_DEPTH launches outstanding: batch k-1 is
            # committed only after k AND k+1 are queued, so the device gets
            # a full iteration (dispatch + commit) of head start. The
            # off-arm (pipelined_waves=False) commits every wave before
            # the next dispatch — strict launch->commit alternation.
            flush_to(PIPELINE_DEPTH if self._pipelined else 0)
            if nxt is not None and pending and pending[-1] is nxt:
                # settle the recorded depth to the post-trim count (the
                # ring keeps the live trace object): a full pipeline
                # reads PIPELINE_DEPTH, a stalled one 1. Waves the trim
                # itself committed (the off arm) keep their dispatch-time
                # depth of 1.
                nxt[4].depth = len(pending)
            # async preemption evictions run between cycles (kep 4832)
            self._flush_evictions_safe()
        flush_all()
        self._drain_bind_results(wait=True)
        self._flush_evictions_safe()
        with span("drain_tail"):
            self._process_deferred_events()
            self.recorder.flush()
        return total
