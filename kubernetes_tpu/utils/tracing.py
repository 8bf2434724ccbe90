"""The always-on flight recorder and the per-pod timelines.

- ``CycleTrace`` / ``FlightRecorder``: EVERY scheduling cycle records
  its fine-grained phases (queue pop, snapshot sync, host plugins, DRA
  allocator, pack, device launch, D2H pull, commit, failure handling,
  binder drain, eviction flush, host fallback) into a bounded ring
  buffer, and each phase feeds a
  per-phase histogram in the metrics Registry — the continuous
  per-stage latency attribution Kant (arxiv 2510.01256) argues
  large-cluster schedulers need, instead of sampling-on-slow. ONE
  entry records a phase, ``FlightRecorder.span``: it keeps the span's
  instants (name, start, end, thread, cycle or loop turn) and delivers
  ``(phase, secs)`` through ``CycleTrace.add`` / ``observe_phase``
  exactly once, at the instant the span ends, on the thread it ran on.
  Beside the wall clock a span takes a reading of its thread's CPU
  clock (``time.thread_time``) at each end: the CPU seconds between
  the two are the part of the interval its thread spent ON the
  interpreter, the rest it stood off it (waiting for the GIL, a lock,
  the device, a sleep). At a span's start a reading at most
  ``CPU_REUSE_S`` old on the wall clock stands for now, so where spans
  follow each other the end of one and the start of the next share one
  read of the clock (``FlightRecorder._cpu_at_start``). The CPU seconds land
  in the same histogram under the phase label ``"<phase>.cpu"``, so a
  phase's waiting share is ``1 - sum{phase="x.cpu"} / sum{phase="x"}``.
  ONE rule derives them from the readings,
  ``FlightRecorder._settle_cpu``: a span delivers at most its own wall
  seconds, and what its readings gave beyond them (a tick of a coarse
  CPU clock, the shared reading's age) is carried to the phase's next
  spans.
  The recorder's overhead budget is <2% of p50 cycle time (PERF.md §6,
  PR 36, has the chip reading): a span is two wall reads, at most two
  CPU reads and as a rule one, two dict writes and one list append;
  while a JAX profiler trace is being taken it is also a
  ``jax.profiler.TraceAnnotation`` of the same name.
  A cycle over the slow threshold is logged from its own ``CycleTrace``
  (``log_if_slow``: every phase with its CPU beside it).

- ``PodTimelines``: per-pod lifecycle stamps (enqueue, pop/attempt,
  assume, bind, parks) plus the last unschedulable diagnosis (which
  device filter rejected how many nodes, which host plugin rejected),
  bounded LRU — the data behind ``/debug/pod?name=``.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import threading
import time
from typing import Callable, Optional

logger = logging.getLogger("kubernetes_tpu.trace")

# canonical cycle phases, in rough hot-path order. Host-tail share is
# the HOST_PHASES fraction of total cycle time; the dra_* phases are
# VIEWS (the DynamicResources slices of pack/host_plugins/commit), not
# disjoint phases, so they are excluded from the share arithmetic.
CYCLE_PHASES = (
    "queue_pop",          # pop_batch + per-pod hub vetting
    "snapshot_sync",      # cache.update_snapshot + mirror.sync (H2D pack)
    "chain_patch",        # churn deltas scattered into the live chain
                          # (chain-surviving churn: the cheap substitute
                          # for a whole-chain invalidate + snapshot_sync)
    "host_plugins",       # host PreFilter/Filter/Score + extenders
    "pack",               # mirror.prepare_launch (pod-side H2D)
    "device_dispatch",    # async launch_batch dispatch, the profiler's
                          # note of the launch and the hand-over of the
                          # pull to the commit thread
    "device_launch",      # dispatch -> results pulled (device + queue wait)
    "d2h_pull",           # device_get of rows/guard/reject_counts
    "commit",             # assume/reserve/permit per winner
    "failure_handling",   # diagnoses, PostFilter/preemption, parks
    "binder_drain",       # handing a launch's binds to the binder pool
                          # and collecting finished binding cycles
    "eviction_flush",     # queued preemption evictions
    "host_fallback",      # serial host path after a device fault
    "dra_mask_compile",   # CEL -> bitmask compile + inventory refresh (view)
    "dra_device_eval",    # per-cycle DRA tensor pack + host-path
                          # DynamicResources PreFilter/Filter time (view;
                          # the fused in-launch eval rides device_launch)
    "dra_commit",         # DynamicResources Reserve/PreBind time (view)
    "learned_score",      # learned-scorer checkpoint mtime poll /
                          # reload / params fetch at snapshot-sync time
                          # (a REAL exclusive phase, counted in totals —
                          # a slow checkpoint path must show up in the
                          # A/B latency gate; the fused MLP eval itself
                          # rides device_launch)
    "device_compile",     # launch walltime of a cycle whose dispatch
                          # triggered an XLA compile (view: the same
                          # seconds already sit in device_launch — the
                          # DeviceProfiler's attribution of WHY that
                          # launch stalled)
    "gang_device",        # fused gang-pack launch: pack + dispatch +
                          # the verdict pull (device + transfer time,
                          # the gang analog of device_launch)
    "gang_commit",        # host commit of device-placed gang units
                          # (reserve-all -> bind-all, atomic rollback)
    "commit_pull",        # pipelined waves only: the commit thread's
                          # device pull, measured AND reported on the
                          # commit thread (overlap view: that wall time
                          # runs CONCURRENT with the loop thread's next
                          # dispatch, so it is excluded from totals/
                          # host-tail — the loop thread's actual blocked
                          # wait lands in device_launch)
    "snapshot_cache",     # cache.update_snapshot: the first of the two
                          # pieces snapshot_sync is timed in (view of
                          # that piece, reported just ahead of it)
    "mirror_sync",        # mirror.sync: the second piece (view)
)

# exclusive phases of the DAEMON LOOP around the cycles, each reported at
# its end through observe_phase; with the cycle phases they tile the loop
# thread's wall time. Left out of cycle totals and the host-tail share,
# so no headline changed its meaning when they arrived.
LOOP_PHASES = (
    "idle_wait",          # the wake event's wait (at most idle_sleep),
                          # the elector's wait and the crash backoff
    "maintenance",        # run_maintenance
    "lock_wait",          # acquiring the scheduler lock for a drain
    "event_intake",       # deferred informer events, the Permit wait
                          # room, the backoff flush
    "gc_sweep",           # gc_guard's exit collection and idle_sweep
    "drain_tail",         # closing a drain: deferred events + the async
                          # event recorder's flush
)

# loop-level views: seconds another component measured, reported by the
# loop beside the exclusive phase they sit in
LOOP_VIEW_PHASES = (
    "queue_done",         # PriorityQueue.done() -> _trim_events dropping
                          # entries from the event log's head, the
                          # queue's own clock; reported with (and inside)
                          # binder_drain, 0.0 when none dropped any
    "gc_pause",           # one collector pause (utils/gcguard's
                          # gc.callbacks hook), on whichever thread
                          # the collector ran
    "slot_pack_terms",    # Mirror._pack_pod_slot's terms arm (pods with
                          # affinity terms: a copied row or a full
                          # pack), the mirror's own clock;
                          # reported with (and inside) mirror_sync once
                          # a sync, 0.0 when it packed no such slot
    "pack_full",          # Mirror._pack_batch_np's rows the packed-row
                          # cache did not serve (bypass and miss: the full
                          # pack_pod), the mirror's own clock; reported
                          # with (and inside) pack once a launch, 0.0 when
                          # every row was a hit
)

# the dra_* attribution views, excluded from total/host-tail arithmetic
# (they double-count time already inside pack/host_plugins/commit)
DRA_VIEW_PHASES = ("dra_mask_compile", "dra_device_eval", "dra_commit")

# attribution views excluded from cycle totals and the host-tail share.
# NOTE: learned_score is NOT here — its time is exclusive (nothing else
# measures the checkpoint poll), so hiding it would let a slow reload
# path pass the --ab-scorer parity gate unseen
VIEW_PHASES = DRA_VIEW_PHASES + (
    "device_compile", "snapshot_cache", "mirror_sync") + LOOP_VIEW_PHASES

# phases measured on the commit thread or a binder worker, CONCURRENT
# with loop-thread work. Counting them in totals/host-tail would book
# overlapped wall time as if serial (the pipelined arm's host-tail share
# over-reported before these were split out). Like VIEW_PHASES they
# still render in /debug/trace and phase_percentiles — they are
# attribution, not cost.
OVERLAP_PHASES = (
    "commit_pull",        # a cycle's span, on the commit thread
    "bind_chunk",         # loop-level, on a binder worker: its run of one
                          # chunk of a launch's binding cycles
                          # (Scheduler._submit_bind_backlog; four a
                          # launch, none a pod). With commit_pull it
                          # accounts for the threads the program owns
                          # beside the loop
)

# the label suffix of a phase's CPU seconds in the phase histogram
# ("commit" -> "commit.cpu"): the same histogram, a series of its own
CPU_SUFFIX = ".cpu"

# everything excluded from the serial-cycle-time arithmetic
EXCLUDED_PHASES = VIEW_PHASES + OVERLAP_PHASES

# what CycleTrace.total() and host_tail_share() leave out: the views,
# the overlap and the loop-level phases
UNCOUNTED_PHASES = frozenset(EXCLUDED_PHASES + LOOP_PHASES)

# trace-export JSON-lines format version (CycleTrace.to_dict "v"):
# v2 added per-pod placement rows (pod, chosen node, aggregate score,
# chosen-node learned-feature vector) — the replay-dataset substrate;
# v3 adds the opt-in top-K alternative-node scores per placement
# ("alt": [[node, score], ...], trace_export_alts) — the counterfactual
# substrate behind per-placement regret (learn/regret.py). Additive:
# v2 rows remain valid replay input (learn/replay.py reads >= 2).
# v4 adds "spans": every phase span of the cycle as [name, start, end,
# thread] on the recorder's clock (phases_ms stays: it is their sums).
# v5 gives each span a fifth element, the milliseconds between its two
# readings of its thread's CPU clock as read (None where it has none),
# and the cycle "cpu_ms" beside "phases_ms": what its spans DELIVERED to
# the "<phase>.cpu" series (FlightRecorder._settle_cpu), which equals
# the readings' sums only where no span read more than its length.
# Additive.
EXPORT_VERSION = 5

# how old a reading of a thread's CPU clock may be and still stand for
# that thread's now at a span's start (FlightRecorder._cpu_at_start), on
# the wall clock (then the clock is not read) or by what the thread has
# burnt since: spans that follow each other share the reading at their
# boundary, one read a span where there were two, and glue shorter than
# this counts to the later span. It bounds what the glue ahead of a span
# can add to its CPU figure; the read it saves costs 6-12 us where the
# thread's CPU clock is a system call (PERF.md §6, PR 36).
CPU_REUSE_S = 50e-6

# the most CPU seconds a phase may be owed (FlightRecorder._settle_cpu):
# two ticks of the coarsest thread CPU clock met (10 ms). More than that
# is no tick's remainder, and would hide as much real waiting later.
CPU_CARRY_MAX_S = 0.02

# phases that are host-side Python work (the "host tail" the ROADMAP's
# sub-10x offenders ask us to attribute); device_launch is device +
# transfer, d2h_pull is transfer, the dra_* views double-count host time
HOST_PHASES = (
    "queue_pop", "snapshot_sync", "chain_patch", "host_plugins", "pack",
    "commit",
    "failure_handling", "binder_drain", "eviction_flush", "host_fallback",
    "learned_score", "gang_commit",
)


def _read_ms(c0: Optional[float], c1: Optional[float]) -> Optional[float]:
    """The milliseconds between a span's two CPU readings, as read; None
    where it carries none."""
    if c0 is None or c1 is None:
        return None
    return round((c1 - c0) * 1e3, 3)


class CycleTrace:
    """One scheduling cycle's phase durations. ``add`` accumulates (a
    phase may be touched several times per cycle, e.g. the re-bucketing
    retry loop re-syncing); the recorder flushes the whole dict to the
    phase histogram when the cycle is recorded. ``cpu`` holds the CPU
    seconds of the same phases' spans, each on its own thread, flushed
    beside them under ``"<phase>.cpu"``; it is no part of ``phases``, so
    no total counts it."""

    __slots__ = ("cycle", "start", "pods", "scheduled", "failed",
                 "chained", "phases", "cpu", "spans", "plugins",
                 "placements", "depth")

    def __init__(self, cycle: int, start: float, pods: int,
                 chained: bool = False):
        self.cycle = cycle
        self.start = start          # wall-clock cycle start
        self.pods = pods
        self.scheduled = 0
        self.failed = 0
        self.chained = chained
        # pipeline depth observed right after this cycle dispatched
        # (how many waves were in flight, the stall detector)
        self.depth = 0
        self.phases: dict[str, float] = {}
        self.cpu: dict[str, float] = {}
        # (name, start, end, thread ident, the thread's CPU clock at the
        # start and at the end) of every span of this cycle, in the
        # order they ended (FlightRecorder.span appends)
        self.spans: list[tuple] = []
        self.plugins: dict[str, float] = {}   # "plugin/point" -> secs
        # per-pod placement rows (export v2+): {"pod", "uid", "node",
        # "score"[, "feat"][, "alt"]} — node None for failed attempts,
        # "alt" the v3 top-K alternative (node, score) pairs. Populated
        # by the scheduler only while the export file is open.
        self.placements: list[dict] | None = None

    def add(self, phase: str, secs: float) -> None:
        self.phases[phase] = self.phases.get(phase, 0.0) + secs

    def total(self) -> float:
        # view phases double-count time inside the real phases; overlap
        # phases ran on the commit thread concurrent with the loop
        return sum(v for k, v in self.phases.items()
                   if k not in UNCOUNTED_PHASES)

    def to_dict(self, thread_names: Optional[dict] = None) -> dict:
        names = thread_names or {}
        d = {
            "v": EXPORT_VERSION,
            "cycle": self.cycle,
            "start": round(self.start, 6),
            "pods": self.pods,
            "scheduled": self.scheduled,
            "failed": self.failed,
            "chained": self.chained,
            "depth": self.depth,
            "total_ms": round(self.total() * 1e3, 3),
            "phases_ms": {k: round(v * 1e3, 3)
                          for k, v in self.phases.items()},
            "cpu_ms": {k: round(v * 1e3, 3) for k, v in self.cpu.items()},
            "spans": [[n, round(a, 6), round(b, 6), names.get(t, t),
                       _read_ms(c0, c1)]
                      for n, a, b, t, c0, c1 in self.spans],
        }
        if self.plugins:
            d["plugins_ms"] = {k: round(v * 1e3, 3)
                               for k, v in self.plugins.items()}
        if self.placements is not None:
            d["placements"] = self.placements
        return d

    def log_if_slow(self, total: float, threshold: float,
                    log: logging.Logger, **fields) -> bool:
        """The slow-cycle line (schedule_one.go:404's slow-attempt trace,
        batch-shaped): silent unless ``total`` passes ``threshold``, then
        every phase of the cycle with its CPU seconds beside it, which
        says whether the slow cycle worked or waited. Returns whether it
        logged."""
        if total <= threshold:
            return False
        head = " ".join(f"{k}={v}" for k, v in fields.items())
        lines = [f"Trace[schedule_cycle] {head} total={total * 1e3:.0f}ms"]
        for phase, secs in self.phases.items():
            cpu = self.cpu.get(phase)
            lines.append(
                f"  - {phase}: {secs * 1e3:.0f}ms"
                + ("" if cpu is None else f" (cpu {cpu * 1e3:.0f}ms)"))
        log.info("%s", "\n".join(lines))
        return True


class _NullTrace(CycleTrace):
    """The disabled recorder's trace: add() is a no-op so the scheduler
    keeps one unconditional code path."""

    def __init__(self):
        super().__init__(-1, 0.0, 0)

    def add(self, phase: str, secs: float) -> None:
        pass


_NULL_TRACE = _NullTrace()


class Span:
    """One timed interval, handed out STARTED by ``FlightRecorder.span``;
    ``end()`` (or leaving the ``with``) reads the clock and reports it.
    ``t0``/``t1``/``secs`` stay readable afterwards, so the caller needs
    no clock pair of its own. ``view`` names a view phase that is this
    very interval (one half of a phase timed in two pieces): it is
    reported with the same seconds just AHEAD of the phase, so a reader
    that rebuilds spans as (now - secs, now) and gives shared time to
    the earlier one always sees the view. Beside the wall clock an
    enabled recorder's span takes a reading of its thread's CPU clock
    at each end, just after the wall reading (``FlightRecorder._cpu_at_start``:
    at its start the last span's closing reading where that is under
    ``CPU_REUSE_S`` old, a read of the clock otherwise and at its end):
    ``c0``/``c1`` are the absolute readings (the CPU a thread spent
    BETWEEN two spans is the later ``c0`` less the earlier ``c1``),
    ``cpu`` the seconds between them as read, None where the recorder is
    off or the span ended on another thread than it began on (two
    threads' CPU clocks share no origin). What the span delivers to its
    phase's ``.cpu`` series is ``FlightRecorder._settle_cpu``'s to say."""

    __slots__ = ("_fl", "name", "view", "_tr", "_ann", "_tid",
                 "t0", "t1", "c0", "c1")

    def __init__(self, fl: "FlightRecorder", name: str,
                 tr: Optional[CycleTrace], view: Optional[str] = None):
        self._fl = fl
        self.name = name
        self.view = view
        self._tr = tr
        self._ann = None
        ann = fl._annotation
        if ann is not None and ann.is_enabled():
            # a profiler trace is being taken: the span shows over the
            # device's operations on the profiler's own clock
            self._ann = ann(name if view is None else view)
            self._ann.__enter__()
        self.t1 = self.c0 = self.c1 = self._tid = None
        self.t0 = t0 = fl._now()
        if fl.enabled:
            self._tid = tid = threading.get_ident()
            self.c0 = fl._cpu_at_start(tid, t0)

    @property
    def secs(self) -> float:
        return self.t1 - self.t0

    @property
    def cpu(self) -> Optional[float]:
        if self.c1 is None:
            return None
        return self.c1 - self.c0

    def end(self, report: bool = True,
            tr: Optional[CycleTrace] = None) -> float:
        """Close the span; returns its seconds. ``report=False`` closes
        it unrecorded (a drain that collected nothing is no phase);
        ``tr`` hands it to a cycle opened after the span began (the
        pop that opens its cycle)."""
        if tr is not None:
            self._tr = tr
        self.t1 = t1 = self._fl._now()
        if report and self.c0 is not None \
                and threading.get_ident() == self._tid:
            # an unrecorded span leaves the clock unread: what it burnt
            # counts to the span after it, like the glue between spans
            self.c1 = self._fl._cpu_at_end(self._tid, t1)
        if report:
            self._fl._report(self)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        return self.t1 - self.t0

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class FlightRecorder:
    """Always-on, low-overhead cycle recorder: a bounded ring of
    CycleTraces + per-phase / per-plugin histograms feeding the metrics
    Registry, with an optional JSON-lines export for offline analysis.

    Thread model: begin/record/plugin_observe run on the scheduling-
    loop thread only (binder-thread observations go through the
    scheduler's AsyncRecorder instead). ``span`` may run on any thread,
    given that one thread at a time writes one phase: a cycle's span
    lands on its CycleTrace (the commit thread's ``commit_pull`` is
    harvested before the loop records the cycle), a loop-level span in
    its own series of the phase histogram, under a lock: the binder
    workers write the one ``bind_chunk`` series at once. Readers
    (``/debug/trace``) take cheap snapshots of the deques."""

    def __init__(self, phase_hist=None, plugin_hist=None,
                 capacity: int = 256, export_path: Optional[str] = None,
                 enabled: bool = True, export_max_bytes: int = 0,
                 now: Callable[[], float] = time.monotonic,
                 gc_pause_hist=None,
                 cpu_now: Callable[[], float] = time.thread_time):
        self.enabled = enabled and capacity > 0
        self.phase_hist = phase_hist
        self.plugin_hist = plugin_hist
        self.gc_pause_hist = gc_pause_hist
        self._now = now
        # the calling thread's CPU clock, read by the spans of an
        # enabled recorder beside the wall clock (_cpu_at_start, _cpu_at_end)
        self._cpu_now = cpu_now
        # thread ident -> (wall instant, CPU reading) of that thread's
        # newest read of its CPU clock; each thread writes its own entry
        self._cpu_read: dict[int, tuple] = {}
        self._loop_lock = threading.Lock()
        # phase -> CPU seconds read but not yet delivered (_settle_cpu).
        # ONE WRITER A PHASE AT A TIME, which is the callers' to keep: a
        # cycle phase is written by the one thread that runs it (the
        # loop's, or the commit thread's commit_pull), a loop-level one
        # under _loop_lock (bind_chunk has four writers)
        self._cpu_owed: dict[str, float] = {}
        self.ring: collections.deque = collections.deque(
            maxlen=max(1, capacity))
        # loop-level spans (LOOP_PHASES and their views): (name, start,
        # end, thread ident, loop turn, the thread's CPU clock at the
        # start and at the end or None twice), bounded like the ring
        self.loop_spans: collections.deque = collections.deque(
            maxlen=max(1, capacity) * 16)
        self.turn = 0                # Scheduler.run's loop turn
        self._thread_names: dict[int, str] = {}
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:          # a control-plane process without JAX
            TraceAnnotation = None
        self._annotation = TraceAnnotation if self.enabled else None
        self.current: Optional[CycleTrace] = None
        self._cycle_seq = 0
        self._export_path = export_path
        self._export_file = None
        # size-based rotation (keep-last-1): a long trace-collection run
        # must not fill the disk. 0 = unbounded (tests/offline tooling).
        self._export_max_bytes = max(0, export_max_bytes)
        self._export_bytes = 0
        if export_path and self.enabled:
            self._export_file = open(export_path, "a", buffering=1)
            try:
                self._export_bytes = os.path.getsize(export_path)
            except OSError:
                self._export_bytes = 0

    @property
    def exporting(self) -> bool:
        """True while an export file is open — the scheduler's gate for
        the placement-row pulls (score + feature D2H) that only the
        offline replay consumer needs."""
        return self._export_file is not None

    # ------------- recording -------------

    def span(self, name: str, tr: Optional[CycleTrace] = None,
             view: Optional[str] = None) -> Span:
        """THE entry for timing a phase: returns a started Span; use it
        as a context manager or call ``end()``. With ``tr`` the span
        belongs to that cycle and is delivered through ``tr.add``;
        without, it is loop-level (stamped with the loop turn) and goes
        through ``observe_phase``. Its CPU seconds go beside them, into
        ``tr.cpu`` or straight into the histogram's ``"<phase>.cpu"``
        series, through neither of those two methods: what they see is
        wall time of phases, as before. A disabled recorder still times
        the span (callers read ``secs``), reads no CPU clock and records
        nothing."""
        return Span(self, name, tr, view)

    def _cpu_at_start(self, tid: int, t: float) -> float:
        """Thread ``tid``'s CPU clock for a span that starts at the wall
        instant ``t``, on that thread: the reading its last span closed
        with, where that is at most CPU_REUSE_S old on the wall clock
        (no read) or the thread has burnt at most CPU_REUSE_S since (a
        read, which a busy machine forces by stretching the gap, not the
        work in it); else a new reading. So two spans that follow each
        other share the read at their boundary, glue under CPU_REUSE_S
        counts to the later span whatever the machine's load, and the
        readings of a thread's spans tile its CPU time wherever its
        spans tile its work."""
        last = self._cpu_read.get(tid)
        if last is None:
            return self._cpu_at_end(tid, t)
        if not 0.0 <= t - last[0] <= CPU_REUSE_S:
            c = self._cpu_at_end(tid, t)
            if not 0.0 <= c - last[1] <= CPU_REUSE_S:
                return c
        return last[1]

    def _cpu_at_end(self, tid: int, t: float) -> float:
        """A read of thread ``tid``'s CPU clock, on that thread, kept with
        the wall instant ``t`` it belongs to for the span that follows."""
        c = self._cpu_now()
        self._cpu_read[tid] = (t, c)
        return c

    def _report(self, sp: Span) -> None:
        if not self.enabled:
            return
        tid = threading.get_ident()
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name
        tr = sp._tr
        secs = sp.t1 - sp.t0
        read = None if sp.c1 is None else max(sp.c1 - sp.c0, 0.0)
        for name in ((sp.name,) if sp.view is None else (sp.view, sp.name)):
            if tr is None:
                self.loop_spans.append((name, sp.t0, sp.t1, tid, self.turn,
                                        sp.c0, sp.c1))
                with self._loop_lock:    # bind_chunk: four writers
                    self.observe_phase(name, secs)
                    if read is not None and self.phase_hist is not None:
                        self.phase_hist.observe(
                            self._settle_cpu(name, secs, read),
                            phase=name + CPU_SUFFIX)
            elif tr is not _NULL_TRACE:
                tr.spans.append((name, sp.t0, sp.t1, tid, sp.c0, sp.c1))
                tr.add(name, secs)
                if read is not None:
                    tr.cpu[name] = tr.cpu.get(name, 0.0) \
                        + self._settle_cpu(name, secs, read)

    def _settle_cpu(self, name: str, secs: float, read: float) -> float:
        """The CPU seconds a span DELIVERS to its phase's ``.cpu`` series,
        the one figure derived from the readings: what its two readings
        gave plus what earlier spans of the phase read beyond their own
        length, held to its wall seconds; the rest is carried on, up to
        CPU_CARRY_MAX_S. Where the thread's CPU clock is exact (a plain
        Linux kernel) a span reads beyond its length only by the age of
        a shared reading (CPU_REUSE_S). Where it advances a tick at a
        time (10 ms on the benchmark's host), a span shorter than the
        tick reads nothing or a whole tick: cutting the tick down to the
        span would lose it and every short phase would read as waiting,
        carrying it keeps the phase's sum unbiased, and still never
        above its wall sum. One writer a phase (see ``_cpu_owed``)."""
        owed = self._cpu_owed.get(name, 0.0) + read
        cpu = min(owed, max(secs, 0.0))
        self._cpu_owed[name] = min(owed - cpu, CPU_CARRY_MAX_S)
        return cpu

    def observe_view(self, phase: str, secs: float) -> None:
        """A loop-level view whose seconds another component measured
        (LOOP_VIEW_PHASES), reported the instant it ended: kept as a
        span ending now with no CPU readings, delivered through
        ``observe_phase``."""
        if not self.enabled:
            return
        end = self._now()
        self.loop_spans.append((phase, end - secs, end,
                                threading.get_ident(), self.turn,
                                None, None))
        self.observe_phase(phase, secs)

    def gc_pause(self, secs: float, generation: int) -> None:
        """One collector pause (utils/gcguard's hook), on whichever
        thread the collector ran. Collections never overlap, so each of
        the two series written here has one writer at a time."""
        if self.enabled and self.gc_pause_hist is not None:
            self.gc_pause_hist.observe(secs, generation=str(generation))
        self.observe_view("gc_pause", secs)

    def begin(self, start: float, pods: int,
              chained: bool = False) -> CycleTrace:
        if not self.enabled:
            return _NULL_TRACE
        self._cycle_seq += 1
        tr = CycleTrace(self._cycle_seq, start, pods, chained)
        self.current = tr
        return tr

    def resume(self, tr: CycleTrace) -> None:
        """Re-attach a dispatched cycle's trace (the pipelined drain
        interleaves dispatch k+1 with finish k) so plugin timings land
        on the cycle whose commit is running."""
        if tr is not _NULL_TRACE:
            self.current = tr

    def record(self, tr: CycleTrace) -> None:
        """Cycle complete: ring + histograms + optional export line."""
        if tr is _NULL_TRACE:
            return
        if self.current is tr:
            self.current = None
        self.ring.append(tr)
        h = self.phase_hist
        if h is not None:
            for phase, secs in tr.phases.items():
                h.observe(secs, phase=phase)
            for phase, secs in tr.cpu.items():
                h.observe(secs, phase=phase + CPU_SUFFIX)
        if self._export_file is not None:
            line = json.dumps(tr.to_dict(self._thread_names)) + "\n"
            if self._export_max_bytes \
                    and self._export_bytes + len(line) \
                    > self._export_max_bytes \
                    and self._export_bytes > 0:
                self._rotate_export()    # may disable the export
            if self._export_file is not None:
                self._export_file.write(line)
                self._export_bytes += len(line)

    def _rotate_export(self) -> None:
        """Keep-last-1 rotation: the current file becomes ``<path>.1``
        (replacing any previous rotation) and a fresh file opens, so the
        on-disk footprint is bounded by ~2x export_max_bytes while the
        newest traces are always intact. A FAILED rotation (permissions
        changed, directory vanished) disables the export outright — the
        bound is the contract; silently resuming unbounded appends would
        reintroduce the disk-fill this exists to prevent."""
        try:
            self._export_file.close()
            os.replace(self._export_path, self._export_path + ".1")
            self._export_file = open(self._export_path, "a", buffering=1)
            self._export_bytes = 0
        except OSError:
            logger.error("trace export rotation failed for %s; "
                         "disabling the export (the size bound is the "
                         "contract)", self._export_path, exc_info=True)
            try:
                self._export_file.close()
            except OSError:
                pass
            self._export_file = None

    def observe_phase(self, phase: str, secs: float) -> None:
        """A standalone phase observation outside a cycle (binder drain
        between cycles, eviction flush, the host-fallback path)."""
        if not self.enabled:
            return
        if self.phase_hist is not None:
            self.phase_hist.observe(secs, phase=phase)

    def plugin_observe(self, plugin: str, point: str, secs: float) -> None:
        """Per-plugin timing from the framework runners; DynamicResources
        time additionally lands in the current cycle's dra_* view phases
        (the ROADMAP's 'DRA allocator Python time' attribution, split so
        future regressions attribute cleanly): host-path PreFilter/Filter
        evaluation feeds dra_device_eval, Reserve/PreBind commit
        bookkeeping feeds dra_commit (dra_mask_compile is observed
        directly by the Scheduler's tensor-build step)."""
        if not self.enabled:
            return
        if self.plugin_hist is not None:
            self.plugin_hist.observe(secs, plugin=plugin,
                                     extension_point=point)
        cur = self.current
        if cur is not None:
            key = f"{plugin}/{point}"
            cur.plugins[key] = cur.plugins.get(key, 0.0) + secs
            if plugin == "DynamicResources":
                cur.add("dra_commit" if point in ("Reserve", "PreBind")
                        else "dra_device_eval", secs)

    def close(self) -> None:
        if self._export_file is not None:
            self._export_file.close()
            self._export_file = None

    # ------------- reading (/debug/trace, perf/harness) -------------

    def last(self, n: int = 32) -> list[dict]:
        if n <= 0:        # [-0:] would be the WHOLE ring, not none of it
            return []
        return [tr.to_dict(self._thread_names)
                for tr in list(self.ring)[-n:]]

    def last_loop_spans(self, n: int = 256) -> list[list]:
        """The newest loop-level spans as [name, start, end, thread,
        turn, milliseconds between the two CPU readings or None]
        (``/debug/trace``'s ``loop_spans``)."""
        if n <= 0:
            return []
        names = self._thread_names
        return [[nm, round(a, 6), round(b, 6), names.get(t, t), turn,
                 _read_ms(c0, c1)]
                for nm, a, b, t, turn, c0, c1 in list(self.loop_spans)[-n:]]

    def phase_percentiles(self) -> dict:
        """{phase: {p50_ms, p90_ms, p99_ms, count, total_s}} from the
        phase histogram (bucket-resolution percentiles, like the rest of
        the registry); a phase's CPU seconds stand beside it as
        ``"<phase>.cpu"``."""
        h = self.phase_hist
        if h is None:
            return {}
        out = {}
        for k in list(h._series):
            labels = dict(k)
            phase = labels.get("phase", "?")
            s = h._series.get(k)
            if not s:
                continue
            out[phase] = {
                "p50_ms": round(h.percentile(50, **labels) * 1e3, 3),
                "p90_ms": round(h.percentile(90, **labels) * 1e3, 3),
                "p99_ms": round(h.percentile(99, **labels) * 1e3, 3),
                "count": s[2],
                "total_s": round(s[1], 6),
            }
        return out

    def plugin_percentiles(self) -> dict:
        """{"plugin/point": {p50_ms, p99_ms, count, total_s}} from the
        per-plugin histogram — the host-plugin / DRA-allocator slice of
        the per-phase breakdown."""
        h = self.plugin_hist
        if h is None:
            return {}
        out = {}
        for k in list(h._series):
            labels = dict(k)
            s = h._series.get(k)
            if not s:
                continue
            key = (f"{labels.get('plugin', '?')}/"
                   f"{labels.get('extension_point', '?')}")
            out[key] = {
                "p50_ms": round(h.percentile(50, **labels) * 1e3, 3),
                "p99_ms": round(h.percentile(99, **labels) * 1e3, 3),
                "count": s[2],
                "total_s": round(s[1], 6),
            }
        return out

    def host_tail_share(self) -> float:
        """Fraction of recorded cycle time spent in host-side phases
        (HOST_PHASES) vs everything measured except the dra_* views —
        the per-phase attribution headline for the sub-10x workloads."""
        h = self.phase_hist
        if h is None:
            return 0.0
        host = total = 0.0
        for k in list(h._series):
            phase = dict(k).get("phase", "?")
            if phase in UNCOUNTED_PHASES or phase.endswith(CPU_SUFFIX):
                continue
            s = h._series.get(k)
            if not s:
                continue
            total += s[1]
            if phase in HOST_PHASES:
                host += s[1]
        return host / total if total > 0 else 0.0


class PodTimelines:
    """Per-pod lifecycle timelines + last unschedulable diagnosis,
    bounded LRU over pods (the newest ``capacity`` pods touched). Events
    are (t, event, detail) tuples; the per-pod event list is capped so a
    requeue-storm pod cannot grow without bound. Lookup by name or uid
    (``/debug/pod?name=``)."""

    MAX_EVENTS_PER_POD = 64

    def __init__(self, capacity: int = 4096,
                 now: Callable[[], float] = time.time):
        self._now = now
        self._capacity = max(1, capacity)
        # uid -> {"name", "namespace", "events": [...], "diagnosis"}
        self._pods: collections.OrderedDict = collections.OrderedDict()
        self._by_name: dict[str, str] = {}   # "ns/name" -> uid (last wins)

    def _entry(self, pod) -> dict:
        uid = pod.metadata.uid
        e = self._pods.get(uid)
        if e is None:
            e = {"uid": uid, "name": pod.metadata.name,
                 "namespace": pod.metadata.namespace,
                 "events": [], "diagnosis": None, "wire": {}}
            self._pods[uid] = e
            self._by_name[f"{pod.metadata.namespace}/"
                          f"{pod.metadata.name}"] = uid
            while len(self._pods) > self._capacity:
                old_uid, old = self._pods.popitem(last=False)
                key = f"{old['namespace']}/{old['name']}"
                if self._by_name.get(key) == old_uid:
                    del self._by_name[key]
        else:
            self._pods.move_to_end(uid)
        return e

    def event(self, pod, event: str, detail: str = "",
              t: Optional[float] = None) -> None:
        e = self._entry(pod)
        ev = e["events"]
        ev.append((t if t is not None else self._now(), event, detail))
        if len(ev) > self.MAX_EVENTS_PER_POD:
            # keep the first events (enqueue/first attempt anchor the
            # timeline) and the newest tail
            del ev[8:len(ev) - self.MAX_EVENTS_PER_POD + 8]

    def diagnose(self, pod, device_rejects: dict, host_rejects: dict,
                 message: str = "") -> None:
        """Record why the pod's last attempt failed: device filter ->
        nodes-rejected counts (from the pulled reject_counts) and host
        plugin -> counts (from the host/fallback path)."""
        e = self._entry(pod)
        e["diagnosis"] = {
            "at": self._now(),
            "device_rejects": dict(device_rejects),
            "host_rejects": dict(host_rejects),
            "message": message,
        }

    def wire_stamp(self, pod, stamp: str, t: float, origin: str = "",
                   hops: int = 0) -> None:
        """Record one cross-wire trace stamp (telemetry.trace) on this
        pod's timeline: ``created`` (the pod's hub add commit),
        ``bound`` (the bind's hub commit), ``acked`` (the kubelet's
        status-Running commit), ``kubelet_recv`` (the bound event's
        arrival at the kubelet after its relay hops). Last stamp wins —
        a relist replaying an event re-stamps identically. Also logged
        as an ordinary timeline event so /debug/pod reads as one
        story."""
        e = self._entry(pod)
        e["wire"][stamp] = {"t": round(t, 6), "origin": origin,
                            "hops": hops}
        detail = f"origin={origin} hops={hops}" if origin else ""
        ev = e["events"]
        ev.append((t, f"wire:{stamp}", detail))
        if len(ev) > self.MAX_EVENTS_PER_POD:
            del ev[8:len(ev) - self.MAX_EVENTS_PER_POD + 8]

    def wire_of(self, uid: str) -> Optional[dict]:
        """The raw wire stamps recorded so far for one pod (None when
        the pod is untracked or unstamped) — the export rows' trace
        column reads this at commit time."""
        e = self._pods.get(uid)
        return (e["wire"] or None) if e else None

    def joined(self, uid: str) -> Optional[dict]:
        """The joined end-to-end trace for one pod (or None while
        incomplete) — telemetry.trace.joined_latency over the wire
        stamps."""
        from kubernetes_tpu.telemetry.trace import joined_latency

        e = self._pods.get(uid)
        return joined_latency(e) if e else None

    def uids(self) -> list[str]:
        return list(self._pods)

    def bind_latencies(self) -> dict[str, float]:
        """uid -> first-enqueued → first-bound seconds for every tracked
        pod that bound — the ONE time-to-bind pass behind both the perf
        harness's quality rows and the scenario replay driver's SLO gate
        (telemetry.slo). Pods that never bound (or whose enqueue stamp
        was LRU-evicted) are absent; callers that need full coverage
        size the timelines to the workload (config.timelines_capacity)."""
        out: dict[str, float] = {}
        for uid, e in self._pods.items():
            enq = bind = None
            for t, ev, _detail in e["events"]:
                if enq is None and ev == "enqueued":
                    enq = t
                elif bind is None and ev == "bound":
                    bind = t
                if enq is not None and bind is not None:
                    break
            if enq is not None and bind is not None and bind >= enq:
                out[uid] = bind - enq
        return out

    def get(self, name: str = "", uid: str = "",
            namespace: str = "default") -> Optional[dict]:
        if not uid and name:
            uid = self._by_name.get(f"{namespace}/{name}", "")
        e = self._pods.get(uid)
        if e is None:
            return None
        from kubernetes_tpu.telemetry.trace import joined_latency

        return {
            "uid": e["uid"], "name": e["name"],
            "namespace": e["namespace"],
            "events": [{"t": round(t, 6), "event": ev, "detail": d}
                       for t, ev, d in e["events"]],
            "diagnosis": e["diagnosis"],
            "wire": dict(e["wire"]),
            "joined": joined_latency(e),
        }

    def forget(self, uid: str) -> None:
        e = self._pods.pop(uid, None)
        if e is not None:
            key = f"{e['namespace']}/{e['name']}"
            if self._by_name.get(key) == uid:
                del self._by_name[key]

    def __len__(self) -> int:
        return len(self._pods)
