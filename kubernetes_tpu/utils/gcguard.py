"""Scheduling-loop GC management.

The reference rides Go's concurrent GC; CPython's generational collector
instead stops the world whenever allocation counts trip a threshold — and a
2048-pod commit wave allocates enough to trip it several times per batch,
costing ~30% of production-path throughput (measured on SchedulingBasic).
The cure mirrors the well-known server recipe (gc.freeze): keep the
collector OFF while the loop is draining, sweep the young generations at
known-idle points where a bounded pause is invisible.

Reference-counting still reclaims the (acyclic) bulk of per-cycle garbage
immediately; what the guard defers is only cycle detection.

The guard also WATCHES the collector it manages: one ``gc.callbacks`` hook
for the life of the process times every pause and hands (seconds,
generation) to the flight recorders that asked (``watch``), on whichever
thread the collector ran — ``scheduler_gc_pause_seconds`` and the
``gc_pause`` view phase.
"""

from __future__ import annotations

import gc
import threading
import time
import weakref


class GCGuard:
    """Re-entrant "collector off while busy" scope.

    ``with guard:`` disables the collector on first entry and on last exit
    re-enables it and sweeps the young generations (gen 0+1 — bounded work,
    independent of total heap size). Nested/concurrent scopes share one
    disable. If the collector was already off (a test or embedder turned it
    off), the guard leaves it alone entirely.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._depth = 0
        self._managed = False
        # pause watch: weak references to the recorders, swapped whole
        # (never mutated) so the hook reads them without a lock — it can
        # fire inside any allocation, also one made under self._lock
        self._watchers: tuple = ()
        self._hooked = False
        self._t0 = 0.0

    def watch(self, recorder) -> None:
        """Report every collector pause to ``recorder.gc_pause(secs,
        generation)`` until it is unwatched or collected."""
        with self._lock:
            live = tuple(r for r in self._watchers if r() is not None)
            self._watchers = live + (weakref.ref(recorder),)
            if not self._hooked:
                gc.callbacks.append(self._on_gc)
                self._hooked = True

    def unwatch(self, recorder) -> None:
        with self._lock:
            self._watchers = tuple(
                r for r in self._watchers
                if r() is not None and r() is not recorder)

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections never overlap (the interpreter runs one at a time),
        # so one start instant is enough
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        secs = time.perf_counter() - self._t0
        for ref in self._watchers:
            recorder = ref()
            if recorder is not None:
                recorder.gc_pause(secs, info.get("generation", -1))

    def __enter__(self) -> "GCGuard":
        with self._lock:
            if self._depth == 0:
                self._managed = gc.isenabled()
                if self._managed:
                    gc.disable()
            self._depth += 1
        return self

    def __exit__(self, *exc) -> None:
        with self._lock:
            self._depth -= 1
            if self._depth == 0 and self._managed:
                gc.enable()
                gc.collect(1)

    def idle_sweep(self) -> None:
        """Bounded young-generation sweep for periodic ticks inside a long
        drain (call where a ~ms pause is acceptable, e.g. the 1s backoff
        flush): keeps deferred cyclic garbage from accumulating without
        ever paying a full gen-2 pass on the hot path."""
        with self._lock:
            if self._depth > 0 and self._managed:
                gc.collect(1)


# process-wide guard shared by every Scheduler in the process (the
# collector is process state; two schedulers must not fight over it)
guard = GCGuard()
