"""Process-wide JAX configuration for the scheduler runtime.

XLA compilation on the target environment is expensive (seconds per program,
including trivial ones), while cached executions are microseconds. The
framework therefore (a) funnels all per-cycle math through a small number of
large jitted programs keyed by static capacity buckets, and (b) enables the
persistent compilation cache so restarts skip recompiles entirely.

The cache directory is placed from OUTSIDE: when the environment sets
``JAX_COMPILATION_CACHE_DIR`` JAX already holds that path and this module
sets none; otherwise the fixed ``<checkout>/.jax_cache`` is used (the path
must not move between processes or the cache never hits). Either way every
child process of an entry point lands in the same directory.
"""

from __future__ import annotations

import os
import threading

_done = False

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def setup() -> None:
    global _done
    if _done:
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # no size/time floor: the tiny eager one-op programs are cached too
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _done = True


def device_info() -> dict:
    """The device a measurement ran on, as JAX reports it — every bench
    row and smoke result carries this so a CPU number can never pass for
    a chip number."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


class CompileMeter:
    """Counts XLA compiles and persistent-cache hits/misses per program,
    from ``jax.monitoring`` (listeners cannot be unregistered, so create
    one per process). JAX records the cache verdict event first and the
    ``backend_compile_duration`` (which names the program) right after on
    the same thread; pairing the two attributes each hit/miss to its
    program name (``jit(schedule_batch_jit)``, ``jit(slice)``, ...)."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring

        self._lock = threading.Lock()
        self._pending = threading.local()
        # program name -> [compiles, hits, misses, seconds]
        self._by_name: dict[str, list] = {}
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self._pending.verdict = 1
        elif event == self._MISS:
            self._pending.verdict = 2

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event != self._COMPILE:
            return
        verdict = getattr(self._pending, "verdict", 0)
        self._pending.verdict = 0
        name = str(kw.get("fun_name", "?"))
        with self._lock:
            rec = self._by_name.setdefault(name, [0, 0, 0, 0.0])
            rec[0] += 1
            if verdict:
                rec[verdict] += 1
            rec[3] += secs

    def totals(self) -> dict:
        """{"compiles", "cache_hits", "cache_misses", "compile_s"} over
        every program compiled (or fetched from the cache) so far."""
        with self._lock:
            recs = list(self._by_name.values())
        return {"compiles": sum(r[0] for r in recs),
                "cache_hits": sum(r[1] for r in recs),
                "cache_misses": sum(r[2] for r in recs),
                "compile_s": round(sum(r[3] for r in recs), 2)}

    def by_name(self) -> dict[str, dict]:
        with self._lock:
            return {n: {"compiles": r[0], "cache_hits": r[1],
                        "cache_misses": r[2], "compile_s": round(r[3], 2)}
                    for n, r in self._by_name.items()}

    def misses(self, names: tuple[str, ...]) -> dict[str, int]:
        """Cache misses on the named jitted programs (matched as
        ``jit(<name>)``); empty = none missed."""
        want = {f"jit({n})" for n in names}
        with self._lock:
            return {n: r[2] for n, r in self._by_name.items()
                    if n in want and r[2]}
