"""Scheduling loop: maintenance + lock_wait + event_intake + gc_sweep + drain_tail seconds, the fixed cost of loop turns, per 1,000 pods bound."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, (
        "maintenance", "lock_wait", "event_intake", "gc_sweep", "drain_tail"))
