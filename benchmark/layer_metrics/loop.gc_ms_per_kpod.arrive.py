"""Scheduling loop: gc_pause seconds (every collector pause, by utils/gcguard's own gc.callbacks hook, any thread) per 1,000 pods bound."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("gc_pause",))
