"""Scheduling loop: commit + binder_drain seconds per 1,000 pods bound."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("commit", "binder_drain"))
