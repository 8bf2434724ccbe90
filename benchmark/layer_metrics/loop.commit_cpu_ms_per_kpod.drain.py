"""Scheduling loop: CPU seconds of the loop thread inside commit + binder_drain (commit.cpu + binder_drain.cpu, PR 36) per 1,000 pods bound: what doing less work there can save; the rest of loop.commit_ms_per_kpod.drain is waiting."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("commit.cpu", "binder_drain.cpu"))
