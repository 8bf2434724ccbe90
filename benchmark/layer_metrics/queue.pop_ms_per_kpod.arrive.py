"""Queues: flight-recorder queue_pop seconds in the window per 1,000 pods bound."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("queue_pop",))
