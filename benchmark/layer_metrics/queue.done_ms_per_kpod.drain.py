"""Queues: flight-recorder queue_done seconds (PriorityQueue._trim_events dropping entries from the head of the in-flight event log while pods stay in flight, by the queue's own clock) in the window per 1,000 pods bound."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("queue_done",))
