"""Queues: CPU seconds of the loop thread inside queue_pop (the series queue_pop.cpu, PR 36) per 1,000 pods bound; what queue.pop_ms_per_kpod.drain reads beyond it is the thread waiting for the interpreter."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("queue_pop.cpu",))
