"""Scheduling loop: CPU seconds of the binder workers inside bind_chunk (bind_chunk.cpu, PR 36), all workers summed, per 1,000 pods bound: what they take of the interpreter the loop thread shares."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("bind_chunk.cpu",))
