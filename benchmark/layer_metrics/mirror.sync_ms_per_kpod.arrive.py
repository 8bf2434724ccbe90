"""Mirror / pack: snapshot_sync + chain_patch + pack seconds per 1,000 pods bound."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("snapshot_sync", "chain_patch", "pack"))
