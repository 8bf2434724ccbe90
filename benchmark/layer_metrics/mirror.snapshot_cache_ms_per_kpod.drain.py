"""Mirror / pack: snapshot_cache seconds (cache.update_snapshot, the first half of snapshot_sync) per 1,000 pods bound."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("snapshot_cache",))
