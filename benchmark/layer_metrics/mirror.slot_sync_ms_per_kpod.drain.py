"""Mirror / pack: mirror_sync seconds (Mirror.sync, the second half of snapshot_sync: node rows and pod-table slots) per 1,000 pods bound."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("mirror_sync",))
