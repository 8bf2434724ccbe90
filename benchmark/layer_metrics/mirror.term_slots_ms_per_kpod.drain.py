"""Mirror / pack: slot_pack_terms seconds (the slow arm of Mirror._pack_pod_slot, the pod-table slots of pods with affinity terms, by the mirror's own clock inside mirror_sync) per 1,000 pods bound; nothing where the program has no such view."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("slot_pack_terms",))
