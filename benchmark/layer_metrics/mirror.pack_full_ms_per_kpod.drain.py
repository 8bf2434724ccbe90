"""Mirror / pack: pack_full seconds (the rows Mirror._pack_batch_np packed in full because the packed-row cache did not serve them, bypass and miss alike, by the mirror's own clock inside pack) per 1,000 pods bound; nothing where the program has no such view."""

from benchmark import readers


def read(obs):
    return readers.phase_ms_per_kpod(obs, ("pack_full",))
