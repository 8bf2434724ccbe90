"""Device programs: device time under schedule_batch_jit per 1,000 pods bound in the traced slice."""

from benchmark import readers


def read(obs):
    return readers.program_ms_per_kpod(obs, "schedule_batch_jit")
