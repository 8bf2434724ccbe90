"""Device programs: device milliseconds of one schedule_batch_jit launch, the median over the launches that lie whole inside the traced slice; nothing where the slice holds no whole launch."""

from benchmark import readers


def read(obs):
    return readers.launch_ms(obs, "schedule_batch_jit")
