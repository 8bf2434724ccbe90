"""Device programs: programs compiled (or fetched from the persistent cache) in the window, by the benchmark's own jax.monitoring listener."""

from benchmark import readers


def read(obs):
    return readers.compiles_in_window(obs)
