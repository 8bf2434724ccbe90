"""Scheduling loop: the longest collector pause in the window, from the
benchmark's gc.callbacks. A window with no collection has no pause to read."""


def read(obs):
    pauses = [ms for ms, _gen in obs["gc_pauses_ms"]]
    return max(pauses) if pauses else None
