"""Entry points: how late the generator created a pod, 99th percentile."""

from benchmark import readers


def read(obs):
    return readers.percentile(obs.get("late_ms") or [], 99)
