"""Scheduling loop: 99th percentile of the bind_p95_ms samples; recorded, never judged."""

from benchmark import readers


def read(obs):
    return readers.percentile(obs.get("bind_ms") or [], 99)
