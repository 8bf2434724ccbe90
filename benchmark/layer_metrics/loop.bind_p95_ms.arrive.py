"""Scheduling loop: 95th percentile of the wait from due to bound. On this
traffic it is the length of one full collection plus a burst's drain, which
swings by a tenth from run to run: recorded here, judged nowhere."""

from benchmark import readers


def read(obs):
    return readers.percentile(obs.get("bind_ms") or [], 95)
