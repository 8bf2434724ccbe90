"""The one general traffic generator: it reads a mix's parameter file
(benchmark/traffic/<mix>.json) and offers pods to the hub from its own thread.

Two kinds. `backlog` is a closed loop on depth: `depth` pods pending, refilled
in slabs as pods bind. `arrivals` is an open loop on a schedule of due
instants that is computed before the window from the file alone: the seed
never decides how many pods are due nor when, only what the pods are.
"""

from __future__ import annotations

import json
import os
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, rehearse: bool = False, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if rehearse:
        mix.update(mix.get("rehearse", {}))
    if mix["kind"] not in ("backlog", "arrivals"):
        raise ValueError(f"traffic mix {name}: unknown kind {mix['kind']!r}")
    return mix


def arrival_schedule(mix: dict, seconds: int) -> list[tuple[float, int]]:
    """(offset from the window's first instant in seconds, pods due then),
    ascending, from `warm_periods` burst periods before the window to its
    end. A base of `base_rate` pods/s in equal groups every `group_ms`, plus
    `burst_pods` more on every multiple of `burst_period_s`."""
    group_s = mix["group_ms"] / 1000.0
    per_group = mix["base_rate"] * mix["group_ms"] / 1000.0
    if per_group != int(per_group) or per_group < 1:
        raise ValueError("base_rate * group_ms / 1000 must be a whole "
                         f"number of pods, got {per_group}")
    period = float(mix["burst_period_s"])
    groups_per_period = round(period / group_s)
    if abs(groups_per_period * group_s - period) > 1e-9:
        raise ValueError("burst_period_s must be a multiple of group_ms")
    first = -int(mix["warm_periods"]) * groups_per_period
    last = round(seconds / group_s)
    out = []
    for k in range(first, last):
        n = int(per_group)
        if k % groups_per_period == 0:
            n += int(mix["burst_pods"])
        out.append((k * group_s, n))
    return out


class BacklogFeeder(threading.Thread):
    """Keeps `depth` pods pending: creates `depth` at once, then a slab each
    time the pending count falls a slab under the mark."""

    def __init__(self, hub, make_pod, bound_count, depth: int, slab: int,
                 clock=time.perf_counter) -> None:
        super().__init__(name="bench-feeder", daemon=True)
        self._hub, self._make, self._bound = hub, make_pod, bound_count
        self._depth, self._slab, self._clock = depth, slab, clock
        self._halt = threading.Event()
        self.offered: list[str] = []          # uids, in creation order
        self.error: BaseException | None = None

    def _create(self, n: int) -> None:
        for _ in range(n):
            pod = self._make(len(self.offered))
            self.offered.append(pod.metadata.uid)
            self._hub.create_pod(pod)

    def run(self) -> None:
        try:
            self._create(self._depth)
            while not self._halt.is_set():
                if len(self.offered) - self._bound() \
                        <= self._depth - self._slab:
                    self._create(self._slab)
                else:
                    self._halt.wait(0.002)
        except BaseException as e:  # noqa: BLE001 — read by the main thread
            self.error = e

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=60)


class ArrivalGenerator(threading.Thread):
    """Offers pre-built pods on their due instants. `pods` is consumed in
    order; `schedule` is arrival_schedule()'s; `t0` is the window's first
    instant on `clock`. Each pod's due instant and the instant the generator
    got to it are kept, so a pod is timed from when it was due and the
    generator's lateness is its own number."""

    def __init__(self, hub, pods: list, schedule: list[tuple[float, int]],
                 t0: float, bound_count, clock=time.perf_counter) -> None:
        super().__init__(name="bench-generator", daemon=True)
        self._hub, self._pods, self._schedule = hub, pods, schedule
        self._t0, self._bound, self._clock = t0, bound_count, clock
        self._halt = threading.Event()
        self.due: dict[str, float] = {}       # uid -> due instant
        self.sent: dict[str, float] = {}      # uid -> create instant
        self.depth_samples: list[tuple[float, int]] = []  # (offset, pending)
        self.error: BaseException | None = None
        i = 0
        for offset, n in schedule:
            for pod in pods[i:i + n]:
                self.due[pod.metadata.uid] = t0 + offset
            i += n
        if i > len(pods):
            raise ValueError(f"schedule needs {i} pods, {len(pods)} built")

    def run(self) -> None:
        try:
            i = 0
            for offset, n in self._schedule:
                wait = self._t0 + offset - self._clock()
                if wait > 0 and self._halt.wait(wait):
                    return
                self.depth_samples.append((offset, i - self._bound()))
                for pod in self._pods[i:i + n]:
                    pod.metadata.creation_timestamp = time.time()
                    self.sent[pod.metadata.uid] = self._clock()
                    self._hub.create_pod(pod)
                i += n
        except BaseException as e:  # noqa: BLE001 — read by the main thread
            self.error = e

    @property
    def offered(self) -> list[str]:
        return list(self.sent)

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=60)
