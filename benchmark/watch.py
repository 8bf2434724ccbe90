"""The benchmark's side of the hub watch stream.

A copy of the watch hook of perf/collector.ThroughputCollector: a pod that
gains spec.nodeName counts as bound, at the instant the event reaches this
watcher. Every bind event is kept, so a second bind of one pod shows.
"""

from __future__ import annotations

import time


class BindWatcher:
    def __init__(self, clock=time.perf_counter) -> None:
        self._clock = clock
        self.first: dict[str, float] = {}    # uid -> first bind event
        self.repeats: list[str] = []         # uids bound again

    def _seen(self, pod) -> None:
        if not pod.spec.node_name:
            return
        uid = pod.metadata.uid
        if uid in self.first:
            self.repeats.append(uid)
        else:
            self.first[uid] = self._clock()

    def on_add(self, pod) -> None:
        self._seen(pod)

    def on_update(self, old, new) -> None:
        if not old.spec.node_name:
            self._seen(new)
        elif new.spec.node_name != old.spec.node_name:
            self.repeats.append(new.metadata.uid)

    def attach(self, hub) -> None:
        from kubernetes_tpu.hub import EventHandlers

        hub.watch_pods(EventHandlers(on_add=self.on_add,
                                     on_update=self.on_update), replay=False)

    def bound_count(self) -> int:
        return len(self.first)
