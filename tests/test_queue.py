"""PriorityQueue semantics vs the reference's queue tests
(backend/queue/scheduling_queue_test.go): tier transitions, backoff math,
queueing hints, in-flight event replay, gates, flush timers. Virtual clock
throughout (the reference uses testingclock the same way)."""

import dataclasses
import random
from collections import deque

import pytest

from kubernetes_tpu.api.objects import (
    ObjectMeta,
    Pod,
    PodSchedulingGate,
    PodSpec,
)
from kubernetes_tpu.backend.queue import PriorityQueue, QueuedPodInfo
from kubernetes_tpu.framework.interface import (
    ActionType,
    ClusterEvent,
    ClusterEventWithHint,
    EventResource,
    QueueingHint,
    Status,
)


class Clock:
    def __init__(self):
        self.t = 1000.0

    def now(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def less(a, b):
    if a.pod.priority() != b.pod.priority():
        return a.pod.priority() > b.pod.priority()
    return a.timestamp < b.timestamp


def mkpod(name, priority=0, gates=()):
    return Pod(metadata=ObjectMeta(name=name),
               spec=PodSpec(priority=priority,
                            scheduling_gates=[PodSchedulingGate(g)
                                              for g in gates]))


NODE_ADD = ClusterEvent(EventResource.NODE, ActionType.ADD)
POD_DELETE = ClusterEvent(EventResource.ASSIGNED_POD, ActionType.DELETE)


def gate_fn(pod):
    if pod.spec.scheduling_gates:
        return Status.unschedulable("gated", plugin="SchedulingGates",
                                    resolvable=False)
    return Status()


def mkq(clock=None, hints=None):
    clock = clock or Clock()
    q = PriorityQueue(less_fn=less, pre_enqueue=gate_fn,
                      queueing_hints=hints or {}, now=clock.now)
    return q, clock


def test_priority_then_fifo_order():
    q, _ = mkq()
    q.add(mkpod("low", 1))
    q.add(mkpod("high", 10))
    q.add(mkpod("mid", 5))
    assert [q.pop().pod.name for _ in range(3)] == ["high", "mid", "low"]


def test_unschedulable_then_event_requeues_with_backoff():
    hints = {"NodeResourcesFit": [ClusterEventWithHint(NODE_ADD)]}
    q, clock = mkq(hints=hints)
    q.add(mkpod("p"))
    qp = q.pop()
    qp.unschedulable_count += 1
    qp.unschedulable_plugins = {"NodeResourcesFit"}
    q.add_unschedulable_if_not_present(qp)
    assert q.pending_counts()["unschedulable"] == 1
    # an unrelated event must not move it
    q.move_all_to_active_or_backoff(POD_DELETE)
    assert q.pending_counts()["unschedulable"] == 1
    # the registered event moves it to backoff (1s not yet elapsed)
    q.move_all_to_active_or_backoff(NODE_ADD)
    assert q.pending_counts()["backoff"] == 1
    # backoff expires -> flush to active
    clock.tick(1.1)
    assert q.flush_backoff_completed() == 1
    assert q.pending_counts()["active"] == 1


def test_backoff_is_exponential_and_capped():
    q, clock = mkq()
    qp = QueuedPodInfo(pod=mkpod("p"), timestamp=clock.now())
    for attempts, want in ((1, 1.0), (2, 2.0), (3, 4.0), (5, 10.0),
                           (10, 10.0)):
        qp.unschedulable_count = attempts
        assert q.backoff_remaining(qp) == want


def test_queueing_hint_fn_skip_blocks_requeue():
    def hint(pod, old, new):
        return QueueingHint.SKIP

    hints = {"NodeResourcesFit": [ClusterEventWithHint(NODE_ADD, hint)]}
    q, _ = mkq(hints=hints)
    q.add(mkpod("p"))
    qp = q.pop()
    qp.unschedulable_plugins = {"NodeResourcesFit"}
    q.add_unschedulable_if_not_present(qp)
    q.move_all_to_active_or_backoff(NODE_ADD)
    assert q.pending_counts()["unschedulable"] == 1


def test_in_flight_event_replay():
    """An event arriving DURING a pod's failed cycle requeues it immediately
    instead of parking it in unschedulable (active_queue.go:147-169)."""
    hints = {"NodeResourcesFit": [ClusterEventWithHint(NODE_ADD)]}
    q, clock = mkq(hints=hints)
    q.add(mkpod("p"))
    qp = q.pop()
    q.move_all_to_active_or_backoff(NODE_ADD)  # concurrent with the cycle
    qp.unschedulable_count += 1
    qp.unschedulable_plugins = {"NodeResourcesFit"}
    q.add_unschedulable_if_not_present(qp)
    assert q.pending_counts()["unschedulable"] == 0
    assert q.pending_counts()["backoff"] == 1


def test_gated_pod_held_until_gates_removed():
    q, _ = mkq()
    old = mkpod("g", gates=("corp/hold",))
    q.add(old)
    assert q.pending_counts()["gated"] == 1
    assert q.pop() is None
    # unrelated events never touch the gated pool (the index skips it)
    q.move_all_to_active_or_backoff(NODE_ADD)
    assert q.pending_counts()["gated"] == 1
    # gates removed: the pod's own spec update re-runs PreEnqueue
    # (eventhandlers route pod updates through queue.update)
    q.update(old, Pod(metadata=old.metadata, spec=PodSpec()))
    assert q.pending_counts()["gated"] == 0
    assert q.pop().pod.name == "g"


def test_unschedulable_timeout_flush():
    q, clock = mkq()
    q.add(mkpod("p"))
    qp = q.pop()
    qp.unschedulable_plugins = {"NodeResourcesFit"}
    q.add_unschedulable_if_not_present(qp)
    assert q.flush_unschedulable_timeout() == 0
    clock.tick(301)
    assert q.flush_unschedulable_timeout() == 1
    assert q.pending_counts()["active"] == 1


def test_pop_batch_drains_in_order():
    q, _ = mkq()
    for i in range(5):
        q.add(mkpod(f"p{i}", priority=i))
    batch = q.pop_batch(3)
    assert [qp.pod.name for qp in batch] == ["p4", "p3", "p2"]
    assert q.in_flight_count() == 3
    for qp in batch:
        q.done(qp.uid)
    assert q.in_flight_count() == 0


def test_error_backoff_separate_counter():
    q, clock = mkq()
    qp = QueuedPodInfo(pod=mkpod("p"), timestamp=clock.now())
    qp.consecutive_errors_count = 3
    assert q.backoff_remaining(qp) == 4.0


# ------------- the in-flight event log against a log that is never trimmed

NODE_TAINT = ClusterEvent(EventResource.NODE, ActionType.UPDATE_NODE_TAINT)


def _index_hint(pod, old, new):
    """QUEUE for one event in four, a different one per pod: losing or
    replaying the wrong event changes where the pod lands."""
    if new is not None and new % 4 == int(pod.name[1:]) % 4:
        return QueueingHint.QUEUE
    return QueueingHint.SKIP


MODEL_HINTS = {
    "Hinted": [ClusterEventWithHint(NODE_ADD, _index_hint)],
    "Unhinted": [ClusterEventWithHint(POD_DELETE)],
}


class NeverTrimmedQueue(PriorityQueue):
    """The plain reference: every event ever seen in one list, never
    trimmed; a pod that comes back replays log[its pop's length:]."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.log = []
        self.started = {}

    def pop(self):
        qp = self._active.pop()
        if qp is None:
            return None
        qp.attempts += 1
        if qp.initial_attempt_timestamp is None:
            qp.initial_attempt_timestamp = self._now()
        self.started[qp.uid] = len(self.log)
        return qp

    def done(self, uid):
        self.started.pop(uid, None)

    def move_all_to_active_or_backoff(self, event, old_obj=None,
                                      new_obj=None):
        self.log.append((event, old_obj, new_obj))
        return super().move_all_to_active_or_backoff(event, old_obj, new_obj)

    def add_unschedulable_if_not_present(self, qp, pod_scheduling_cycle=0):
        start = self.started.pop(qp.uid, None)
        qp.timestamp = self._now()
        if self.is_parked(qp.uid):
            return
        if start is not None and any(
                self._worth_requeuing(qp, *e) for e in self.log[start:]):
            self._requeue(qp)
        elif qp.consecutive_errors_count > 0 \
                and not qp.unschedulable_plugins:
            self._requeue(qp)
        else:
            self._park(qp, self._unschedulable)


def _pool_of(q, uid, in_flight):
    for name in ("_active", "_backoff", "_unschedulable", "_gated"):
        if uid in getattr(q, name):
            return name
    return "in_flight" if uid in in_flight else "gone"


def _model_step(rng, clock, q, ref, pods, flying):
    """One random operation on both queues. ``flying`` holds, per uid in
    flight, the queue's QueuedPodInfo and the reference's."""
    clock.tick(rng.choice((0.0, 0.01, 0.4)))
    op = rng.choices(
        ("add", "pop", "event", "burst", "done", "fail", "flush", "re_add"),
        (5, 6, 8, 1, 3, 5, 2, 1))[0]

    def event():
        ev = rng.choice((NODE_ADD, POD_DELETE, NODE_TAINT))
        new = rng.randrange(8)
        assert q.move_all_to_active_or_backoff(ev, None, new) \
            == ref.move_all_to_active_or_backoff(ev, None, new)

    if op == "add":
        pod = mkpod(f"p{len(pods)}", priority=rng.randrange(3))
        pods.append(pod)
        q.add(pod)
        ref.add(pod)
    elif op == "pop":
        for _ in range(rng.randrange(1, 4)):    # a batch shares its start
            a, b = q.pop(), ref.pop()
            assert (a and a.uid) == (b and b.uid)
            if a is None:
                break
            flying[a.uid] = (a, b)
    elif op == "event":
        event()
    elif op == "burst":
        with q.coalescing(), ref.coalescing():
            for _ in range(rng.randrange(1, 6)):
                event()
    elif op == "flush":
        clock.tick(rng.choice((1.0, 11.0)))
        assert q.flush_backoff_completed() == ref.flush_backoff_completed()
    elif not flying:
        return op
    elif op == "done":
        uid = rng.choice(sorted(flying))
        del flying[uid]
        q.done(uid)
        ref.done(uid)
    elif op == "fail":
        a, b = flying.pop(rng.choice(sorted(flying)))
        plugins = rng.choice(({"Hinted"}, {"Unhinted"}, {"Hinted"},
                              {"Hinted", "Unhinted"}, set()))
        errors = int(not plugins and rng.random() < 0.5)
        for qp in (a, b):
            qp.unschedulable_plugins = set(plugins)
            qp.unschedulable_count += 1
            qp.consecutive_errors_count = errors
        q.add_unschedulable_if_not_present(a)
        ref.add_unschedulable_if_not_present(b)
    elif op == "re_add":
        # a relist re-delivers a pod whose cycle is still running: it can
        # be popped again, under a newer start, before its done()
        pod = flying[rng.choice(sorted(flying))][0].pod
        q.add(pod)
        ref.add(pod)
    return op


@pytest.mark.parametrize("seed", range(24))
def test_trimmed_log_replays_like_a_log_never_trimmed(seed):
    """Random interleavings of add, pop, hinted and unhinted events,
    coalesced bursts, done, failed cycles, re-pops and backoff flushes:
    every pod sits in the same pool as under the reference at every step,
    and the log holds exactly the events from the oldest in-flight start
    on (none once nothing is in flight)."""
    rng = random.Random(seed)
    clock = Clock()
    kw = dict(less_fn=less, pre_enqueue=gate_fn,
              queueing_hints=MODEL_HINTS, now=clock.now)
    q, ref = PriorityQueue(**kw), NeverTrimmedQueue(**kw)
    pods, flying, ops = [], {}, set()
    for _ in range(600):
        ops.add(_model_step(rng, clock, q, ref, pods, flying))
        assert set(q._in_flight) == set(ref.started)
        for pod in pods:
            uid = pod.metadata.uid
            assert _pool_of(q, uid, q._in_flight) \
                == _pool_of(ref, uid, ref.started), pod.name
        seqs = [e[0] for e in q._events]
        if q._in_flight:
            oldest = min(q._in_flight.values())
            assert seqs == list(range(oldest, q._next_seq))
        else:
            assert not seqs
    assert len(ops) == 8 and q.trim_scans > 0   # every operation ran
    assert q.pending_counts() == ref.pending_counts()


class Examined(deque):
    """A deque that counts the entries its owner looks at or drops."""

    examined = 0

    def __getitem__(self, i):
        self.examined += 1
        return super().__getitem__(i)

    def popleft(self):
        self.examined += 1
        return super().popleft()

    def __iter__(self):
        for entry in super().__iter__():
            self.examined += 1
            yield entry

    def clear(self):
        self.examined += len(self)
        super().clear()


class NeverWalked(dict):
    """The in-flight set answers by uid only."""

    def _walked(self, *a):
        raise AssertionError("the in-flight set was iterated")

    __iter__ = keys = values = items = _walked


def test_done_examines_each_entry_a_bounded_number_of_times():
    """20,000 pods in flight at 20,000 distinct starts, 40,000 events,
    done() in shuffled order: the log entries and start markers the queue
    looks at stay within a small multiple of events + pods (a min() over
    the in-flight set and a rebuilt log per done() would look at over
    10^8). A count of work, not a wall-clock time."""
    pods_n, events_n = 20_000, 40_000
    q, _ = mkq()
    q._events, q._starts = Examined(), Examined()
    q._in_flight = NeverWalked()
    uids = []
    for i in range(pods_n):
        q.add(mkpod(f"p{i}"))
        uids.append(q.pop().uid)
        for _ in range(events_n // pods_n):
            q.move_all_to_active_or_backoff(POD_DELETE)
    assert q.event_log_len() == events_n and len(q._starts) == pods_n
    random.Random(26).shuffle(uids)
    for uid in uids:
        q.done(uid)
        assert len(q._starts) <= len(q._events) + 1
    assert q.event_log_len() == 0 and not q._starts
    assert q.trim_calls == pods_n and q.events_high_water == events_n
    examined = q._events.examined + q._starts.examined
    assert examined <= 6 * (events_n + pods_n), examined


def park(q, pod, plugins=("NodeResourcesFit",), count=1):
    """Add, pop and fail ``pod``: it ends in the unschedulable pool."""
    q.add(pod)
    qp = q.pop()
    qp.unschedulable_count = count
    qp.unschedulable_plugins = set(plugins)
    q.add_unschedulable_if_not_present(qp)
    assert q.pending_counts()["unschedulable"] == 1
    return qp


@pytest.mark.parametrize("path", ["add", "activate", "event_requeue",
                                  "backoff_flush", "gate_lifted"])
def test_every_path_into_the_activeq_sets_the_wake_event(path):
    """The scheduling loop's idle wait ends on q.wake: every way a pod
    becomes poppable sets it."""
    hints = {"NodeResourcesFit": [ClusterEventWithHint(NODE_ADD)]}
    q, clock = mkq(hints=hints)
    p = mkpod("p", gates=("g",) if path == "gate_lifted" else ())
    if path == "activate":
        park(q, p)
    elif path == "event_requeue":
        park(q, p, count=0)             # no backoff: straight to active
    elif path == "backoff_flush":
        park(q, p)
        q.move_all_to_active_or_backoff(NODE_ADD)
        assert q.pending_counts()["backoff"] == 1
        clock.tick(1.1)
    elif path == "gate_lifted":
        q.add(p)
        assert q.pending_counts()["gated"] == 1
    q.wake.clear()
    if path == "add":
        q.add(p)
    elif path == "activate":
        q.activate([p])
    elif path == "event_requeue":
        assert q.move_all_to_active_or_backoff(NODE_ADD) == 1
    elif path == "backoff_flush":
        assert q.flush_backoff_completed() == 1
    else:
        q.update(p, dataclasses.replace(p, spec=PodSpec()))
    assert q.pending_counts()["active"] == 1
    assert q.wake.is_set()


def test_what_puts_no_pod_in_the_activeq_leaves_the_wake_event_clear():
    """A gated add, a park, an unrelated event, a move to backoff, an
    update of a queued pod and a delete leave q.wake as it was."""
    hints = {"NodeResourcesFit": [ClusterEventWithHint(NODE_ADD)]}
    q, clock = mkq(hints=hints)
    q.add(mkpod("gated", gates=("g",)))
    assert not q.wake.is_set()
    p = mkpod("p")
    park(q, p)
    q.wake.clear()
    q.move_all_to_active_or_backoff(POD_DELETE)
    assert not q.wake.is_set()
    q.move_all_to_active_or_backoff(NODE_ADD)
    assert q.pending_counts()["backoff"] == 1
    q.update(p, dataclasses.replace(p))
    q.delete(p)
    assert q.flush_backoff_completed() == 0
    assert len(q) == 1 and not q.wake.is_set()


# suite-tier discipline (tests/test_markers.py): area marker
pytestmark = pytest.mark.core
