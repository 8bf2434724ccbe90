"""Preemption end-to-end: Evaluator + DefaultPreemption PostFilter +
nominator + device victim sweep.

Mirrors the reference's preemption integration tests
(test/integration/scheduler/preemption) against the in-process hub:
high-priority pods evict lower-priority victims, get a NominatedNodeName,
and bind once the victims vacate."""

from kubernetes_tpu.api.objects import (
    Container,
    LABEL_HOSTNAME,
    LABEL_ZONE,
    LabelSelector,
    Node,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodDisruptionBudget,
    PodSpec,
    ResourceRequirements,
)
from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import Scheduler


class Clock:
    def __init__(self):
        self.t = 1000.0

    def now(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def mknode(i, cpu="4"):
    name = f"node-{i}"
    return Node(metadata=ObjectMeta(name=name, labels={
        LABEL_HOSTNAME: name, LABEL_ZONE: "z1"}),
        status=NodeStatus(allocatable={"cpu": cpu, "memory": "32Gi",
                                       "pods": "110"}))


def mkpod(name, cpu="500m", priority=0, labels=None, policy=None):
    spec = PodSpec(
        containers=[Container(name="c", resources=ResourceRequirements(
            requests={"cpu": cpu, "memory": "256Mi"}))],
        priority=priority)
    if policy:
        spec.preemption_policy = policy
    return Pod(metadata=ObjectMeta(name=name, labels=labels or {}), spec=spec)


def mksched(hub, clock=None, batch=16):
    cfg = default_config()
    cfg.batch_size = batch
    clock = clock or Clock()
    return Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64),
                     now=clock.now), clock


def drain(sched, clock, rounds=6):
    for _ in range(rounds):
        sched.run_until_idle()
        clock.tick(3.0)
        sched.queue.flush_backoff_completed()
    sched.run_until_idle()


def bound_node(hub, pod):
    p = hub.get_pod(pod.metadata.uid)
    return p.spec.node_name if p else None


def test_basic_preemption_evicts_and_binds():
    """Cluster full of low-priority pods; a high-priority pod evicts enough
    victims on one node and binds there."""
    hub = Hub()
    sched, clock = mksched(hub)
    for i in range(2):
        hub.create_node(mknode(i, cpu="2"))
    low = [mkpod(f"low-{i}", cpu="1", priority=0) for i in range(4)]
    for p in low:
        hub.create_pod(p)
    drain(sched, clock)
    assert sched.stats["scheduled"] == 4  # both nodes full

    high = mkpod("high", cpu="1500m", priority=100)
    hub.create_pod(high)
    drain(sched, clock)
    assert bound_node(hub, high) in ("node-0", "node-1")
    assert sched.stats["preemptions"] == 1
    # exactly 2 victims evicted on the chosen node (each frees 1 cpu)
    gone = [p for p in low if hub.get_pod(p.metadata.uid) is None]
    assert len(gone) == 2
    assert {bound_node(hub, p) for p in low if hub.get_pod(p.metadata.uid)} \
        != {None}


def test_no_preemption_of_equal_or_higher_priority():
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="2"))
    incumbent = mkpod("incumbent", cpu="2", priority=100)
    hub.create_pod(incumbent)
    drain(sched, clock)
    assert bound_node(hub, incumbent) == "node-0"

    challenger = mkpod("challenger", cpu="1", priority=100)
    hub.create_pod(challenger)
    drain(sched, clock)
    assert hub.get_pod(incumbent.metadata.uid) is not None
    assert bound_node(hub, challenger) == ""


def test_preemption_policy_never():
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="2"))
    low = mkpod("low", cpu="2", priority=0)
    hub.create_pod(low)
    drain(sched, clock)

    never = mkpod("never", cpu="1", priority=100, policy="Never")
    hub.create_pod(never)
    drain(sched, clock)
    assert hub.get_pod(low.metadata.uid) is not None  # not evicted
    assert bound_node(hub, never) == ""


def test_minimal_victims_lowest_priority_first():
    """Victims are the least-important prefix: evicting the single prio-1
    pod suffices; the prio-5 pod survives."""
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="2"))
    p1 = mkpod("p1", cpu="1", priority=1)
    p5 = mkpod("p5", cpu="1", priority=5)
    hub.create_pod(p1)
    hub.create_pod(p5)
    drain(sched, clock)

    high = mkpod("high", cpu="1", priority=100)
    hub.create_pod(high)
    drain(sched, clock)
    assert bound_node(hub, high) == "node-0"
    assert hub.get_pod(p1.metadata.uid) is None      # evicted
    assert hub.get_pod(p5.metadata.uid) is not None  # reprieved


def test_pdb_violations_steer_candidate_choice():
    """Two viable nodes; victims on node-0 are PDB-protected with no
    disruptions left -> node-1 is preferred (fewest PDB violations)."""
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="2"))
    hub.create_node(mknode(1, cpu="2"))
    a = mkpod("a", cpu="2", priority=0, labels={"app": "guarded"})
    b = mkpod("b", cpu="2", priority=0, labels={"app": "free"})
    hub.create_pod(a)
    hub.create_pod(b)
    drain(sched, clock)
    node_of_a = bound_node(hub, a)
    hub.create_pdb(PodDisruptionBudget(
        metadata=ObjectMeta(name="pdb"),
        selector=LabelSelector(match_labels={"app": "guarded"}),
        disruptions_allowed=0))

    high = mkpod("high", cpu="1", priority=100)
    hub.create_pod(high)
    drain(sched, clock)
    assert hub.get_pod(a.metadata.uid) is not None   # protected pod survives
    assert hub.get_pod(b.metadata.uid) is None       # unprotected evicted
    assert bound_node(hub, high) is not None
    assert bound_node(hub, high) != node_of_a


def test_nominated_reservation_not_stolen():
    """After preemption the preemptor's NominatedNodeName reserves the
    vacated room: a later lower-priority pod must not steal it."""
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="2"))
    low = mkpod("low", cpu="2", priority=0)
    hub.create_pod(low)
    drain(sched, clock)

    high = mkpod("high", cpu="2", priority=100)
    hub.create_pod(high)
    # one batch: preempt + nominate, victim deleted, high parked
    sched.run_until_idle()
    nominated = hub.get_pod(high.metadata.uid).status.nominated_node_name
    assert nominated == "node-0"
    # an opportunist shows up before high re-schedules
    opportunist = mkpod("opportunist", cpu="2", priority=0)
    hub.create_pod(opportunist)
    drain(sched, clock)
    assert bound_node(hub, high) == "node-0"
    assert bound_node(hub, opportunist) == ""


def test_preemption_for_anti_affinity_blocked_pod():
    """The pod FITS resource-wise everywhere, but a low-priority victim's
    presence violates the preemptor's required anti-affinity on every node;
    evicting the victim (not freeing resources) is what helps — the
    full-pipeline dry-run finds it, the resource-only sweep could not
    (default_preemption.go:219 removes victims then re-runs ALL filters)."""
    from kubernetes_tpu.api.objects import (
        Affinity,
        PodAffinityTerm,
        PodAntiAffinity,
    )

    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="8"))
    # a low-priority pod labeled app=red sits on the only node
    blocker = mkpod("blocker", cpu="100m", priority=0,
                    labels={"app": "red"})
    hub.create_pod(blocker)
    drain(sched, clock)
    assert bound_node(hub, blocker) == "node-0"

    # high-priority pod with required anti-affinity against app=red:
    # resources are plentiful; only the blocker's eviction helps
    anti = Affinity(pod_anti_affinity=PodAntiAffinity(required=[
        PodAffinityTerm(topology_key=LABEL_HOSTNAME,
                        label_selector=LabelSelector(
                            match_labels={"app": "red"}))]))
    high = mkpod("high", cpu="100m", priority=100)
    high.spec.affinity = anti
    hub.create_pod(high)
    drain(sched, clock)
    assert hub.get_pod(blocker.metadata.uid) is None, "blocker evicted"
    assert bound_node(hub, high) == "node-0"
    assert sched.stats["preemptions"] >= 1


def test_no_useless_eviction_when_anti_affinity_unresolvable():
    """The preemptor's anti-affinity blocker is a HIGHER-priority pod: no
    victim set can help, so nothing must be evicted even though plenty of
    lower-priority victims exist (the exact dry-run discards the node)."""
    from kubernetes_tpu.api.objects import (
        Affinity,
        PodAffinityTerm,
        PodAntiAffinity,
    )

    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="8"))
    blocker = mkpod("blocker", cpu="100m", priority=200,
                    labels={"app": "red"})
    filler = mkpod("filler", cpu="100m", priority=0)
    hub.create_pod(blocker)
    hub.create_pod(filler)
    drain(sched, clock)

    anti = Affinity(pod_anti_affinity=PodAntiAffinity(required=[
        PodAffinityTerm(topology_key=LABEL_HOSTNAME,
                        label_selector=LabelSelector(
                            match_labels={"app": "red"}))]))
    high = mkpod("high", cpu="100m", priority=100)
    high.spec.affinity = anti
    hub.create_pod(high)
    drain(sched, clock)
    assert bound_node(hub, high) == ""
    assert hub.get_pod(filler.metadata.uid) is not None, \
        "no useless eviction of the unrelated filler"
    assert sched.stats.get("preemptions", 0) == 0


def test_pdb_violating_victims_reprieved_first():
    """Two equal candidates for reprieve; the PDB-protected victim must be
    the one KEPT when either alone would satisfy the preemptor."""
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="2"))
    protected = mkpod("protected", cpu="1", priority=0,
                      labels={"app": "guarded"})
    plain = mkpod("plain", cpu="1", priority=0)
    hub.create_pod(protected)
    hub.create_pod(plain)
    hub.create_pdb(PodDisruptionBudget(
        metadata=ObjectMeta(name="pdb"),
        selector=LabelSelector(match_labels={"app": "guarded"}),
        disruptions_allowed=0))
    drain(sched, clock)
    assert sched.stats["scheduled"] == 2

    high = mkpod("high", cpu="1", priority=100)
    hub.create_pod(high)
    drain(sched, clock)
    assert bound_node(hub, high) == "node-0"
    assert hub.get_pod(protected.metadata.uid) is not None, \
        "PDB-protected victim reprieved"
    assert hub.get_pod(plain.metadata.uid) is None


def test_async_gate_holds_preemptor_until_victims_gone():
    """While the eviction work is queued, the preemptor is gated out of the
    activeQ (DefaultPreemption PreEnqueue); once flush_evictions runs, the
    deletion events requeue and it binds."""
    hub = Hub()
    sched, clock = mksched(hub)
    hub.create_node(mknode(0, cpu="2"))
    low = [mkpod(f"low-{i}", cpu="1", priority=0) for i in range(2)]
    for p in low:
        hub.create_pod(p)
    drain(sched, clock)

    high = mkpod("high", cpu="2", priority=100)
    hub.create_pod(high)
    drain(sched, clock)
    assert bound_node(hub, high) == "node-0"
    assert all(hub.get_pod(p.metadata.uid) is None for p in low)
    assert not sched.preemption.preempting, "gate cleared after evictions"


def test_sweep_never_drops_inactive_resource_constraint():
    """Column-subset sweep regression: victims free ONLY memory (cpu-less
    requests), the preemptor needs more CPU than the node has — eviction
    can never help, so preemption must find no candidate and evict
    nothing (the padding-alias bug silently deleted the CPU constraint
    from the sweep)."""
    hub = Hub()
    hub.create_node(mknode(0, cpu="4"))
    # an UNEVICTABLE cpu hog pins the node's cpu (priority above the
    # preemptor), so cpu stays scarce no matter what gets evicted
    hog = mkpod("cpu-hog", cpu="3500m", priority=100)
    hub.create_pod(hog)
    # low-priority victims request memory only: the freed-column set is
    # {memory, pods}, cpu inactive
    victims = []
    for i in range(3):
        v = Pod(metadata=ObjectMeta(name=f"memhog-{i}"),
                spec=PodSpec(containers=[Container(
                    name="c", resources=ResourceRequirements(
                        requests={"memory": "8Gi"}))], priority=50))
        victims.append(v)
        hub.create_pod(v)
    sched, clock = mksched(hub)
    drain(sched, clock, rounds=2)
    assert all(hub.get_pod(v.metadata.uid).spec.node_name
               for v in victims), "victims must be running"
    # preemptor: 2 CPU (only 500m free; no victim frees cpu) AND 8Gi
    # memory (only ~7.7Gi free; victims DO free memory). Eviction makes
    # the memory half fit but never the cpu half, and cpu is within
    # allocatable so the unresolvable guard does not fire — only the
    # sweep's cpu constraint stands between this pod and a useless
    # eviction at kmin>=1
    pre = Pod(metadata=ObjectMeta(name="cpu-hungry"),
              spec=PodSpec(containers=[Container(
                  name="c", resources=ResourceRequirements(
                      requests={"cpu": "2", "memory": "8Gi"}))],
                  priority=60))
    hub.create_pod(pre)
    drain(sched, clock, rounds=3)
    assert hub.get_pod(pre.metadata.uid).spec.node_name == ""
    assert sched.stats.get("preemptions", 0) == 0, \
        "no nomination may come from a sweep that ignored the cpu column"
    for v in victims:
        assert hub.get_pod(v.metadata.uid) is not None, \
            "no victim may be evicted for an unresolvable preemptor"


# suite-tier discipline (tests/test_markers.py): area marker
import pytest  # noqa: E402
pytestmark = pytest.mark.core


def test_batched_sweeps_keep_the_victim_state_on_the_host(monkeypatch):
    """The batched path reads the victim cumsum on the host (_host_kmin):
    its first build and every row update dispatch nothing to the device,
    so a sweep whose dirty rows fall in a new pow2 bucket compiles no
    scatter inside a run (on one TPU v5e the PreemptionBasic cell met one
    to three such compiles in a 30 s window while the state was kept on
    the device). Only the single-pod device sweep uploads the state, and
    its answer stands."""
    from kubernetes_tpu.framework import preemption

    uploads = []
    real = preemption.jnp.asarray

    class Jnp:
        def __getattr__(self, name):
            return getattr(preemption.jnp_real, name)

        @staticmethod
        def asarray(x, *a, **kw):
            uploads.append(getattr(x, "shape", None))
            return real(x, *a, **kw)

    monkeypatch.setattr(preemption, "jnp_real", preemption.jnp,
                        raising=False)
    monkeypatch.setattr(preemption, "jnp", Jnp())
    hub = Hub()
    sched, clock = mksched(hub)
    for i in range(6):
        hub.create_node(mknode(i, cpu="4"))
    for i in range(24):
        hub.create_pod(mkpod(f"low-{i}", cpu="900m", priority=0))
    drain(sched, clock)
    highs = []
    for wave in (1, 2, 3):          # three sweeps: a build, then updates
        for j in range(wave):
            highs.append(mkpod(f"high-{wave}-{j}", cpu="3", priority=10))
            hub.create_pod(highs[-1])
        drain(sched, clock)
    assert all(bound_node(hub, p) for p in highs)
    sweep = sched.preemption.sweep_stats()
    assert sweep["nominated"] >= 6
    assert sweep["victim_state_rebuilds"] >= 1
    assert sweep["victim_state_updates"] >= 1
    assert [u for u in uploads if u is not None and len(u) == 3] == []
    # the single-pod device sweep uploads the host state and reads it
    prio = 10
    st = sched.preemption._vic_state[prio]
    kmin = sched.preemption._host_kmin(
        mkpod("probe", cpu="3", priority=prio), st, sched.mirror,
        sched.mirror.free_matrix())
    sched.cache.update_snapshot(sched.snapshot)
    cands = sched.preemption.find_candidates(
        mkpod("probe", cpu="3", priority=prio), sched.snapshot,
        resource_only=True)
    assert any(u is not None and len(u) == 3 for u in uploads)
    assert {c.row for c in cands} == {
        r for r in range(len(kmin)) if 1 <= kmin[r] < 2 ** 30
        and r in st["victims_by_row"]}


def test_a_sweep_sees_the_nominations_of_the_sweeps_before_it():
    """Two failure handlings can sweep back to back with no launch's pack
    between them (the pipelined drain finishes its last waves together),
    so the mirror's nominated overlay does not yet hold the first sweep's
    nominations. The second sweep must still leave the first one's node
    alone: where that node was the only candidate, the second preemptor
    has none, instead of a plan whose victims the first plan already
    claims (its flush would fire no deletion, and the preemptor would be
    activated only to fail and be swept again)."""
    import types

    hub = Hub()
    sched, clock = mksched(hub)
    for i in range(3):
        hub.create_node(mknode(i, cpu="4"))
    # node-0 holds priority-0 fillers; the other two are full of pods of
    # the preemptors' own priority, which no preemptor may evict
    for i in range(3):
        for j in range(4):
            p = mkpod(f"fill-{i}-{j}", cpu="900m", priority=0 if i == 0
                      else 10)
            p.spec.node_name = f"node-{i}"
            hub.create_pod(p)
    drain(sched, clock)
    sched.cache.update_snapshot(sched.snapshot)
    sched.mirror.sync(sched.snapshot)
    sched.mirror.set_nominated(sched.nominator.by_node())

    def job(name):
        pod = mkpod(name, cpu="3", priority=10)
        hub.create_pod(pod)
        return types.SimpleNamespace(pod=pod, uid=pod.metadata.uid)

    first = sched.preemption.batch_preempt([job("a")], sched.snapshot)
    assert [node for node, _s in first.values()] == ["node-0"]
    second = sched.preemption.batch_preempt([job("b")], sched.snapshot)
    assert [node for node, _s in second.values()] == [None]
    assert sched.preemption.sweep_stats()["no_candidates"] == 1
    sched.close()


def _finish_one_kmin_a_preemptor(ev, handle, rng):
    """finish_batch_preempt's decisions with _host_kmin computed once per
    preemptor (no memo), drawing from ``rng``: {uid: (node | None,
    Status, victim uids)}. Writes nothing of the evaluator's state."""
    import numpy as np

    from kubernetes_tpu.framework.interface import Status
    from kubernetes_tpu.framework.preemption import (
        MAX_VERIFY_CANDIDATES,
        Candidate,
        Evaluator,
    )
    from kubernetes_tpu.utils.interner import NONE

    eligible, victims_by_row, st, mirror, snapshot = handle
    ev.cache_snapshot = snapshot.node_info_map
    free_mat = mirror.free_matrix()
    pdbs = ev.hub.list_pdbs()
    used_rows = {row for uid, row in ev._unreserved.items()
                 if not mirror.holds_nomination(uid)
                 and ev.nominator.node_of(uid) is not None}
    out = {}
    for qp in eligible:
        kmin = ev._host_kmin(qp.pod, st, mirror, free_mat)
        rows = np.nonzero((kmin != NONE) & (kmin >= 1))[0]
        window = []
        if len(rows):
            off = rng.randrange(len(rows))
            for i in range(len(rows)):
                row = int(rows[(off + i) % len(rows)])
                vs = victims_by_row.get(row)
                k = int(kmin[row])
                if (vs is None or row in used_rows or k > len(vs)
                        or not ev._host_static_ok(
                            qp.pod, mirror.name_of_row(row) or "")):
                    continue
                window.append((row, k))
                if len(window) >= MAX_VERIFY_CANDIDATES:
                    break
        candidates = []
        for row, k in window:
            vs = ev._reprieve_by_resources(
                [pi.pod for pi in victims_by_row[row][:k]], qp.pod, row,
                free_mat)
            candidates.append(Candidate(
                node_name=mirror.name_of_row(row) or "", row=row,
                victims=vs, pdb_violations=ev._pdb_violations(vs, pdbs)))
        if not candidates:
            out[qp.uid] = (None, Status.unschedulable(
                "no preemption candidates", plugin="DefaultPreemption"), [])
            continue
        best = Evaluator.select_candidate(candidates)
        used_rows.add(best.row)
        out[qp.uid] = (best.node_name, Status(),
                       [v.metadata.uid for v in best.victims])
    return out


@pytest.mark.parametrize("seed", [4200000101, 4200000202, 4200000303])
def test_one_kmin_a_request_decides_as_one_kmin_a_preemptor(seed):
    """finish_batch_preempt computes one kmin per distinct (request row,
    own nominated row) of a burst and reuses it for the preemptors equal to
    it. Over a seeded cluster, with equal requests, two requests of one
    priority, a preemptor that carries a nomination (its own request handed
    back to its row) and a non-empty nominated overlay, its nominations,
    statuses, victims and rotation draws are those of a loop that computes
    kmin once per preemptor."""
    import random
    import types

    rng = random.Random(seed)
    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 16
    clock = Clock()
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=128),
                      now=clock.now)
    try:
        for i in range(12):
            hub.create_node(mknode(i, cpu="4"))
            # fillers of mixed size and priority: some nodes hold pods the
            # preemptors may not evict, so candidates differ by node
            for j in range(4):
                p = mkpod(f"fill-{i}-{j}",
                          cpu=rng.choice(("700m", "900m", "950m")),
                          priority=rng.choice((0, 0, 1, 5, 20)))
                p.spec.node_name = f"node-{i}"
                hub.create_pod(p)
        drain(sched, clock)

        def job(name, cpu, nominated=""):
            pod = mkpod(name, cpu=cpu, priority=10)
            hub.create_pod(pod)
            if nominated:
                pod.status.nominated_node_name = nominated
                sched.nominator.add(pod, nominated)
            return types.SimpleNamespace(pod=pod, uid=pod.metadata.uid)

        # a parked pod's nomination: the overlay reserves it on its node
        parked = mkpod("parked", cpu="1000m", priority=10)
        hub.create_pod(parked)
        sched.nominator.add(parked, f"node-{rng.randrange(12)}")
        kinds = ["3000m"] * 5 + ["2000m"] * 3
        rng.shuffle(kinds)
        jobs = [job(f"high-{j}", cpu) for j, cpu in enumerate(kinds)]
        # first, so that the plain 3000m preemptors after it would read its
        # handed-back row if the key left the own row out
        jobs.insert(0, job("renominated", "3000m",
                           nominated=f"node-{rng.randrange(12)}"))
        sched.cache.update_snapshot(sched.snapshot)
        sched.mirror.sync(sched.snapshot)
        ev = sched.preemption

        def sweep(jobs, keys):
            sched.mirror.set_nominated(sched.nominator.by_node())
            assert sched.mirror._nominated_req_of_row
            handle, immediate = ev.begin_batch_preempt(jobs, sched.snapshot)
            assert handle is not None and not immediate
            ref_rng = random.Random()
            ref_rng.setstate(ev._rng.getstate())
            want = _finish_one_kmin_a_preemptor(ev, handle, ref_rng)
            before = ev.sweep_stats()
            ev._pending.clear()
            got = ev.finish_batch_preempt(handle)
            victims = {pod.metadata.uid: [v.metadata.uid for v in c.victims]
                       for c, pod in ev._pending}
            assert {uid: (node, status, victims.get(uid, []))
                    for uid, (node, status) in got.items()} == want
            assert ev._rng.getstate() == ref_rng.getstate()
            after = ev.sweep_stats()
            assert after["kmin_computed"] - before["kmin_computed"] == keys
            assert after["kmin_reused"] - before["kmin_reused"] \
                == len(jobs) - keys
            return want

        # three keys: 3000m, 2000m, 3000m with its own row handed back
        first = sweep(jobs, 3)
        assert any(node for node, _s, _v in first.values())
        # the next burst sweeps against an overlay that now reserves the
        # first one's nominations: nothing of the first call's kmins holds
        again = [job(f"again-{j}", cpu) for j, cpu in enumerate(kinds)]
        sweep(again, 2)
    finally:
        sched.close()
