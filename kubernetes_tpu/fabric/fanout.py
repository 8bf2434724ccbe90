"""Fan-in scale smoke: 10k kubelet-analog reflectors through a relay tree.

Run it: ``python -m kubernetes_tpu.fabric.fanout [--procs]``. One hub, a
chaos proxy in front of
it, two level-1 relay nodes dialing upstream through the proxy, eight
level-2 relay nodes dialing the level-1s, and 10k simulated reflectors
(in-process subscribers — bounded queues and resume cursors, the exact
relay-facing surface an HTTP reflector has, without 10k sockets of
harness overhead) hanging off the level-2s.

Gates (the ISSUE-9 acceptance criteria):

* the hub holds ≤ level-1-relay-count pod watch sockets, however many
  reflectors subscribe downstream;
* a chaos watch-cut storm against the relays' upstream streams
  reconnects via journal RESUME every time — zero relists, zero lost
  events (every subscriber converges to the hub's final revision with
  the exact event count);
* a mid-storm reconnect wave of downstream subscribers is served
  entirely from the relay rings (resume), never from the hub;
* a deliberately slow subscriber is EVICTED (bounded queue) and counted,
  then catches back up via resume after reconnecting — backpressure
  cuts one consumer, not the tree;
* the binary wire codec carries the same event stream in ≤ 1/3 the
  bytes of the JSON wire (measured on the storm's own events);
* a scheduler's drift sentinel in steady state issues ZERO full LIST
  calls (journal-rv incremental diffing, ROADMAP's carried-over
  O(cluster) gap).
"""

from __future__ import annotations

import json
import time

from kubernetes_tpu.fabric import codec as binwire
from kubernetes_tpu.fabric.relay import RelayCore
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.utils.wire import to_wire


def _wire_bytes(events: list[dict]) -> tuple[int, int]:
    """(json_bytes, bin1_bytes) for the same event stream — the
    wire-bytes-per-cycle comparison, measured on real storm events."""
    jb = bb = 0
    for ev in events:
        jb += len(json.dumps(to_wire(ev)).encode()) + 1   # + newline
        bb += len(binwire.frame(binwire.encode(ev)))
    return jb, bb


def _drift_steady_state(nodes: int = 16, pods: int = 32) -> dict:
    """Mini drift-sentinel check: after the first (full) pass, a
    steady-state pass must issue ZERO cluster LISTs — the incremental
    comparer reads only the journal suffix."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing import CountingHub, MakeNode, MakePod

    hub = Hub()
    counting = CountingHub(hub)
    for i in range(nodes):
        hub.create_node(MakeNode().name(f"dn-{i}").capacity(
            cpu="16").obj())
    sched = Scheduler(counting, default_config(),
                      caps=Capacities(nodes=max(32, nodes * 2),
                                      pods=max(128, pods * 2)))
    try:
        for i in range(pods):
            hub.create_pod(MakePod().name(f"dp-{i}").req(
                cpu="100m").obj())
        sched.run_until_idle()
        sched.drift_check_interval = 1e-9
        sched._last_drift_check = 0.0
        sched._run_drift_sentinel()             # first pass: full diff
        first_lists = counting.lists
        # steady state: nothing changed — the sentinel must not LIST
        counting.lists = 0
        sched._last_drift_check = 0.0
        sched._run_drift_sentinel()
        steady_lists = counting.lists
        # ...and a small change costs O(changes), still zero LISTs
        hub.create_pod(MakePod().name("dp-late").req(cpu="100m").obj())
        sched.run_until_idle()
        counting.lists = 0
        sched._last_drift_check = 0.0
        sched._run_drift_sentinel()
        changed_lists = counting.lists
        return {"first_pass_lists": first_lists,
                "steady_lists": steady_lists,
                "changed_lists": changed_lists,
                "incremental_passes": sched.stats["drift_incremental"],
                "ok": steady_lists == 0 and changed_lists == 0
                and first_lists > 0}
    finally:
        sched.close()
        hub.close()


def _e2e_traced_pipeline(hub, relay_url: str, server_address: str,
                         l1_servers, nodes: int = 16, pods: int = 48,
                         timeout_s: float = 90.0) -> dict:
    """The end-to-end SLO phase (ISSUE-10): a scheduler against the
    hub, hollow kubelets whose pod WATCHES ride the relay tree, and a
    per-pod joined timeline — hub commit (created) -> relay hop
    (kubelet_recv carries the hop count) -> scheduler cycle -> bind
    commit (bound) -> kubelet ack commit (acked). Gates: every pod
    binds, >= 99% of bound pods have a COMPLETE joined trace including
    the relay leg, and the run reports a created->acked p99.

    Also scrapes the fleet while every component is alive: FleetView
    over the hub server, each L1 relay, and the kubemark feeder — all
    healthy, and the merged exposition re-parses strictly."""
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hubclient import RemoteHub
    from kubernetes_tpu.kubemark import HollowNodes
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.telemetry.fleet import FleetView
    from kubernetes_tpu.telemetry.trace import latency_summary
    from kubernetes_tpu.testing import MakePod

    prof_name = "e2e-sched"      # leave the storm's fan/churn pods alone
    cfg = default_config()
    cfg.profiles[0].scheduler_name = prof_name
    watch_client = RemoteHub(relay_url, timeout=10.0)
    hollow = HollowNodes(hub, nodes, prefix="e2e", cpu="32",
                         watch_hub=watch_client)
    sched = Scheduler(hub, cfg,
                      caps=Capacities(nodes=64, pods=256))
    created: list[str] = []
    try:
        for i in range(pods):
            p = MakePod().name(f"e2e-{i}").namespace("e2e") \
                .scheduler_name(prof_name).req(cpu="100m").obj()
            hub.create_pod(p)
            created.append(p.metadata.uid)

        def complete() -> int:
            return sum(1 for uid in created
                       if sched.timelines.joined(uid) is not None)

        deadline = time.monotonic() + timeout_s
        while complete() < pods and time.monotonic() < deadline:
            sched.run_until_idle()
            time.sleep(0.05)
        joins = [j for j in (sched.timelines.joined(uid)
                             for uid in created) if j is not None]
        bound = sum(1 for uid in created
                    if (hub.get_pod(uid) is not None
                        and hub.get_pod(uid).spec.node_name))
        with_relay_leg = sum(1 for j in joins
                             if "bind_to_kubelet_s" in j)
        lat = latency_summary([j["create_to_ack_s"] for j in joins])
        out = {
            "pods": pods, "bound": bound,
            "joinable": len(joins),
            "joinable_frac": round(len(joins) / max(bound, 1), 4),
            "relay_leg_frac": round(with_relay_leg / max(bound, 1), 4),
            "relay_hops_max": max((j["relay_hops"] for j in joins),
                                  default=0),
            "created_to_acked": lat,
            "ok": (bound == pods
                   and len(joins) >= 0.99 * bound
                   and with_relay_leg >= 0.99 * bound
                   and lat.get("p99_s") is not None),
        }

        # fleet aggregation, scraped while everything is alive
        feeder_ep = hollow.serve_metrics()
        endpoints = [{"component": "hub", "shard": "hub",
                      "url": server_address}]
        endpoints += [{"component": "relay", "shard": f"l1-{i}",
                       "url": s.address}
                      for i, s in enumerate(l1_servers)]
        endpoints.append({"component": "kubemark", "shard": "feeder",
                          "url": feeder_ep.address})
        fleet = FleetView(endpoints)
        records = fleet.scrape()        # ONE round of HTTP round-trips
        summary = fleet.summary(records)
        merged = fleet.render_text(records)
        from kubernetes_tpu.telemetry.fleet import parse_exposition

        merged_exp = parse_exposition(merged)   # strict: raises on rot
        labeled = all("component" in s.labels
                      for s in merged_exp.samples)
        out["fleet"] = {
            "endpoints": summary["total"],
            "healthy": summary["healthy"],
            "merged_samples": len(merged_exp.samples),
            "ok": summary["ok"] and labeled
            and len(merged_exp.samples) > 0,
        }
        return out
    finally:
        sched.close()
        hollow.stop()
        watch_client.close()


def run_fanout_smoke(subscribers: int = 10000, l1_count: int = 2,
                     l2_count: int = 8, pods: int = 120,
                     churn: int = 60, cuts: int = 10,
                     resub: int = 500, seed: int = 23,
                     timeout_s: float = 240.0) -> dict:
    """The storm. Returns the invariant report; ``ok`` is True iff
    every gate above held."""
    from kubernetes_tpu.chaos import ChaosConfig, ChaosProxy
    from kubernetes_tpu.fabric.relay import RelayServer
    from kubernetes_tpu.hubserver import HubServer
    from kubernetes_tpu.testing import MakePod

    report: dict = {"subscribers": subscribers, "l1": l1_count,
                    "l2": l2_count, "pods": pods, "cuts": cuts,
                    "seed": seed}
    hub = Hub(journal_capacity=65536)
    server = HubServer(hub).start()
    proxy = ChaosProxy(server.address,
                       config=ChaosConfig(seed=seed)).start()
    l1_servers: list[RelayServer] = []
    l2_cores: list[RelayCore] = []
    try:
        # the tree: hub <- proxy <- L1 relays <- L2 relays <- subscribers
        for _ in range(l1_count):
            core = RelayCore(proxy.address, kinds=("pods",),
                             ring_capacity=65536, timeout=10.0)
            l1_servers.append(RelayServer(core).start())
        for i in range(l2_count):
            l2_cores.append(RelayCore(
                l1_servers[i % l1_count].address, kinds=("pods",),
                ring_capacity=65536, timeout=10.0))
        subs = [l2_cores[i % l2_count].subscribe(
                    ("pods",), queue_limit=1_000_000)
                for i in range(subscribers)]
        resubbed: set[int] = set()

        # ---- phase 1: pod storm ----
        t0 = time.monotonic()
        for i in range(pods):
            hub.create_pod(MakePod().name(f"fan-{i}")
                           .namespace(f"ns-{i % 7}")
                           .req(cpu="100m").obj())

        def l1_stats(key: str) -> int:
            return sum(s.core.client.resilience_stats()[key]
                       for s in l1_servers)

        # ---- phase 2: watch-cut storm on the L1 upstream streams ----
        # every cut must heal by journal RESUME (since_rv), never by a
        # relist; churn pods keep events flowing so cuts trigger
        base_resumes = l1_stats("watch_resumes")
        base_relists = l1_stats("watch_relists")
        proxy.set_fault(watch_cut_every=3)
        ci = 0
        deadline = time.monotonic() + timeout_s / 2
        while l1_stats("watch_resumes") - base_resumes < cuts \
                and time.monotonic() < deadline:
            p = MakePod().name(f"churn-{ci}").namespace("churn") \
                .req(cpu="50m").obj()
            hub.create_pod(p)
            if ci >= 1 and ci % 2 == 0:
                # deletes too: the resume path must carry tombstones
                doomed = [x for x in hub.list_pods()
                          if x.metadata.namespace == "churn"]
                if doomed:
                    try:
                        hub.delete_pod(doomed[0].metadata.uid)
                    except Exception:  # noqa: BLE001 — already gone
                        pass
            ci += 1
            if ci > churn:
                time.sleep(0.2)
            else:
                time.sleep(0.05)
        proxy.set_fault(watch_cut_every=0)
        proxy.heal()
        report["upstream_resumes"] = l1_stats("watch_resumes") \
            - base_resumes
        report["upstream_relists"] = l1_stats("watch_relists") \
            - base_relists

        # ---- phase 3: mid-storm downstream reconnect wave ----
        # every reconnect resumes off a relay RING; the hub never sees
        # one of these
        ring_410 = 0
        for i in range(0, min(resub, subscribers)):
            idx = (i * 37) % subscribers     # deterministic spread
            if idx in resubbed:
                continue
            core = l2_cores[idx % l2_count]
            old = subs[idx]
            core.unsubscribe(old)
            try:
                subs[idx] = core.subscribe(("pods",),
                                           since_rv=old.cursor,
                                           queue_limit=1_000_000)
            except Exception:  # noqa: BLE001 — RvTooOld = ring moved
                ring_410 += 1
                subs[idx] = core.subscribe(("pods",),
                                           queue_limit=1_000_000)
            resubbed.add(idx)
        resume_serves = sum(c.resume_serves for c in l2_cores)
        report["resub_wave"] = len(resubbed)
        report["resub_ring_410s"] = ring_410
        report["relay_resume_serves"] = resume_serves

        # ---- phase 4: convergence ----
        pod_events = [c for c in hub.list_changes(0, ("pods",))
                      .get("changes", [])]
        target_rv = max((c["rv"] for c in pod_events), default=0)
        expected = len(pod_events)
        deadline = time.monotonic() + timeout_s / 2
        lagging = subscribers
        while time.monotonic() < deadline:
            lagging = sum(1 for s in subs
                          if s.cursor < target_rv and not s.evicted)
            if lagging == 0:
                break
            time.sleep(0.25)
        report["lagging_subscribers"] = lagging
        report["target_rv"] = target_rv
        report["pod_events"] = expected
        # exact-count check on the never-reconnected subscribers: a
        # relay tree that drops or duplicates would show here
        drained = [s.drain() for i, s in enumerate(subs)
                   if i not in resubbed]
        counts = [len(evs) for evs in drained]
        report["event_count_min"] = min(counts)
        report["event_count_max"] = max(counts)
        exact = min(counts) == max(counts) == expected
        # trace propagation: every live event reaching an L2 subscriber
        # crossed exactly two relay hops, stamp intact (chaos proxy on
        # the upstream leg strips the CODEC, never the in-body trace)
        total_evs = traced = 0
        for evs in drained:
            for d in evs:
                total_evs += 1
                tr = d.get("trace")
                if tr is not None and tr.hops == 2 \
                        and tr.origin == "hub" and tr.ts > 0:
                    traced += 1
        report["events_traced_frac"] = round(
            traced / max(total_evs, 1), 4)
        report["fanout_elapsed_s"] = round(time.monotonic() - t0, 2)

        # ---- phase 5: slow-subscriber eviction ----
        evictions_before = sum(c.slow_evictions for c in l2_cores)
        slow = l2_cores[0].subscribe(("pods",), queue_limit=4)
        for i in range(8):
            hub.create_pod(MakePod().name(f"evict-{i}")
                           .namespace("evict").req(cpu="50m").obj())
        deadline = time.monotonic() + 20.0
        while not slow.evicted and time.monotonic() < deadline:
            time.sleep(0.1)
        report["slow_evicted"] = slow.evicted
        report["slow_evictions_total"] = \
            sum(c.slow_evictions for c in l2_cores) - evictions_before
        # the evicted consumer reconnects and resumes where it stood
        recovered = l2_cores[0].subscribe(("pods",),
                                          since_rv=slow.cursor,
                                          queue_limit=1_000_000)
        final_rv = hub.current_rv
        deadline = time.monotonic() + 20.0
        while recovered.cursor < final_rv \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        report["evicted_recovered"] = recovered.cursor >= final_rv

        # ---- phase 6: upstream socket accounting ----
        # the hub's pod store must hold ≤ one watch registration per L1
        # relay (cut streams unregister within a keepalive)
        deadline = time.monotonic() + 15.0
        while len(hub._pods.handlers) > l1_count \
                and time.monotonic() < deadline:
            time.sleep(0.5)
        report["hub_pod_watchers"] = len(hub._pods.handlers)

        # ---- phase 7: wire bytes, same storm both codecs ----
        wire_events = [{"type": c["type"], "rv": c["rv"],
                        "old": None if c["type"] != "delete"
                        else c["obj"],
                        "new": None if c["type"] == "delete"
                        else c["obj"]}
                       for c in pod_events]
        jb, bb = _wire_bytes(wire_events)
        report["wire_bytes_json"] = jb
        report["wire_bytes_bin1"] = bb
        report["wire_ratio"] = round(jb / max(bb, 1), 2)

        # ---- phase 8: drift sentinel steady state ----
        report["drift"] = _drift_steady_state()

        # ---- phase 9: e2e joined-trace SLO + fleet aggregation ----
        # scheduler + hollow kubelets (watching through the relay tree)
        # over the SAME storm-worn fabric: >= 99% of bound pods must
        # join a complete created -> bound -> acked trace with the
        # relay leg measured, and every component's /metrics + /healthz
        # must merge into one healthy fleet exposition
        report["e2e"] = _e2e_traced_pipeline(
            hub, l1_servers[0].address, server.address, l1_servers)

        report["ok"] = bool(
            report["upstream_resumes"] >= cuts
            and report["upstream_relists"] == 0
            and lagging == 0
            and exact
            and report["events_traced_frac"] >= 0.99
            and report["resub_ring_410s"] == 0
            and report["relay_resume_serves"] >= len(resubbed)
            and report["slow_evicted"]
            and report["slow_evictions_total"] >= 1
            and report["evicted_recovered"]
            and report["hub_pod_watchers"] <= l1_count
            and report["wire_ratio"] >= 3.0
            and report["drift"]["ok"]
            and report["e2e"]["ok"]
            and report["e2e"]["fleet"]["ok"])
    finally:
        for c in l2_cores:
            c.close()
        for s in l1_servers:
            s.stop()
        proxy.stop()
        server.stop()
        hub.close()
    return report


def _wal_bytes(events: list[dict]) -> tuple[int, int]:
    """(json_bytes, bin1_bytes) for the same WAL record stream — the
    replay-size ratio the bin1 journal WAL buys, measured on the
    storm's own events."""
    from kubernetes_tpu.storage import Journal, JournalEvent

    jb = bb = 0
    for ev in events:
        rec = Journal._event_record(JournalEvent(
            rv=ev["rv"], kind="pods", type=ev["type"],
            old=ev.get("old"), new=ev.get("new")))
        jb += len(Journal._json_record(rec).encode()) + 1
        bb += len(binwire.frame(binwire.encode(rec)))
    return jb, bb


def run_fanout_smoke_procs(subscribers: int = 50000, l1_count: int = 2,
                           l2_count: int = 4, pods: int = 80,
                           churn: int = 40, cuts: int = 10,
                           resub: int = 300, seed: int = 23,
                           pod_shards: int = 2,
                           timeout_s: float = 360.0) -> dict:
    """The PROCESS-MODE storm (ISSUE 11): shards as separate OS
    processes behind the stateless router, relays discovered through
    the served topology map (no flags), hollow-kubelet-analog
    subscribers hanging off the auto-discovered tree. On top of the
    in-process smoke's gates, this one must survive

    * a watch-cut storm against the L1 relays' upstream streams
      (healed by composite-cursor RESUME — 0 relists),
    * one ``kill -9``'d pod-shard process mid-storm, restarted by the
      supervisor with bin1-WAL replay onto a new port,
    * one LIVE ring rebalance mid-storm (event-silent, resume points
      intact),
    * one ``kill -9``'d **state-core LEADER** mid-storm (the shared
      rv/fencing/ring quorum — ISSUE 13): a new leader is elected,
      commits stall briefly and resume, the killed replica rejoins
      from its WAL, and the stream invariants below still hold,

    with exact per-subscriber event counts, ≤ l1_count router sockets
    per shard process, and a FleetView scrape showing every process
    (incl. all three state replicas, exactly one of them leading)
    healthy under its own pid/port identity."""
    import tempfile

    from kubernetes_tpu.fabric.cluster import RING_SLOTS, ring_slot
    from kubernetes_tpu.fabric.relay import (
        RelayCore,
        RelayServer,
        discover_relay_url,
    )
    from kubernetes_tpu.fabric.router import fetch_topology
    from kubernetes_tpu.fabric.supervisor import spawn_local_cluster
    from kubernetes_tpu.hub import Unavailable
    from kubernetes_tpu.hubclient import RemoteHub
    from kubernetes_tpu.telemetry.fleet import FleetView
    from kubernetes_tpu.testing import MakePod

    # the exact-count gate needs untouched subscribers left over after
    # the reconnect wave
    resub = min(resub, subscribers // 3)
    report: dict = {"procs": True, "subscribers": subscribers,
                    "l1": l1_count, "l2": l2_count, "pods": pods,
                    "cuts": cuts, "seed": seed,
                    "pod_shards": pod_shards, "state_replicas": 3}
    wal_dir = tempfile.mkdtemp(prefix="fabric-smoke-wal-")
    cluster = spawn_local_cluster(pod_shards=pod_shards,
                                  wal_dir=wal_dir, state_replicas=3)
    client = RemoteHub(cluster.router_url, timeout=10.0)
    l1_servers: list[RelayServer] = []
    l2_cores: list[RelayCore] = []

    def create_retry(pod, deadline_s: float = 30.0) -> None:
        # the kill -9 window: writes to the dead shard's segment fail
        # Unavailable until the supervisor restart re-registers it
        end = time.monotonic() + deadline_s
        while True:
            try:
                client.create_pod(pod)
                return
            except Unavailable:
                if time.monotonic() > end:
                    raise
                time.sleep(0.2)

    try:
        # ---- the tree, discovered not configured ----
        for i in range(l1_count):
            core = RelayCore(cluster.router_url, kinds=("pods",),
                             ring_capacity=65536, timeout=10.0)
            l1_servers.append(RelayServer(
                core, advertise={"state_url": cluster.router_url,
                                 "name": f"l1-{i}",
                                 "parent": cluster.router_url,
                                 "interval_s": 0.5}).start())
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            topo = fetch_topology(cluster.router_url)
            if len(topo.get("relays", [])) >= l1_count:
                break
            time.sleep(0.2)
        report["advertised_relays"] = len(topo.get("relays", []))
        for i in range(l2_count):
            # each L2 discovers its parent from the served map
            url = discover_relay_url(cluster.router_url, seed=i)
            l2_cores.append(RelayCore(url, kinds=("pods",),
                                      ring_capacity=65536,
                                      timeout=10.0))
        subs = [l2_cores[i % l2_count].subscribe(
                    ("pods",), queue_limit=2_000_000)
                for i in range(subscribers)]
        resubbed: set[int] = set()

        def l1_stats(key: str) -> int:
            return sum(s.core.client.resilience_stats()[key]
                       for s in l1_servers)

        # ---- phase 1: pod storm across shards ----
        t0 = time.monotonic()
        for i in range(pods):
            create_retry(MakePod().name(f"fan-{i}")
                         .namespace(f"ns-{i % 7}")
                         .req(cpu="100m").obj())

        # ---- phase 2: watch-cut storm on the L1 upstream streams ----
        base_resumes = l1_stats("watch_resumes")
        base_relists = l1_stats("watch_relists")
        ci = 0
        deadline = time.monotonic() + timeout_s / 3
        while l1_stats("watch_resumes") - base_resumes < cuts \
                and time.monotonic() < deadline:
            if ci % 2 == 0:
                # cut a relay's upstream socket (no proxy in the
                # process fabric: the cut IS the failure mode)
                victim = l1_servers[ci % l1_count].core.client
                with victim._wlock:
                    handles = list(victim._watchers)
                for h in handles:
                    try:
                        h.close()
                    except OSError:
                        pass
            create_retry(MakePod().name(f"churn-{ci}")
                         .namespace("churn").req(cpu="50m").obj())
            if ci >= 1 and ci % 2 == 0:
                doomed = [x for x in client.list_pods()
                          if x.metadata.namespace == "churn"]
                if doomed:
                    try:
                        client.delete_pod(doomed[0].metadata.uid)
                    except Exception:  # noqa: BLE001 — already gone
                        pass
            ci += 1
            time.sleep(0.05 if ci <= churn else 0.2)
        report["upstream_resumes"] = l1_stats("watch_resumes") \
            - base_resumes
        report["upstream_relists"] = l1_stats("watch_relists") \
            - base_relists

        # ---- phase 3: kill -9 a shard process mid-storm ----
        victim_shard = cluster.pod_shards[0]
        ring_now = client.fabric_ring()
        live_ns = [f"ns-{i}" for i in range(7)
                   if ring_now["slots"][ring_slot(
                       f"ns-{i}", len(ring_now["slots"]))]
                   != victim_shard]
        report["killed_pid"] = cluster.sup.kill_shard(victim_shard)
        # keep committing: the live shard keeps flowing while the dead
        # one's segment waits out the restart
        for i in range(6):
            create_retry(MakePod().name(f"during-kill-{i}")
                         .namespace(live_ns[i % len(live_ns)])
                         .req(cpu="50m").obj())
        restarted = cluster.sup.restart_shard(victim_shard)
        report["restarted_port"] = restarted.port
        for i in range(6):
            create_retry(MakePod().name(f"after-kill-{i}")
                         .namespace(f"ns-{i % 7}").req(cpu="50m").obj())

        # ---- phase 4: LIVE ring rebalance mid-storm ----
        ring = client.fabric_ring()
        slot = ring_slot("ns-0", len(ring["slots"]) or RING_SLOTS)
        src = ring["slots"][slot]
        dst = next(n for n in cluster.pod_shards if n != src)
        report["rebalance"] = client.rebalance_segment([slot], dst)
        for i in range(4):
            create_retry(MakePod().name(f"post-move-{i}")
                         .namespace("ns-0").req(cpu="50m").obj())

        # ---- phase 4b: kill -9 the state-core LEADER mid-storm ----
        # rv allocation, fencing, and the ring live on the quorum: the
        # kill costs a brief write stall (redirect-retried), never a
        # relist, never a lost or duplicated event downstream
        state_leader = cluster.state_leader()
        report["state_leader_killed"] = state_leader
        report["state_leader_pid"] = cluster.sup.kill_shard(state_leader)
        for i in range(6):
            create_retry(MakePod().name(f"during-state-kill-{i}")
                         .namespace(f"ns-{i % 7}").req(cpu="50m").obj())
        report["state_new_leader"] = cluster.state_leader(timeout_s=30.0)
        restarted_state = cluster.sup.restart_shard(state_leader)
        report["state_restarted_port"] = restarted_state.port
        for i in range(4):
            create_retry(MakePod().name(f"after-state-kill-{i}")
                         .namespace(f"ns-{i % 7}").req(cpu="50m").obj())

        # ---- phase 5: mid-storm downstream reconnect wave ----
        # composite-cursor resumes off the relay rings: zero 410s even
        # across the kill and the rebalance
        ring_410 = 0
        for i in range(0, min(resub, subscribers)):
            idx = (i * 37) % subscribers
            if idx in resubbed:
                continue
            core = l2_cores[idx % l2_count]
            old = subs[idx]
            core.unsubscribe(old)
            try:
                subs[idx] = core.subscribe(
                    ("pods",), since_rv=old.cursor,
                    cursors={k: v for k, v in old.cursors.items()
                             if k},
                    queue_limit=2_000_000)
            except Exception:  # noqa: BLE001 — RvTooOld = ring moved
                ring_410 += 1
                subs[idx] = core.subscribe(("pods",),
                                           queue_limit=2_000_000)
            resubbed.add(idx)
        report["resub_wave"] = len(resubbed)
        report["resub_ring_410s"] = ring_410
        report["relay_resume_serves"] = sum(c.resume_serves
                                            for c in l2_cores)

        # ---- phase 6: convergence + exact per-subscriber counts ----
        changes = client.list_changes(0, ("pods",)).get("changes", [])
        expected = len(changes)
        stats = client.get_journal_stats()
        target_curs = {name: st.get("rv", 0)
                       for name, st in stats["shards"].items()
                       if name in cluster.pod_shards}

        def lagging_count() -> int:
            n = 0
            for s in subs:
                if s.evicted:
                    continue
                for shard, rv in target_curs.items():
                    if s.cursors.get(shard, 0) < rv:
                        n += 1
                        break
            return n

        deadline = time.monotonic() + timeout_s / 3
        lagging = subscribers
        while time.monotonic() < deadline:
            lagging = lagging_count()
            if lagging == 0:
                break
            time.sleep(0.25)
        report["lagging_subscribers"] = lagging
        report["pod_events"] = expected
        drained = [s.drain() for i, s in enumerate(subs)
                   if i not in resubbed]
        counts = [len(evs) for evs in drained]
        report["event_count_min"] = min(counts)
        report["event_count_max"] = max(counts)
        exact = min(counts) == max(counts) == expected
        shards_seen = {d.get("sh") for evs in drained[:50]
                       for d in evs}
        report["shards_seen"] = sorted(s for s in shards_seen if s)

        # ---- phase 7: slow-subscriber eviction + recovery ----
        evict_before = sum(c.slow_evictions for c in l2_cores)
        slow = l2_cores[0].subscribe(("pods",), queue_limit=4)
        for i in range(8):
            create_retry(MakePod().name(f"evict-{i}")
                         .namespace("evict").req(cpu="50m").obj())
        deadline = time.monotonic() + 20.0
        while not slow.evicted and time.monotonic() < deadline:
            time.sleep(0.1)
        report["slow_evicted"] = slow.evicted
        report["slow_evictions_total"] = \
            sum(c.slow_evictions for c in l2_cores) - evict_before
        recovered = l2_cores[0].subscribe(
            ("pods",), since_rv=slow.cursor,
            cursors={k: v for k, v in slow.cursors.items() if k},
            queue_limit=2_000_000)
        final_curs = {name: st.get("rv", 0) for name, st in
                      client.get_journal_stats()["shards"].items()
                      if name in cluster.pod_shards}
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if all(recovered.cursors.get(s, 0) >= rv
                   for s, rv in final_curs.items()):
                break
            time.sleep(0.1)
        report["evicted_recovered"] = all(
            recovered.cursors.get(s, 0) >= rv
            for s, rv in final_curs.items())

        # ---- phase 8: per-shard-process socket accounting ----
        # each shard process must hold ≤ l1_count pod watch streams —
        # the router's pass-through conns, one per L1 relay, however
        # many subscribers hang downstream
        shard_watchers = {}
        for name, rec in client.fabric_shards().items():
            if name not in cluster.pod_shards:
                continue
            sc = RemoteHub(rec["url"], timeout=5.0)
            try:
                st = sc.get_journal_stats()
                shard_watchers[name] = st.get("watchers", {}) \
                    .get("pods", 0)
            finally:
                sc.close()
        report["shard_pod_watchers"] = shard_watchers
        sockets_ok = all(v <= l1_count
                         for v in shard_watchers.values())

        # ---- phase 9: WAL replay-size ratio (bin1 vs JSON lines) ----
        wire_events = [{"rv": c["rv"], "type": c["type"],
                        "old": c["obj"] if c["type"] == "delete"
                        else None,
                        "new": None if c["type"] == "delete"
                        else c["obj"]}
                       for c in changes]
        jb, bb = _wal_bytes(wire_events)
        report["wal_bytes_json"] = jb
        report["wal_bytes_bin1"] = bb
        report["wal_replay_ratio"] = round(jb / max(bb, 1), 2)

        # ---- phase 10: fleet health with per-process identity ----
        # every state REPLICA is its own endpoint: followers answer
        # 200-with-role (healthy, not degraded) and the summary rows
        # carry who leads
        endpoints = [{"component": "state", "shard": f"state-{i}",
                      "url": u}
                     for i, u in enumerate(cluster.state_urls)]
        endpoints += [{"component": "router", "shard": "router-0",
                       "url": cluster.router_url}]
        endpoints += [{"component": "shard", "shard": name,
                       "url": rec["url"]}
                      for name, rec in
                      client.fabric_shards().items()]
        endpoints += [{"component": "relay", "shard": f"l1-{i}",
                       "url": s.address}
                      for i, s in enumerate(l1_servers)]
        fleet = FleetView(endpoints)
        records = fleet.scrape()
        summary = fleet.summary(records)
        pids = [r.get("pid") for r in summary["endpoints"]
                if r["component"] in ("state", "shard", "router")]
        state_roles = [r.get("role") for r in summary["endpoints"]
                       if r["component"] == "state"]
        report["fleet"] = {
            "endpoints": summary["total"],
            "healthy": summary["healthy"],
            "pids_distinct": len(set(pids)) == len(pids)
            and all(pids),
            "state_roles": state_roles,
            "ok": summary["ok"]
            and state_roles.count("leader") == 1,
        }
        report["fanout_elapsed_s"] = round(time.monotonic() - t0, 2)

        report["ok"] = bool(
            report["upstream_resumes"] >= cuts
            and report["upstream_relists"] == 0
            and lagging == 0
            and exact
            and report["resub_ring_410s"] == 0
            and report["relay_resume_serves"] >= len(resubbed)
            and report["slow_evicted"]
            and report["evicted_recovered"]
            and sockets_ok
            and len(report["shards_seen"]) >= 2
            and report["wal_replay_ratio"] >= 3.0
            and report["fleet"]["ok"]
            and report["fleet"]["pids_distinct"])
    finally:
        for c in l2_cores:
            c.close()
        for s in l1_servers:
            s.stop()
        client.close()
        cluster.stop()
    return report


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="relay-tree fan-out smoke")
    ap.add_argument("--subscribers", type=int, default=10000)
    ap.add_argument("--smoke", action="store_true",
                    help="small/fast variant (1k subscribers)")
    ap.add_argument("--procs", action="store_true",
                    help="process-mode variant: shard processes + "
                         "stateless router + auto-discovered relays "
                         "(50k subscribers unless --subscribers/"
                         "--smoke)")
    ap.add_argument("--seed", type=int, default=23)
    args = ap.parse_args()
    if args.procs:
        n = 1000 if args.smoke else (
            args.subscribers if args.subscribers != 10000 else 50000)
        r = run_fanout_smoke_procs(subscribers=n, seed=args.seed)
    else:
        n = 1000 if args.smoke else args.subscribers
        r = run_fanout_smoke(subscribers=n, seed=args.seed)
    print(json.dumps(r))
    raise SystemExit(0 if r["ok"] else 1)


if __name__ == "__main__":
    main()
