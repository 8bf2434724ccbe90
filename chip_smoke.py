#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the scheduler still starts on the chip.

Drives the PRODUCTION drain loop (hub -> jobqueue -> queue -> mirror pack ->
fused launch -> off-thread pull -> commit -> binder -> hub) on one TPU chip at
5,000 nodes, through the entry points a user would call, and checks what came
out. It claims no speed: any pods/s it prints is labelled "not a benchmark".

One process owns the chip at a time, so the parent stays OFF JAX and runs its
legs as sequential child processes:

  probe  a child that asserts jax.default_backend() == "tpu" and prints the
         device and the jax/jaxlib/libtpu versions. No TPU -> exit 2, no result.
  A      `python -m kubernetes_tpu.perf.run_one scheduling_basic --scale 1.0`
         twice, in two fresh processes: every pod bound, no device fallback,
         zero measured compiles; the second process must hit the persistent
         compile cache on every launch program and warm faster than the first.
  B      one process, production Scheduler + in-process Hub, every device
         program: node-affinity auction + foreign-pod churn (patch_chain),
         required spread + hostname anti-affinity (the serial commit scan),
         soft-only preferred terms (the soft-topology auction), a saturated
         labelled pool + high-priority pods (the preemption sweep), one
         PodGroup storm wave (pack_gangs_jit) and one claim-template batch
         (ops/dra.batch_feasible). The end state is checked by a plain host
         check written in this file, independent of ops/.
  C      the served daemon: the parent hosts Hub + HubServer with 5,000
         nodes, spawns `python -m kubernetes_tpu --hub URL --secure-port P`
         (default Capacities, so the 1024 -> 8192 re-bucket happens on the
         chip), creates pods through RemoteHub, waits for every bind, scrapes
         /metrics, then SIGTERM must exit 0.
  D      only with >= 4 devices: the production scenario under
         node_mesh(jax.devices()[:4]) must place exactly like one device.

`--rehearse` runs the same legs at tiny sizes on the CPU for the tests; the
result is then labelled "rehearsal": true. The driver never passes it.

Stdout is two lines. The first is the summary, one JSON object: per-leg ok,
cold and warm compile seconds, cache hits and misses, launch, compile and
fallback counters, versions, the native engine, "claim": null (also written to
chiprun_out/chip_smoke/summary.json). The LAST line is the verdict, one JSON
object with exactly these keys, the device as JAX reports it:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Exit code 0 only if every leg passed. Leg logs land in chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# the whole run, compilation included, must end inside this many seconds
TIME_LIMIT_S = 1200.0
# what the parent keeps back for its own teardown and the final print
TIME_RESERVE_S = 45.0

# The deployment's sizes, not a toy's: 5,000 nodes (4 cpu / 32Gi / 110 pods,
# perf/workloads.py:_node) over 8 zones, node bucket 8192, the bench's batch
# widths (4096 auction, 2048 scan), pod table 16384; gang and DRA at their
# bench shapes (128-node bucket). A cold run that cannot finish in time cuts
# PODS here — never nodes or batch width — and lists the cut in "reduced".
FULL = dict(
    nodes=5000, zones=8, node_cap=8192, pod_cap=16384,
    auction_batch=4096, scan_batch=2048, basic_scale=1.0,
    na_wave=4096, na_tail=512, foreign=32,
    topo_pods=3000, soft_pods=4096,
    pool_nodes=64, preemptors=8,
    gang_nodes=96, gang_cap=128, gangs=8, gang_size=48, gang_batch=512,
    dra_nodes=100, dra_cap=128, dra_pods=200, dra_batch=256,
    small_pod_cap=2048,
    daemon_pods=2048, drain_s=300.0,
    multi_plain=2048, multi_anti=256, multi_spread=256, multi_gold=16,
    multi_high=1, multi_batch=1024,
)
REHEARSAL = dict(
    nodes=64, zones=8, node_cap=64, pod_cap=512,
    auction_batch=32, scan_batch=32, basic_scale=0.01,
    na_wave=32, na_tail=16, foreign=4,
    topo_pods=48, soft_pods=64,
    pool_nodes=4, preemptors=2,
    gang_nodes=16, gang_cap=16, gangs=8, gang_size=8, gang_batch=64,
    dra_nodes=8, dra_cap=8, dra_pods=16, dra_batch=32, small_pod_cap=128,
    daemon_pods=64, drain_s=60.0,
    multi_plain=16, multi_anti=8, multi_spread=8, multi_gold=2,
    multi_high=1, multi_batch=16,
)
# pod counts of the warm (compile) pass of each Leg B scene: same nodes,
# same capacities and batch widths — therefore the same programs
WARM = dict(na_wave=16, na_tail=8, foreign=2, topo_pods=16, soft_pods=16,
            pool_nodes=2, preemptors=1, gangs=1, dra_pods=8)
NA_ZONES = 4                # node-affinity pods may land in zones 0..3
SPREAD_SKEW = 1
POOL_LABEL = ("pool", "gold")


class SmokeFailure(Exception):
    """A leg's check failed."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ objects
# kubernetes_tpu.testing's fluent builders import no JAX, so the parent may
# use them too. Node shape: perf/workloads.py:_node (4 cpu / 32Gi / 110).


def make_node(i: int, zones: int, pool: bool = False):
    from kubernetes_tpu.api.objects import LABEL_ZONE
    from kubernetes_tpu.testing import MakeNode

    node = MakeNode().name(f"node-{i}").capacity(
        cpu="4", memory="32Gi", pods="110").label(
        LABEL_ZONE, f"zone-{i % zones}")
    if pool:
        node.label(*POOL_LABEL)
    return node.obj()


def pod(name: str, cpu: str = "100m", mem: str = "500Mi"):
    """A MakePod builder with the bench's default requests; callers chain
    constraints and finish with .obj()."""
    from kubernetes_tpu.testing import MakePod

    return MakePod().name(name).req(cpu=cpu, memory=mem)


# ------------------------------------------------------------ host check
# A straightforward check of the END STATE, written here and independent of
# ops/: integer arithmetic on the objects the hub holds.

_SUFFIX = {"Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30}


def _milli(q: str) -> int:
    return int(q[:-1]) if q.endswith("m") else int(q) * 1000


def _bytes(q: str) -> int:
    for suf, mul in _SUFFIX.items():
        if q.endswith(suf):
            return int(q[:-2]) * mul
    return int(q)


def host_check(hub, *, anti_label: tuple | None = None,
               spread_label: tuple | None = None, zones: int = 0,
               na_prefix: str = "", pool_prefix: str = "") -> dict:
    """Per node: summed requests <= allocatable, pods <= allocatable pods.
    Optionally: at most one ``anti_label`` pod per node (required hostname
    anti-affinity); zone counts of ``spread_label`` pods within
    SPREAD_SKEW (required zone spread); ``na_prefix`` pods inside their
    allowed zones; ``pool_prefix`` pods on a node of their pool."""
    from kubernetes_tpu.api.objects import LABEL_ZONE

    nodes = {n.metadata.name: n for n in hub.list_nodes()}
    used: dict[str, list[int]] = {name: [0, 0, 0] for name in nodes}
    anti: dict[str, int] = {}
    zone_count = {f"zone-{z}": 0 for z in range(zones)}
    errors: list[str] = []
    bound = 0
    for p in hub.list_pods():
        node = p.spec.node_name
        if not node:
            continue
        bound += 1
        if node not in nodes:
            errors.append(f"{p.metadata.name} bound to unknown node {node}")
            continue
        u = used[node]
        for c in p.spec.containers:
            req = c.resources.requests
            u[0] += _milli(req.get("cpu", "0"))
            u[1] += _bytes(req.get("memory", "0"))
        u[2] += 1
        labels = p.metadata.labels
        node_labels = nodes[node].metadata.labels
        if anti_label and labels.get(anti_label[0]) == anti_label[1]:
            anti[node] = anti.get(node, 0) + 1
        if spread_label and labels.get(spread_label[0]) == spread_label[1]:
            zone_count[node_labels[LABEL_ZONE]] += 1
        if na_prefix and p.metadata.name.startswith(na_prefix):
            z = int(node_labels[LABEL_ZONE].rsplit("-", 1)[1])
            if z >= NA_ZONES:
                errors.append(f"{p.metadata.name} in {node_labels[LABEL_ZONE]}"
                              " outside its node affinity")
        if pool_prefix and p.metadata.name.startswith(pool_prefix) \
                and node_labels.get(POOL_LABEL[0]) != POOL_LABEL[1]:
            errors.append(f"{p.metadata.name} on {node} outside its pool")
    for name, (cpu, mem, count) in used.items():
        alloc = nodes[name].status.allocatable
        if cpu > _milli(alloc["cpu"]) or mem > _bytes(alloc["memory"]) \
                or count > int(alloc["pods"]):
            errors.append(f"{name} over-packed: cpu {cpu}m mem {mem} "
                          f"pods {count} vs {alloc}")
    over = {n: c for n, c in anti.items() if c > 1}
    if over:
        errors.append(f"hostname anti-affinity violated on {len(over)} "
                      f"node(s), e.g. {sorted(over.items())[:3]}")
    if spread_label and zone_count:
        skew = max(zone_count.values()) - min(zone_count.values())
        if skew > SPREAD_SKEW:
            errors.append(f"zone spread skew {skew} > maxSkew {SPREAD_SKEW}: "
                          f"{zone_count}")
    if errors:
        raise SmokeFailure("host check: " + "; ".join(errors[:8])
                           + (f" (+{len(errors) - 8} more)"
                              if len(errors) > 8 else ""))
    return {"bound": bound, "nodes": len(nodes)}


# ------------------------------------------------------------ children


def _child_boot(rehearse: bool):
    """Every child's first steps: the compile cache where the environment
    (or the fixed default) says, then the platform assertion BEFORE any
    work, then the device line."""
    from kubernetes_tpu.utils import jaxsetup

    jaxsetup.setup()
    meter = jaxsetup.CompileMeter()
    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not rehearse:
        print(f"chip_smoke: JAX found no accelerator (default backend "
              f"{backend!r})", file=sys.stderr)
        sys.exit(2)
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — version lookup is informational
        libtpu = None
    import kubernetes_tpu.native as native

    info = {**jaxsetup.device_info(),
            "versions": {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__, "libtpu": libtpu},
            "native_engine": native.mod is not None,
            "cache_dir": jax.config.jax_compilation_cache_dir}
    log(f"child device: {json.dumps(info)}")
    return jax, meter, info


class _Scene:
    """One production Scheduler on a fresh in-process Hub."""

    def __init__(self, size: dict, batch: int, node_cap: int, pod_cap: int,
                 inject_fault: bool = False, claim_controller: bool = False):
        from kubernetes_tpu.config.types import default_config
        from kubernetes_tpu.hub import Hub
        from kubernetes_tpu.ops.features import Capacities
        from kubernetes_tpu.scheduler import Scheduler

        # a journal deep enough that the bind audit replays every commit
        self.hub = Hub(journal_capacity=1 << 18)
        if claim_controller:
            from kubernetes_tpu.plugins.dra import ResourceClaimController

            ResourceClaimController(self.hub)
        cfg = default_config()
        cfg.batch_size = batch
        self.sched = Scheduler(self.hub, cfg, caps=Capacities(
            nodes=node_cap, pods=pod_cap))
        if inject_fault:
            from kubernetes_tpu.chaos import DeviceChaos, DeviceChaosConfig

            self.sched.fault_injector = DeviceChaos(DeviceChaosConfig(
                seed=1, launch_error_rate=1.0))
        self.size = size
        self.expected: list[str] = []       # uids that must end bound

    def nodes(self, n: int, zones: int, pool: int = 0) -> None:
        for i in range(n):
            self.hub.create_node(make_node(i, zones, pool=i < pool))

    def submit(self, pods: list) -> list[str]:
        for p in pods:
            self.hub.create_pod(p)
        uids = [p.metadata.uid for p in pods]
        self.expected.extend(uids)
        return uids

    def drain(self) -> None:
        """The production loop until every expected pod is bound; idle
        waits let backoff (preemptors waiting on victims) expire."""
        timeout_s = self.size["drain_s"]
        deadline = time.time() + timeout_s
        while True:
            self.sched.run_until_idle()
            pending = [u for u in self.expected
                       if (p := self.hub.get_pod(u)) is not None
                       and not p.spec.node_name]
            if not pending:
                return
            if time.time() > deadline:
                raise SmokeFailure(
                    f"{len(pending)} pod(s) still pending after "
                    f"{timeout_s:.0f}s (queue "
                    f"{self.sched.queue.pending_counts()}, stats "
                    f"{self.sched.stats})")
            time.sleep(0.05)
            self.sched.queue.flush_backoff_completed()

    def finish(self, **check) -> dict:
        """Close, refuse a run that left the device, audit the journal,
        run the host check; returns the scene's counters."""
        from kubernetes_tpu.perf.harness import (
            DeviceFallback, assert_device_path)
        from kubernetes_tpu.testing.audit import audit_bind_journal

        self.sched.close()
        try:
            assert_device_path(self.sched)
        except DeviceFallback as e:
            raise SmokeFailure(str(e)) from e
        alive = [u for u in self.expected
                 if self.hub.get_pod(u) is not None]
        audit = audit_bind_journal(hub=self.hub, expected_uids=alive)
        if not audit["ok"]:
            raise SmokeFailure(
                f"bind journal audit failed: double_binds="
                f"{audit['double_binds'][:3]} lost={audit['lost'][:3]} "
                f"too_old={audit['too_old']}")
        checked = host_check(self.hub, **check)
        s = self.sched.stats
        prof = self.sched.profiler
        return {"bound": checked["bound"], "binds_audited": audit["binds"],
                "launches": prof.launches + s["gang_device_launches"],
                "device_fallbacks": s["device_fallbacks"],
                "gang_fallbacks": s["gang_fallbacks"],
                "quarantined": s["quarantined"]}

    def shapes(self, **want) -> int:
        """Launches of the profiler shapes whose key matches ``want``."""
        return sum(rec["launches"]
                   for shape, rec in self.sched.profiler.shapes.items()
                   if all(dict(shape).get(k) == v for k, v in want.items()))


def scene_auction_churn(size: dict, inject_fault: bool) -> dict:
    """Node-affinity pods through the auction engine, with foreign pods
    bound and deleted between waves so that patch_chain runs."""
    from kubernetes_tpu.api.objects import LABEL_ZONE

    sc = _Scene(size, size["auction_batch"], size["node_cap"],
                size["pod_cap"], inject_fault)
    sc.nodes(size["nodes"], size["zones"])
    allowed = [f"zone-{z}" for z in range(NA_ZONES)]

    def na_pod(i: int):
        return pod(f"na-{i}", cpu="500m").node_affinity_in(
            LABEL_ZONE, allowed).obj()

    wave, tail, k = size["na_wave"], size["na_tail"], size["foreign"]
    sc.submit([na_pod(i) for i in range(wave)])
    sc.drain()
    # foreign churn: another writer binds big pods onto nodes this
    # scheduler is packing; the live device chain must absorb them
    eligible = [i for i in range(size["nodes"])
                if i % size["zones"] < NA_ZONES][:k]
    foreign = [pod(f"foreign-{j}", cpu="3000m", mem="1Gi")
               .node_name(f"node-{i}").obj()
               for j, i in enumerate(eligible)]
    for p in foreign:
        sc.hub.create_pod(p)
    sc.submit([na_pod(wave + i) for i in range(wave)])
    sc.drain()
    for p in foreign[: k // 2]:
        sc.hub.delete_pod(p.metadata.uid)
    sc.submit([na_pod(2 * wave + i) for i in range(tail)])
    sc.drain()
    out = sc.finish(na_prefix="na-")
    s = sc.sched.stats
    out.update(chain_patches=s["chain_patches"],
               chain_patch_rows=s["chain_patch_rows"],
               auction_launches=sc.shapes(serial=False, topo=False))
    if s["chain_patches"] < 2 or s["chain_patch_fallbacks"]:
        raise SmokeFailure(f"patch_chain did not carry the churn: {s}")
    if not out["auction_launches"]:
        raise SmokeFailure("no launch took the auction engine")
    return out


def scene_required_topology(size: dict, _inject: bool) -> dict:
    """Required zone spread + hostname anti-affinity: the serial commit
    scan at the platform's scan_unroll."""
    from kubernetes_tpu.api.objects import LABEL_HOSTNAME, LABEL_ZONE
    from kubernetes_tpu.models.pipeline import scan_unroll

    sc = _Scene(size, size["scan_batch"], size["node_cap"], size["pod_cap"])
    sc.nodes(size["nodes"], size["zones"])
    sel = {"app": "spread"}
    sc.submit([pod(f"topo-{i}").labels(sel)
               .pod_anti_affinity(LABEL_HOSTNAME, sel)
               .spread_constraint(SPREAD_SKEW, LABEL_ZONE, match=sel).obj()
               for i in range(size["topo_pods"])])
    sc.drain()
    out = sc.finish(anti_label=("app", "spread"),
                    spread_label=("app", "spread"), zones=size["zones"])
    out.update(scan_launches=sc.shapes(serial=True, topo=True),
               scan_unroll=scan_unroll())
    if not out["scan_launches"]:
        raise SmokeFailure("no launch took the serial commit scan")
    return out


def scene_soft_topology(size: dict, _inject: bool) -> dict:
    """Soft-only preferred spread + preferred anti-affinity: off the CPU
    the launch must take the auction engine (scheduler.py soft_auction)."""
    import jax

    from kubernetes_tpu.api.objects import LABEL_ZONE

    sc = _Scene(size, size["scan_batch"], size["node_cap"], size["pod_cap"])
    sc.nodes(size["nodes"], size["zones"])
    sel = {"team": "soft"}
    sc.submit([pod(f"soft-{i}").labels(sel)
               .preferred_pod_anti_affinity(10, LABEL_ZONE, sel)
               .spread_constraint(5, LABEL_ZONE, "ScheduleAnyway", sel).obj()
               for i in range(size["soft_pods"])])
    sc.drain()
    out = sc.finish()
    want_auction = jax.default_backend() != "cpu"
    out.update(soft_launches=sc.shapes(soft=True),
               soft_auction_launches=sc.shapes(soft=True, serial=False),
               soft_engine="auction" if want_auction else "scan (cpu)")
    if not out["soft_launches"]:
        raise SmokeFailure("no launch compiled the soft-topology program")
    if want_auction and out["soft_auction_launches"] != out["soft_launches"]:
        raise SmokeFailure(
            f"soft-only launches did not take the auction engine: {out}")
    return out


def scene_preemption(size: dict, _inject: bool) -> dict:
    """A labelled pool saturated with low-priority fillers; high-priority
    pods restricted to the pool must dry-run victims on the device, evict
    and bind. They arrive one at a time (the PreemptionAsync cadence):
    equal-priority preemptors that hit one small pool in the SAME batch
    can nominate the same node and strand the loser until the 5-minute
    unschedulable flush — a host-side defect the CPU shows too (PERF.md,
    open questions), not what this leg is here to prove."""
    pool = size["pool_nodes"]
    sel = dict([POOL_LABEL])
    sc = _Scene(size, size["scan_batch"], size["node_cap"], size["pod_cap"])
    sc.nodes(size["nodes"], size["zones"], pool=pool)
    fillers = sc.submit([pod(f"low-{i}", cpu="900m").node_selector(sel).obj()
                         for i in range(4 * pool)])
    sc.drain()
    # victims are deleted by the eviction flush: only the preemptors (and
    # the fillers that survive) are expected bound at the end
    sc.expected = []
    for i in range(size["preemptors"]):
        sc.submit([pod(f"high-{i}", cpu="3000m").priority(10)
                   .node_selector(sel).obj()])
        sc.drain()
    out = sc.finish(pool_prefix="high-")
    evicted = sum(1 for u in fillers if sc.hub.get_pod(u) is None)
    out.update(preemptions=sc.sched.stats.get("preemptions", 0),
               victims_evicted=evicted)
    if evicted < 3 * size["preemptors"]:
        raise SmokeFailure(
            f"{size['preemptors']} preemptors of 3000m need 3 victims each; "
            f"only {evicted} evicted")
    return out


def floor_div_probe() -> dict:
    """ops/gang.py needs "how many whole requests fit in free" in f32.
    f32 division on the TPU is not the CPU's: a quotient that is an exact
    integer in real arithmetic lands one ulp low there, so gang.floor_div
    corrects it. Checked against float64 on every a = b * q with
    b <= 4096, q <= 128 (all exact in f32) and on the f32 neighbours just
    below and above each; what plain floor(a / b) gets wrong on this
    device is reported beside it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_tpu.ops.gang import floor_div

    b = np.broadcast_to(
        np.arange(1, 4097, dtype=np.float32)[:, None], (4096, 128))
    exact = b * np.arange(1, 129, dtype=np.float32)[None, :]
    a = np.stack([np.nextafter(exact, np.float32(0)), exact,
                  np.nextafter(exact, np.float32(np.inf))])
    want = np.floor(a.astype(np.float64) / b)
    plain = np.asarray(jax.jit(lambda a, b: jnp.floor(a / b))(a, b))
    got = np.asarray(jax.jit(floor_div)(a, b))
    bad = np.argwhere(got != want)
    out = {"cases": int(a.size), "wrong": int(len(bad)),
           "plain_floor_division_wrong": int((plain != want).sum())}
    if len(bad):
        k, i, j = (int(x) for x in bad[0])
        raise SmokeFailure(
            f"gang.floor_div is wrong on this device in {len(bad)} of "
            f"{a.size} cases, e.g. {a[k, i, j]!r} / {b[i, j]!r} -> "
            f"{got[k, i, j]} (want {want[k, i, j]}): {out}")
    return out


def scene_gang_wave(size: dict, _inject: bool) -> dict:
    """One PodGroup storm wave through pack_gangs_jit, sized to fill the
    cluster EXACTLY: 1-cpu members, 4 per 4-cpu node. A member capacity
    floored one low (f32 division on the chip) strands the last gangs;
    one high over-packs a node — the host check sees either."""
    from kubernetes_tpu.api.objects import (
        LABEL_POD_GROUP, LABEL_QUEUE, ObjectMeta, PodGroup)

    sc = _Scene(size, size["gang_batch"], size["gang_cap"],
                size["small_pod_cap"])
    sc.nodes(size["gang_nodes"], size["zones"])
    pods = []
    for g in range(size["gangs"]):
        sc.hub.create_pod_group(PodGroup(
            metadata=ObjectMeta(name=f"gang-{g}"),
            min_member=size["gang_size"], queue="jobs",
            schedule_timeout_seconds=120.0))
        pods += [pod(f"gang-{g}-m{m}", cpu="1", mem="200Mi").labels(
                     {LABEL_POD_GROUP: f"gang-{g}", LABEL_QUEUE: "jobs"}).obj()
                 for m in range(size["gang_size"])]
    sc.submit(pods)
    sc.drain()
    out = sc.finish()
    out["gang_device_launches"] = sc.sched.stats["gang_device_launches"]
    out["floor_div_probe"] = floor_div_probe()
    if not out["gang_device_launches"]:
        raise SmokeFailure("no wave went through pack_gangs_jit")
    if out["gang_fallbacks"]:
        raise SmokeFailure(
            f"gang units left the device packer: "
            f"{sc.sched.metrics.gang_fallbacks.snapshot()}")
    return out


def scene_dra_templates(size: dict, _inject: bool) -> dict:
    """One claim-template batch through ops/dra.batch_feasible, at the
    DRASteadyStateClaimTemplates shape (perf/workloads.py) but HALF its
    pods: the fused mask is static per launch, so a batch that fills a
    node's last device can hand one pod "devices vanished" at Reserve,
    and with nothing left to bind no event ever wakes it (the bench
    workload itself hangs that way on the CPU — PERF.md, open
    questions). Half full, no node runs out inside the batch."""
    from kubernetes_tpu.perf import workloads as W

    sc = _Scene(size, size["dra_batch"], size["dra_cap"],
                size["small_pod_cap"], claim_controller=True)
    for i in range(size["dra_nodes"]):
        sc.hub.create_node(W._dra_node(i))
        sc.hub.create_resource_slice(W._dra_attr_slice(i))
    sc.hub.create_resource_claim_template(W._dra_template(0))
    sc.submit([W._dra_template_pod(i) for i in range(size["dra_pods"])])
    sc.drain()
    out = sc.finish()
    dra = sc.sched._dra.device_view.stats
    out.update(dra_launches=sc.shapes(dra=True),
               dra_host_fallback_pods=dra["host_fallback_pods"])
    if not out["dra_launches"]:
        raise SmokeFailure("no launch fused the device DRA allocator")
    if dra["host_fallback_pods"]:
        raise SmokeFailure(f"DRA pods took the host allocator: {dra}")
    # every claim allocated on its pod's node, a selector-matching device
    # (even index = preallocate), no device handed out twice
    node_of = {p.metadata.name: p.spec.node_name
               for p in sc.hub.list_pods()}
    taken: set[tuple] = set()
    for claim in sc.hub.list_resource_claims():
        alloc = claim.status.allocation
        owner = claim.metadata.name.rsplit("-", 1)[0]
        if alloc is None or alloc.node_name != node_of.get(owner):
            raise SmokeFailure(f"claim {claim.metadata.name}: allocation "
                               f"{alloc} vs pod on {node_of.get(owner)}")
        for d in alloc.devices:
            key = (alloc.node_name, d.device)
            if key in taken or int(d.device.rsplit("-", 1)[1]) % 2:
                raise SmokeFailure(f"claim {claim.metadata.name}: device "
                                   f"{key} double-booked or unselected")
            taken.add(key)
    out["claims_allocated"] = len(taken)
    if len(taken) != size["dra_pods"]:
        raise SmokeFailure(f"{len(taken)} device(s) allocated for "
                           f"{size['dra_pods']} claim pods")
    return out


SCENES = (("auction_churn", scene_auction_churn),
          ("required_topology", scene_required_topology),
          ("soft_topology", scene_soft_topology),
          ("preemption", scene_preemption),
          ("gang_wave", scene_gang_wave),
          ("dra_templates", scene_dra_templates))


def child_leg_b(size: dict, rehearse: bool, inject_fault: bool) -> dict:
    import logging

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    _jax, meter, info = _child_boot(rehearse)
    from kubernetes_tpu.models.pipeline import launch_cache_size

    out: dict = {"device": info, "scenes": {}}
    for name, fn in SCENES:
        t0 = time.time()
        fn({**size, **WARM}, False)           # warm pass: compiles only
        warm_s = time.time() - t0
        cache0, meter0 = launch_cache_size(), meter.by_name()
        t0 = time.time()
        res = fn(size, inject_fault)
        res["warm_s"] = round(warm_s, 1)
        res["run_s"] = round(time.time() - t0, 1)
        # zero compiles inside the post-warm window: the launch programs
        # by the repo's own count, everything else by name from the meter
        res["launch_compiles_after_warm"] = launch_cache_size() - cache0
        res["other_compiles_after_warm"] = {
            n: rec["compiles"] - meter0.get(n, {"compiles": 0})["compiles"]
            for n, rec in meter.by_name().items()
            if rec["compiles"] > meter0.get(n, {"compiles": 0})["compiles"]}
        out["scenes"][name] = res
        log(f"leg B scene {name}: {json.dumps(res)}")
        if res["launch_compiles_after_warm"]:
            raise SmokeFailure(
                f"scene {name}: {res['launch_compiles_after_warm']} launch "
                f"compile(s) after the warm pass")
    out["compile"] = meter.totals()
    for key in ("device_fallbacks", "gang_fallbacks", "quarantined",
                "launches"):
        out[key] = sum(s[key] for s in out["scenes"].values())
    return out


def child_leg_d(size: dict, rehearse: bool) -> dict:
    """Four chips: the production scenario under a 4-device node mesh must
    place exactly like the single-device run, with the resident node
    table sharded over all four."""
    jax, _meter, info = _child_boot(rehearse)
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.parallel import node_mesh
    from kubernetes_tpu.testing.parity import drive_production_scenario

    devs = jax.devices()
    if len(devs) < 4:
        raise SmokeFailure(f"leg D needs 4 devices, found {len(devs)}")
    caps = Capacities(nodes=size["node_cap"], pods=size["pod_cap"])
    kw = dict(zones=size["zones"], gold_nodes=size["multi_gold"],
              plain=size["multi_plain"], anti=size["multi_anti"],
              spread=size["multi_spread"], low=2 * size["multi_gold"],
              high=size["multi_high"], batch_size=size["multi_batch"])
    base, sched1 = drive_production_scenario(None, size["nodes"], caps, **kw)
    sched1.close()
    sharded, sched = drive_production_scenario(
        node_mesh(devs[:4]), size["nodes"], caps, **kw)
    sched.close()
    from kubernetes_tpu.perf.harness import (
        DeviceFallback, assert_device_path)

    try:
        assert_device_path(sched1)
        assert_device_path(sched)
    except DeviceFallback as e:
        raise SmokeFailure(str(e)) from e
    spread = len(sched.mirror.to_blobs().node_f32.sharding.device_set)
    diff = {k: (base.get(k), sharded.get(k))
            for k in set(base) | set(sharded)
            if base.get(k) != sharded.get(k)}
    unbound = [k for k, v in sharded.items()
               if not v and not k.startswith("low-")]
    if diff or spread != 4 or unbound:
        raise SmokeFailure(f"multichip parity: {len(diff)} placement(s) "
                           f"differ {sorted(diff.items())[:3]}, node table "
                           f"on {spread} device(s), unbound {unbound[:3]}")
    return {"device": info, "pods": len(sharded), "devices_sharded": spread,
            "preemptions": sched.stats.get("preemptions", 0)}


def child_main(args) -> int:
    size = REHEARSAL if args.rehearse else FULL
    try:
        if args.child == "probe":
            out = _child_boot(args.rehearse)[2]
        elif args.child == "B":
            out = child_leg_b(size, args.rehearse, args.inject_device_fault)
        elif args.child == "D":
            out = child_leg_d(size, args.rehearse)
        else:
            raise SystemExit(f"unknown child {args.child!r}")
    except SmokeFailure as e:
        print(f"chip_smoke child {args.child}: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


# ------------------------------------------------------------ parent


class Parent:
    def __init__(self, rehearse: bool, inject_fault: bool):
        self.rehearse = rehearse
        self.inject_fault = inject_fault
        self.size = REHEARSAL if rehearse else FULL
        self.t0 = time.time()
        self.procs: list[subprocess.Popen] = []
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = REPO + os.pathsep + self.env.get(
            "PYTHONPATH", "")
        if rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"
        os.makedirs(LOG_DIR, exist_ok=True)

    def remaining(self) -> float:
        return TIME_LIMIT_S - TIME_RESERVE_S - (time.time() - self.t0)

    def spawn(self, label: str, cmd: list[str], stdout) -> tuple:
        err_path = os.path.join(LOG_DIR, f"{label}.stderr.log")
        err = open(err_path, "w")
        proc = subprocess.Popen(cmd, stdout=stdout, stderr=err, text=True,
                                env=self.env, cwd=REPO,
                                start_new_session=True)
        err.close()
        self.procs.append(proc)
        return proc, err_path

    def kill_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except OSError:
                    pass
                proc.wait()

    def run_child(self, label: str, cmd: list[str]) -> dict:
        """Run one child to its end; its last stdout line is its JSON."""
        budget = self.remaining()
        if budget <= 0:
            raise SmokeFailure(f"{label}: no time left inside the "
                               f"{TIME_LIMIT_S:.0f}s limit")
        t0 = time.time()
        proc, err_path = self.spawn(label, cmd, subprocess.PIPE)
        try:
            stdout, _ = proc.communicate(timeout=budget)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            raise SmokeFailure(f"{label}: timed out after {budget:.0f}s\n"
                               + _tail(err_path)) from None
        with open(os.path.join(LOG_DIR, f"{label}.stdout.log"), "w") as f:
            f.write(stdout)
        if proc.returncode != 0:
            raise SmokeFailure(f"{label}: exit code {proc.returncode}\n"
                               + _tail(err_path))
        lines = stdout.strip().splitlines()
        try:
            out = json.loads(lines[-1])
        except (IndexError, ValueError):
            raise SmokeFailure(f"{label}: no JSON result\n"
                               + _tail(err_path)) from None
        log(f"{label}: ok in {time.time() - t0:.1f}s")
        return out

    def self_cmd(self, child: str) -> list[str]:
        cmd = [sys.executable, os.path.join(REPO, "chip_smoke.py"),
               "--child", child]
        if self.rehearse:
            cmd.append("--rehearse")
        if self.inject_fault:
            cmd.append("--inject-device-fault")
        return cmd

    # ---- leg A: the bench entry, twice -------------------------------

    def leg_a(self) -> dict:
        scale = self.size["basic_scale"]
        cmd = [sys.executable, "-m", "kubernetes_tpu.perf.run_one",
               "scheduling_basic", "--scale", str(scale)]
        runs = []
        for n in (1, 2):
            r = self.run_child(f"A{n}", cmd)
            want = max(1, int(10000 * scale))
            if r.get("pods_scheduled") != want:
                raise SmokeFailure(f"A{n}: {r.get('pods_scheduled')} of "
                                   f"{want} measured pods scheduled")
            if r["stats"]["device_fallbacks"] or r["measured_compiles"]:
                raise SmokeFailure(
                    f"A{n}: device_fallbacks="
                    f"{r['stats']['device_fallbacks']} measured_compiles="
                    f"{r['measured_compiles']}")
            if not self.rehearse and r["platform"] != "tpu":
                raise SmokeFailure(f"A{n} ran on {r['platform']!r}")
            runs.append(r)
        cold, warm = runs
        cc = warm["compile_cache"]
        if not cc["cache_hits"] or cc["launch_misses"]:
            raise SmokeFailure(f"A2: the persistent compile cache did not "
                               f"carry the launch programs: {cc}")
        if not self.rehearse and not warm["warm_s"] < cold["warm_s"]:
            raise SmokeFailure(
                f"A2 warmed in {warm['warm_s']}s, no faster than the cold "
                f"process ({cold['warm_s']}s)")
        return {
            "ok": True, "nodes": max(1, int(5000 * scale)),
            "pods": cold["stats"]["scheduled"],
            "cold_warm_s": cold["warm_s"], "warm_warm_s": warm["warm_s"],
            "cold_compile": cold["compile_cache"], "warm_compile": cc,
            "measured_compiles": [r["measured_compiles"] for r in runs],
            "device_fallbacks": sum(r["device_fallbacks"] for r in runs),
            "launches": sum(r["stats"]["batches"] for r in runs),
            "pods_per_sec_not_a_benchmark": [r["pods_per_sec"]
                                             for r in runs]}

    # ---- leg B / D: children of this file ----------------------------

    def leg_b(self) -> dict:
        r = self.run_child("B", self.self_cmd("B"))
        if not self.rehearse and r["device"]["platform"] != "tpu":
            raise SmokeFailure(f"B ran on {r['device']['platform']!r}")
        return {"ok": True, **r}

    def leg_d(self) -> dict:
        return {"ok": True, **self.run_child("D", self.self_cmd("D"))}

    # ---- leg C: the served daemon ------------------------------------

    def leg_c(self) -> dict:
        import urllib.request

        from kubernetes_tpu.hub import Hub
        from kubernetes_tpu.hubclient import RemoteHub
        from kubernetes_tpu.hubserver import HubServer
        from kubernetes_tpu.telemetry.fleet import parse_exposition
        from kubernetes_tpu.testing.audit import audit_bind_journal

        size = self.size
        hub = Hub(journal_capacity=1 << 18)
        for i in range(size["nodes"]):
            hub.create_node(make_node(i, size["zones"]))
        server = HubServer(hub).start()
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        daemon, err_path = self.spawn(
            "C", [sys.executable, "-m", "kubernetes_tpu", "--hub",
                  server.address, "--secure-port", str(port)],
            subprocess.DEVNULL)
        client = RemoteHub(server.address)
        t0 = time.time()
        try:
            pods = [pod(f"served-{i}").obj()
                    for i in range(size["daemon_pods"])]
            for p in pods:
                client.create_pod(p)
            uids = [p.metadata.uid for p in pods]
            while True:
                pending = sum(1 for u in uids
                              if not hub.get_pod(u).spec.node_name)
                if not pending:
                    break
                if daemon.poll() is not None:
                    raise SmokeFailure(
                        f"C: the daemon exited rc {daemon.returncode} with "
                        f"{pending} pod(s) pending\n" + _tail(err_path))
                if self.remaining() <= 0:
                    raise SmokeFailure(
                        f"C: {pending} pod(s) still pending at the time "
                        f"limit\n" + _tail(err_path))
                time.sleep(0.2)
            bind_s = time.time() - t0
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
                samples = parse_exposition(resp.read().decode()).samples
            daemon.send_signal(signal.SIGTERM)
            try:
                rc = daemon.wait(timeout=60)
            except subprocess.TimeoutExpired:
                raise SmokeFailure("C: the daemon ignored SIGTERM for 60s\n"
                                   + _tail(err_path)) from None
        finally:
            client.close()
            server.stop()
        if rc != 0:
            raise SmokeFailure(f"C: the daemon exited rc {rc} on SIGTERM\n"
                               + _tail(err_path))
        with open(err_path) as f:
            device = next((json.loads(line.split(" ", 1)[1]) for line in f
                           if line.startswith("device: ")), None)
        if device is None:
            raise SmokeFailure("C: the daemon never named its device\n"
                               + _tail(err_path))
        if not self.rehearse and device["platform"] != "tpu":
            raise SmokeFailure(f"C: the daemon ran on {device['platform']!r}")
        audit = audit_bind_journal(hub=hub, expected_uids=uids)
        if not audit["ok"]:
            raise SmokeFailure(f"C: bind journal audit failed: "
                               f"{audit['double_binds'][:3]} "
                               f"lost={audit['lost'][:3]}")
        host_check(hub)
        def total(name: str) -> int:
            return int(sum(s.value for s in samples if s.name == name))

        fallbacks = total("scheduler_device_fallbacks_total")
        quarantined = total("scheduler_quarantines_total")
        if fallbacks or quarantined:
            raise SmokeFailure(f"C: scheduler_device_fallbacks_total="
                               f"{fallbacks} scheduler_quarantines_total="
                               f"{quarantined}\n" + _tail(err_path))
        compiles = {s.labels["cause"]: int(s.value) for s in samples
                    if s.name == "scheduler_device_compiles_total"}
        # the daemon starts at the default 1024-node bucket: pods bound
        # to more distinct nodes than that prove the mirror re-bucketed
        distinct = len({hub.get_pod(u).spec.node_name for u in uids})
        if size["nodes"] > 1024 and distinct <= 1024:
            raise SmokeFailure(
                f"C: pods landed on {distinct} distinct node(s); the "
                f"default 1024-node mirror never grew to hold "
                f"{size['nodes']}")
        return {
            "ok": True, "device": device, "nodes": size["nodes"],
            "pods": len(uids), "binds_audited": audit["binds"],
            "device_fallbacks": fallbacks, "quarantined": quarantined,
            "launches": total("scheduling_cycle_duration_seconds_count"),
            "compiles": compiles, "distinct_nodes": distinct,
            "launch_shapes": total("scheduler_device_launch_shapes"),
            "create_to_all_bound_s": round(bind_s, 1),
            "sigterm_rc": rc}


def _tail(path: str, n: int = 3000) -> str:
    try:
        with open(path) as f:
            return f"--- end of {path} ---\n" + f.read()[-n:]
    except OSError:
        return ""


def parent_main(args) -> int:
    if args.inject_device_fault and not args.rehearse:
        print("--inject-device-fault is a rehearsal-only test seam",
              file=sys.stderr)
        return 2
    legs = [x for x in args.legs.upper().split(",") if x]
    par = Parent(args.rehearse, args.inject_device_fault)
    # a driver that gives up sends SIGTERM: leave no child on the chip
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        try:
            probe = par.run_child("probe", par.self_cmd("probe"))
        except SmokeFailure as e:
            # no accelerator (or no JAX at all): non-zero, and NO result
            print(f"chip_smoke: {e}", file=sys.stderr)
            return 2
        result: dict = {
            "ok": False,
            "device": {"platform": probe["platform"],
                       "kind": probe["device_kind"],
                       "count": probe["device_count"]},
            "rehearsal": args.rehearse,
            "versions": probe["versions"],
            "native_engine": probe["native_engine"],
            "cache_dir": probe["cache_dir"],
            # cuts of scale made to fit the time limit: none were needed
            "nodes": par.size["nodes"], "reduced": [], "legs": {}}
        if "D" in legs and probe["device_count"] < 4:
            legs.remove("D")
            result["multichip"] = \
                f"not run ({probe['device_count']} devices)"
        for leg in legs:
            fn = {"A": par.leg_a, "B": par.leg_b, "C": par.leg_c,
                  "D": par.leg_d}[leg]
            t0 = time.time()
            try:
                out = fn()
            except SmokeFailure as e:
                log(f"leg {leg} FAILED: {e}")
                out = {"ok": False, "error": str(e).splitlines()[0][:400]}
            out["wall_s"] = round(time.time() - t0, 1)
            result["legs"][leg] = out
        if "D" in result["legs"]:
            result["multichip"] = (
                "4 devices: placements equal the single-device run"
                if result["legs"]["D"]["ok"] else "FAILED")
    finally:
        par.kill_all()
    ran = result["legs"]
    for key in ("device_fallbacks", "gang_fallbacks", "quarantined",
                "launches"):
        result[key] = sum(leg.get(key, 0) for leg in ran.values())
    result["wall_s"] = round(time.time() - par.t0, 1)
    result["ok"] = bool(ran) and all(leg["ok"] for leg in ran.values())
    result["claim"] = None
    summary = json.dumps(result)
    with open(os.path.join(LOG_DIR, "summary.json"), "w") as f:
        f.write(summary + "\n")
    print(summary)
    # the verdict: exactly these keys, and the last line of stdout
    print(json.dumps({"ok": result["ok"], "device": result["device"]}),
          flush=True)
    return 0 if result["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, for the tests; the result "
                         "says \"rehearsal\": true")
    ap.add_argument("--legs", default="A,B,C,D",
                    help="comma-separated legs to run (default: all; D "
                         "only runs with >= 4 devices)")
    ap.add_argument("--inject-device-fault", action="store_true",
                    help="rehearsal-only test seam: fault every launch of "
                         "leg B's first scene; the smoke must then FAIL")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return child_main(args) if args.child else parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
