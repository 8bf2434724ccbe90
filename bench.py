"""Headline benchmark: production-path scheduling throughput, 30 workloads.

Drives EVERY thresholded reference scheduler_perf workload (BASELINE.md's
full table: the 5 BASELINE.json headliners plus the affinity, spreading,
churn, gated, daemonset, unschedulable, DRA and feature-gate-variant
shapes) through the
PRODUCTION Scheduler loop — pods created via
hub.create_pod, popped from the PriorityQueue, packed into the HBM mirror,
scheduled by the fused device pipeline, committed through the framework's
reserve/permit/bind points, bindings written to the hub — exactly the path
a real cluster would run. Throughput is observed from the hub watch stream
by a 1s-window collector (util.go:442-630 equivalent).

Each workload runs in its OWN subprocess (kubernetes_tpu.perf.run_one),
matching the reference harness's per-workload process isolation: in one
shared process, earlier workloads' device-memory/executable pressure
shows up as multi-second stalls in later measured phases. Each subprocess
does a tiny same-shapes warmup pass first, and the on-disk XLA compile
cache carries compilations across processes and rounds.

Prints ONE JSON line: the headline SchedulingBasic number vs the
reference's 270 pods/s CI floor (misc/performance-config.yaml:63), with
per-workload results (value, threshold, vs_baseline, window percentiles,
and the device each row ran on) under "workloads". Exits non-zero,
naming them, when any workload failed or timed out.

On a machine with a chip: `python bench.py --no-test-gate` (the default
path first runs the whole pytest suite). This process never touches JAX,
so each run_one child has the chip to itself; the control-plane gates
(--scaleout, --fanout-smoke, --overload, --chaos-smoke) force their
children onto the CPU.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_repo = os.path.dirname(os.path.abspath(__file__))

BASELINE_PODS_PER_SEC = 270.0  # misc/performance-config.yaml:63

# the committed artifact README.md's bench table is generated from; a
# new measurement round commits a new artifact and re-points this
README_BENCH_ARTIFACT = "BENCH_r19_builder.json"
_TABLE_BEGIN = "<!-- BENCH_TABLE_BEGIN"
_TABLE_END = "<!-- BENCH_TABLE_END -->"


def readme_bench_table(artifact: dict) -> str:
    """Render the README bench table MECHANICALLY from a bench artifact —
    hand-edited numbers drift from the committed measurements (round-5
    shipped a 243 pods/s claim over a 44.8 artifact row); generated rows
    cannot."""
    lines = ["| workload | pods/s | floor | multiple |",
             "|---|---|---|---|"]
    for w in artifact["workloads"].values():
        floor = w.get("threshold") or 0
        mult = w["pods_per_sec"] / floor if floor else 0.0
        lines.append(f"| {w['name']} | {w['pods_per_sec']:,.1f} "
                     f"| {floor:g} | {mult:.1f}× |")
    return "\n".join(lines)


def readme_check(write: bool = False,
                 artifact_path: str | None = None) -> bool:
    """--readme-check: diff README.md's generated bench-table block
    against the committed artifact; False (CI-red) on mismatch.
    --readme-update (write=True) rewrites the block in place."""
    artifact_path = artifact_path or os.path.join(_repo,
                                                  README_BENCH_ARTIFACT)
    with open(artifact_path) as f:
        artifact = json.load(f)
    readme_path = os.path.join(_repo, "README.md")
    with open(readme_path) as f:
        readme = f.read()
    begin = readme.find(_TABLE_BEGIN)
    end = readme.find(_TABLE_END)
    if begin < 0 or end < 0 or end < begin:
        print("README.md: bench-table markers missing/corrupt "
              f"({_TABLE_BEGIN} ... {_TABLE_END})", file=sys.stderr)
        return False
    # keep the marker line (it names the artifact) — regenerate between
    # the end of that line and the END marker
    body_start = readme.index("\n", begin) + 1
    want = readme_bench_table(artifact) + "\n"
    have = readme[body_start:end]
    if have == want:
        return True
    if write:
        with open(readme_path, "w") as f:
            f.write(readme[:body_start] + want + readme[end:])
        print(f"README.md bench table regenerated from "
              f"{os.path.basename(artifact_path)}", file=sys.stderr)
        return True
    import difflib

    diff = difflib.unified_diff(
        have.splitlines(keepends=True), want.splitlines(keepends=True),
        fromfile="README.md (committed)",
        tofile=f"{os.path.basename(artifact_path)} (generated)")
    sys.stderr.writelines(diff)
    print("README bench table does not match the committed artifact; "
          "run `python bench.py --readme-update`", file=sys.stderr)
    return False

BENCH_WORKLOAD_FNS = (
    "scheduling_basic",
    "scheduling_node_affinity",
    "scheduling_pod_anti_affinity",
    "topology_spreading",
    "preemption_async",
    "unschedulable",
    "unschedulable_qhints",
    "mixed_churn",
    "scheduling_daemonset",
    "scheduling_while_gated",
    "preferred_pod_affinity",
    "preferred_pod_anti_affinity",
    "ns_selector_anti_affinity",
    "dra_steady_state",
    "dra_steady_state_templates",
    "dra_steady_state_cel_in",
    "dra_multi_request",
    "scheduling_pod_affinity",
    "mixed_scheduling_base_pod",
    "ns_selector_pod_affinity",
    "ns_selector_preferred_affinity",
    "gated_pods_with_pod_affinity",
    "preferred_topology_spreading",
    "scheduling_with_node_inclusion_policy",
    "scheduling_basic_qhints",
    "preemption_async_enabled",
    "ns_selector_preferred_anti_affinity",
    "multi_tenant_gang_storm",
    "quota_exhaustion_churn",
    "gang_preemption",
    "gang_topology_packing",
)

# the ROADMAP's sub-10x offenders, profiled with the flight recorder's
# per-phase attribution by --profile (mirrors workloads.PROFILE_WORKLOADS
# by name; tests/test_perf_harness.py asserts the two stay in sync)
PROFILE_WORKLOAD_FNS = (
    "scheduling_daemonset",
    "mixed_churn",
    "preferred_pod_anti_affinity",
    "preferred_topology_spreading",
    "ns_selector_preferred_affinity",
    "ns_selector_preferred_anti_affinity",
    "dra_steady_state",
    "dra_steady_state_templates",
    "multi_tenant_gang_storm",
    "quota_exhaustion_churn",
    "gang_preemption",
    "gang_topology_packing",
)

# the always-on recorder's cost ceiling: what makes "every cycle, every
# phase" viable instead of sampling-on-slow
TRACE_OVERHEAD_BUDGET = 0.02   # <2% p50 cycle time

# --ab-scorer: learned-vs-hand-tuned phase-total latency parity bar
AB_LATENCY_BUDGET = 0.03       # <3% phase-total delta on SchedulingBasic


def run_ab_scorer(smoke: bool = False, scale: float = 0.1,
                  generations: int = 1) -> dict:
    """--ab-scorer: the learned-scoring quality harness, end to end in
    one process — (1) a hand-tuned collection run of SchedulingBasic
    with the trace export on, (2) replay-train a checkpoint from the
    exported placement rows, (3) paired A/B of hand-tuned vs learned on
    the same workloads with the SAME tie-break seed, reporting latency
    parity (non-view flight-recorder phase totals) and the quality
    metrics (preemptions, spread imbalance, time-to-bind p99, and —
    now that the arms export the v3 alternative rows — per-placement
    regret mean/p99) the harness records per workload. ``--generations
    N`` (ROADMAP item 4's gate) additionally closes the loop N-1 more
    times: each refresh generation re-collects traces under the LIVE
    learned policy, retrains through the learn-loop daemon body, and
    passes the promotion gate before the next collection hot-reloads
    the winner. The artifact rows are shaped for embedding in
    BENCH_r08+ files (quality columns ride "workloads")."""
    import shutil
    import tempfile

    # the workdir holds the rotation-disabled trace export (can exceed
    # 64MiB at full scale) + the checkpoint: cleaned on EVERY exit path
    workdir = tempfile.mkdtemp(prefix="ab_scorer_")
    try:
        return _ab_scorer_run(workdir, smoke, scale, generations)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _ab_scorer_run(workdir: str, smoke: bool, scale: float,
                   generations: int = 1) -> dict:
    from kubernetes_tpu.utils import jaxsetup

    jaxsetup.setup()

    from kubernetes_tpu.config.types import Plugin, default_config
    from kubernetes_tpu.learn.checkpoint import save_checkpoint
    from kubernetes_tpu.learn.replay import build_dataset
    from kubernetes_tpu.learn.train import TrainConfig, train
    from kubernetes_tpu.perf.harness import run_workload
    from kubernetes_tpu.perf import workloads as W
    from kubernetes_tpu.utils.tracing import LOOP_PHASES, VIEW_PHASES

    tie_seed = 2026_0801

    def shrink(factory, **kw):
        """Smoke variant: small cluster AND small capacity buckets, so
        the in-process smoke never compiles the 8192-node programs —
        same trick as trace_overhead_smoke."""
        def make():
            w = factory(**kw)
            w.node_capacity = 64
            w.pod_capacity = 2048
            w.batch_size = 32
            w.warm_full_nodes = False
            return w
        return make

    if smoke:
        scale = 1.0
        ab_factories = (
            ("SchedulingBasic", shrink(W.scheduling_basic, init_nodes=32,
                                       init_pods=16, measure_pods=200)),
            ("TopologySpreading", shrink(W.topology_spreading,
                                         init_nodes=32, init_pods=64,
                                         measure_pods=96)),
            # 24 nodes x 4 cpu hold ~96 of the 900m init pods: keep the
            # init phase under capacity or it can never complete
            ("PreemptionAsync", shrink(W.preemption_async, init_nodes=24,
                                       init_pods=80, measure_pods=48)),
        )
        collection = ab_factories[0][1]
    else:
        ab_factories = (("SchedulingBasic", W.scheduling_basic),
                        ("TopologySpreading", W.topology_spreading),
                        ("PreemptionAsync", W.preemption_async))
        collection = W.scheduling_basic

    def base_cfg():
        c = default_config()
        c.tie_break_seed = tie_seed
        return c

    trace_path = os.path.join(workdir, "traces.jsonl")
    ckpt_path = os.path.join(workdir, "scorer.json")

    # 1. collection: hand-tuned SchedulingBasic with the export on
    # (feature vectors opted in — they ARE the training substrate;
    # rotation off for this bounded-lifetime run so a >64MiB collection
    # cannot silently rotate early examples out of the dataset)
    def export_into(c, path):
        c.trace_export_path = path
        c.trace_export_features = True
        # the v3 alternative rows: the regret substrate (and the
        # learn-loop's counterfactual fine-tune input)
        c.trace_export_alts = True
        c.trace_export_max_bytes = 0
        return c

    cfg = export_into(base_cfg(), trace_path)
    print("ab-scorer: collection run (trace export)...", file=sys.stderr)
    run_workload(collection(), scale=scale, config=cfg)

    # 2. replay-train the scorer from the exported placement rows
    ds = build_dataset([trace_path])
    params, info = train(ds, TrainConfig(
        seed=0, meta={"version": 1, "source": "ab_scorer"}))
    doc = save_checkpoint(ckpt_path, params, meta=info)
    print(f"ab-scorer: trained on {len(ds)} examples "
          f"(bc loss {info['bc_loss_first']} -> {info['bc_loss_last']})",
          file=sys.stderr)

    def learned_cfg():
        c = base_cfg()
        prof = c.profiles[0]
        prof.plugins.score.enabled.append(Plugin("LearnedScore", 1.0))
        prof.plugin_config["LearnedScore"] = {
            "checkpoint_path": ckpt_path}
        return c

    def phase_total(res: dict) -> float:
        return sum(p["total_s"]
                   for ph, p in res.get("flight", {})
                   .get("phases", {}).items()
                   if ph not in VIEW_PHASES and ph not in LOOP_PHASES)

    def arm(res: dict) -> dict:
        return {
            "pods_per_sec": res.get("pods_per_sec"),
            "phase_total_s": round(phase_total(res), 4),
            "quality": res.get("quality", {}),
        }

    out = {}
    improved_any = []
    for name, factory in ab_factories:
        pair = {}
        for arm_name, cfg_fn in (("hand", base_cfg),
                                 ("learned", learned_cfg)):
            # per-arm tiny compile pass, then the measured run — the
            # learned arm compiles a different program (the MLP term).
            # BOTH passes export (alts on) so the measured run reuses
            # the warm pass's with_alts program AND its quality row
            # carries the regret columns; the export rides both arms
            # symmetrically, so latency parity is unaffected
            run_workload(factory(), scale=0.05 if smoke else 0.005,
                         config=export_into(cfg_fn(), os.path.join(
                             workdir, f"warm_{name}_{arm_name}.jsonl")))
            pair[arm_name] = run_workload(
                factory(), scale=scale, profile=True,
                config=export_into(cfg_fn(), os.path.join(
                    workdir, f"ab_{name}_{arm_name}.jsonl")))
        hand, learned = arm(pair["hand"]), arm(pair["learned"])
        ht, lt = hand["phase_total_s"], learned["phase_total_s"]
        delta = (lt - ht) / ht if ht > 0 else 0.0
        qd = {}
        better = []
        for k in ("preemptions", "spread_stddev", "spread_max_min",
                  "time_to_bind_p99_ms", "regret_mean", "regret_p99"):
            if k not in hand["quality"] or k not in learned["quality"]:
                # a metric missing on EITHER side (e.g. the regret
                # block failed in one arm) is "no data", never a
                # default-0 fabricated win
                continue
            hv = hand["quality"][k]
            lv = learned["quality"][k]
            qd[k] = round(lv - hv, 3)
            # "improved" needs a >=1% relative drop — a sub-noise float
            # delta must not satisfy the quality acceptance criterion
            if hv > 0 and lv < hv and (hv - lv) >= 0.01 * hv:
                better.append(k)
        if better:
            improved_any.append(name)
        out[name] = {"hand": hand, "learned": learned,
                     "latency_delta_pct": round(delta * 100.0, 2),
                     "quality_delta": qd, "improved": better}
        print(f"ab-scorer {name}: phase-total {ht:.3f}s -> {lt:.3f}s "
              f"({delta * 100:+.2f}%), improved: {better or 'none'}",
              file=sys.stderr)
    # ----- refresh generations (ROADMAP item 4's 3-generation gate):
    # collect under the LIVE learned policy -> learn-loop body
    # (retrain + regret fine-tune + promotion gate) -> the next
    # collection's scheduler loads whatever the gate published
    gens = []
    if generations > 1:
        from kubernetes_tpu.learn.loop import LearnLoop, LoopConfig

        loop_traces = os.path.join(workdir, "loop_traces.jsonl")
        loop = LearnLoop(LoopConfig(
            trace_path=loop_traces,
            staging_dir=os.path.join(workdir, "staging"),
            live_path=ckpt_path,
            min_new_rows=32, min_holdout_rows=8,
            bc_epochs=80 if smoke else 200,
            ft_epochs=40 if smoke else 100))
        for _g in range(2, generations + 1):
            res = run_workload(collection(), scale=scale,
                               config=export_into(learned_cfg(),
                                                  loop_traces))
            rep = loop.run_once()
            row = {"generation": rep.get("generation"),
                   "version": rep.get("version"),
                   "status": rep.get("status"),
                   "gate": rep.get("gate"),
                   "regret": rep.get("regret"),
                   "pods_per_sec": res.get("pods_per_sec"),
                   "quality": res.get("quality")}
            gens.append(row)
            print(f"ab-scorer generation {rep.get('generation')}: "
                  f"{rep.get('status')} (version {rep.get('version')}, "
                  f"gate {rep.get('gate')})", file=sys.stderr)

    basic = out.get("SchedulingBasic", {})
    # the 3% parity bar is a FULL-SCALE property (phase totals measured
    # in seconds); smoke phase totals are ~0.1s of mostly dispatch
    # overhead, so the smoke bar is advisory-loose — it exists to catch
    # "the learned arm got 2x slower", not to measure parity
    budget = AB_LATENCY_BUDGET if not smoke else 0.15
    result = {
        "metric": "ab_scorer",
        "unit": "quality",
        "smoke": smoke,
        "tie_break_seed": tie_seed,
        "scale": scale,
        "checkpoint": {k: doc["meta"].get(k)
                       for k in ("version", "fingerprint", "examples",
                                 "bc_loss_last")},
        "latency_budget_pct": budget * 100.0,
        "latency_ok": (basic.get("latency_delta_pct", 0.0)
                       <= budget * 100.0),
        "improved_workloads": improved_any,
        "workloads": out,
    }
    if gens:
        result["generations"] = gens
    return result


def run_profile(smoke: bool = False) -> dict:
    """--profile: run the sub-10x offender workloads with the flight
    recorder's breakdown in each subprocess result, print a per-phase
    p50/p99 table (incl. host-plugin and DRA-allocator time) to stderr
    and the artifact JSON line to stdout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _repo + os.pathsep + env.get("PYTHONPATH", "")
    scale = "0.02" if smoke else "1.0"
    out = {}
    for fn in PROFILE_WORKLOAD_FNS:
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "kubernetes_tpu.perf.run_one", fn,
                 "--scale", scale, "--profile"],
                capture_output=True, text=True, timeout=1800, env=env,
                cwd=_repo)
        except subprocess.TimeoutExpired:
            print(f"{fn}: TIMEOUT after 1800s", file=sys.stderr)
            continue
        if proc.returncode != 0:
            print(f"{fn}: FAILED\n{proc.stderr[-2000:]}", file=sys.stderr)
            continue
        r = json.loads(proc.stdout.strip().splitlines()[-1])
        fl = r.get("flight", {})
        out[r["name"]] = {
            "name": r["name"],
            "pods_per_sec": r.get("pods_per_sec"),
            "threshold": r.get("threshold"),
            "flight": fl,
        }
        print(f"\n{r['name']}: {r.get('pods_per_sec', 0):.1f} pods/s — "
              f"host-tail share {fl.get('host_tail_share', 0):.1%}, "
              f"{fl.get('cycles_recorded', 0)} cycles recorded",
              file=sys.stderr)
        occ = fl.get("occupancy") or {}
        if occ:
            # pipelined waves: how much of each cycle's wall the device
            # launch actually covered (mean near 1.0 = pipeline full)
            print(f"  occupancy: mean {occ['mean']:.1%}, "
                  f"p50 {occ['p50']:.1%}, p99 {occ['p99']:.1%} "
                  f"over {occ['n']} cycles", file=sys.stderr)
        print(f"  {'phase':<18} {'p50_ms':>9} {'p99_ms':>9} "
              f"{'count':>7} {'total_s':>9}", file=sys.stderr)
        for phase, p in sorted(fl.get("phases", {}).items(),
                               key=lambda kv: -kv[1]["total_s"]):
            print(f"  {phase:<18} {p['p50_ms']:>9.3f} {p['p99_ms']:>9.3f} "
                  f"{p['count']:>7} {p['total_s']:>9.3f}", file=sys.stderr)
        plugins = sorted(fl.get("plugins", {}).items(),
                         key=lambda kv: -kv[1]["total_s"])[:8]
        if plugins:
            print(f"  {'plugin/point':<34} {'p50_ms':>9} {'p99_ms':>9} "
                  f"{'total_s':>9}", file=sys.stderr)
            for key, p in plugins:
                print(f"  {key:<34} {p['p50_ms']:>9.3f} "
                      f"{p['p99_ms']:>9.3f} {p['total_s']:>9.3f}",
                      file=sys.stderr)
        dev = fl.get("device")
        if dev:
            # the DeviceProfiler column: compiles by attributed cause +
            # resident HBM footprint — the "why does the device path
            # stall" answer next to the phase table
            causes = ", ".join(f"{k}={v}" for k, v in
                               sorted(dev["compile_causes"].items()))
            print(f"  device: {dev['launches']} launches, "
                  f"{dev['compiles']} compiles ({causes or 'none'}), "
                  f"{len(dev['shapes'])} shapes, "
                  f"{dev['buffer_total_mib']} MiB resident",
                  file=sys.stderr)
    # the fabric row: fanout smoke (small variant) — e2e joined-trace
    # SLO (created->acked p99) + fleet health next to the host tails
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "kubernetes_tpu.fabric.fanout",
             "--smoke"],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=_repo)
        if proc.returncode == 0 and proc.stdout.strip():
            fr = json.loads(proc.stdout.strip().splitlines()[-1])
            out["FanoutSmoke"] = {
                "name": "FanoutSmoke",
                "e2e": fr.get("e2e"),
                "events_traced_frac": fr.get("events_traced_frac"),
                "ok": fr.get("ok"),
            }
            e2e = fr.get("e2e", {})
            lat = e2e.get("created_to_acked", {})
            print(f"\nFanoutSmoke: created->acked p99 "
                  f"{lat.get('p99_s', '?')}s over {lat.get('count', 0)} "
                  f"pods, joinable {e2e.get('joinable_frac', 0):.0%}, "
                  f"fleet {e2e.get('fleet', {}).get('healthy', 0)}/"
                  f"{e2e.get('fleet', {}).get('endpoints', 0)} healthy",
                  file=sys.stderr)
        else:
            print(f"fanout smoke (profile row): FAILED\n"
                  f"{proc.stderr[-1500:]}", file=sys.stderr)
    except subprocess.TimeoutExpired:
        print("fanout smoke (profile row): TIMEOUT", file=sys.stderr)
    return {
        "metric": "phase_profile",
        "unit": "ms",
        "workloads": out,
    }


def trace_overhead_smoke(pairs: int = 4) -> dict:
    """--trace-overhead: the always-on recorder's bar — <2% p50
    cycle-time cost. One process (shared compile cache), a fixed-seed
    shrunk SchedulingBasic, alternating recorder-off/on runs, EXACT raw
    per-cycle durations pooled per arm (the histogram's power-of-2
    buckets would quantize a 2% delta away), medians compared. The ON
    arm also runs the SLO watchdog + an armed autopsy store, so the
    budget covers the whole observability stack: recorder, timelines,
    incident hooks, and breach detection."""
    import tempfile

    from kubernetes_tpu.utils import jaxsetup

    jaxsetup.setup()
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.perf.harness import run_workload
    from kubernetes_tpu.perf.workloads import scheduling_basic

    def make():
        # ~16 pods/cycle x ~16 cycles per run: enough samples per arm
        # for a stable median without a minutes-long smoke
        w = scheduling_basic(init_nodes=32, init_pods=16,
                             measure_pods=240)
        w.node_capacity = 64
        w.pod_capacity = 512
        w.batch_size = 16
        return w

    autopsy_dir = tempfile.mkdtemp(prefix="bench-trace-autopsy-")

    def cfg(recorder_on: bool):
        c = default_config()
        if not recorder_on:
            c.flight_recorder_capacity = 0
        else:
            # the full observability stack on the measured arm: the
            # watchdog evaluates every maintenance pass and the store
            # is armed (no breaches expected on this clean workload,
            # but the hot-path hook checks are what the budget prices)
            c.autopsy_dir = autopsy_dir
            c.watchdog_interval_s = 0.0
        return c

    run_workload(make(), scale=0.1, config=cfg(True))   # compile pass
    arms: dict[bool, list[float]] = {False: [], True: []}
    for _ in range(pairs):
        for on in (False, True):    # alternate so drift hits both arms
            times: list[float] = []
            run_workload(make(), config=cfg(on), cycle_times=times)
            arms[on].extend(times)

    def p50(xs: list[float]) -> float:
        xs = sorted(xs)
        return xs[len(xs) // 2]

    off_p50, on_p50 = p50(arms[False]), p50(arms[True])
    # 100us absolute floor: on a loaded CI box two sub-5ms medians can
    # sit 2% apart from scheduler-unrelated jitter alone
    ok = on_p50 <= off_p50 * (1.0 + TRACE_OVERHEAD_BUDGET) + 100e-6
    return {
        "metric": "trace_overhead",
        "cycle_p50_off_ms": round(off_p50 * 1e3, 3),
        "cycle_p50_on_ms": round(on_p50 * 1e3, 3),
        "delta_pct": round((on_p50 - off_p50) / off_p50 * 100.0, 2),
        "budget_pct": TRACE_OVERHEAD_BUDGET * 100.0,
        "cycles_per_arm": len(arms[True]),
        "ok": ok,
    }


def _control_plane_env() -> dict:
    """Environment for the control-plane gates' children (fabric shard
    processes, scheduler replica arms, the chaos battery): they measure
    no device metric and must not hold the chip — N of them would fight
    over the one a measurement needs — so the CPU platform is set HARD,
    whatever the caller's environment names."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run_scaleout_bench(smoke: bool = False, replicas: int = 4,
                       timeout_s: float = 300.0) -> dict:
    """--scaleout: horizontal scale-out throughput A/B. Two arms on a
    fresh proc fabric each: ONE scheduler OS process vs ``replicas``
    scheduler OS processes (``python -m kubernetes_tpu --hub <router>
    --slices``), draining an identical partition-friendly workload
    (pods spread over 32 namespaces, plain 50m-cpu requests — no gang
    coupling, so slices are independent). OS processes, not threads:
    in-process replicas share one GIL and could never show real
    scaling. ``ok`` iff the multi-replica arm clears 3x the
    single-replica arm's pods/s (acceptance floor) — the single-
    replica arm IS the no-regression reference, measured on the same
    fabric, same workload, same commit. With fewer cores than replica
    processes the floor is unmeasurable (``hardware_limited`` in the
    report); both arms then gate on completeness only."""
    import tempfile
    import time as _time

    pods = 200 if smoke else 800
    nodes = 16
    env = _control_plane_env()

    def run_arm(n_replicas: int) -> dict:
        from kubernetes_tpu.fabric.supervisor import spawn_local_cluster
        from kubernetes_tpu.hubclient import RemoteHub
        from kubernetes_tpu.testing import MakeNode, MakePod

        wal_dir = tempfile.mkdtemp(prefix="scaleout-bench-")
        cluster = spawn_local_cluster(pod_shards=2, wal_dir=wal_dir)
        admin = RemoteHub(cluster.router_url, timeout=10.0,
                          retry_deadline=3.0)
        procs = []
        try:
            for i in range(nodes):
                admin.create_node(MakeNode().name(f"bn-{i}")
                                  .capacity(cpu="64", memory="256Gi",
                                            pods="440").obj())
            for i in range(n_replicas):
                procs.append(subprocess.Popen(
                    [sys.executable, "-m", "kubernetes_tpu",
                     "--hub", cluster.router_url, "--slices",
                     "--slice-heartbeat", "0.25",
                     "--id", f"bench-{i}", "--secure-port", "0"],
                    env=env, cwd=_repo,
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            # wait for every replica to join the slice ring (startup —
            # JAX import included — must not count against pods/s)
            t0 = _time.monotonic()
            while _time.monotonic() - t0 < 120.0:
                try:
                    if len(admin.fabric_schedulers()) >= n_replicas \
                            and admin.fabric_sched_ring()["slots"]:
                        break
                except Exception:  # noqa: BLE001 — fabric warming up
                    pass
                _time.sleep(0.2)
            else:
                raise RuntimeError(
                    f"{n_replicas} replicas never joined the ring")
            _time.sleep(1.0)     # let the slice map settle
            t_start = _time.monotonic()
            for i in range(pods):
                admin.create_pod(MakePod().name(f"bp-{i}")
                                 .namespace(f"bns-{i % 32}")
                                 .req(cpu="50m").obj())
            deadline = _time.monotonic() + timeout_s
            bound = 0
            while _time.monotonic() < deadline:
                bound = sum(1 for p in admin.list_pods()
                            if p.spec.node_name)
                if bound >= pods:
                    break
                _time.sleep(0.1)
            elapsed = _time.monotonic() - t_start
            return {"replicas": n_replicas, "pods": pods,
                    "bound": bound, "elapsed_s": round(elapsed, 2),
                    "pods_per_sec": round(bound / elapsed, 1)
                    if elapsed > 0 else 0.0,
                    "complete": bound >= pods}
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
            try:
                admin.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            cluster.stop()

    single = run_arm(1)
    multi = run_arm(replicas)
    speedup = (multi["pods_per_sec"] / single["pods_per_sec"]
               if single["pods_per_sec"] else 0.0)
    # the 3x floor is a PARALLELISM claim: N CPU-bound scheduler
    # processes (plus the fabric's own) need at least that many cores
    # to demonstrate it. On a smaller box the arms still gate
    # correctness (every pod bound, both arms complete) but the
    # speedup number only measures contention — report it honestly
    # instead of failing hardware that can't show the win
    cores = os.cpu_count() or 1
    hardware_limited = cores < replicas + 1
    return {"metric": "scaleout", "platform": "cpu",
            "single": single, "multi": multi,
            "speedup": round(speedup, 2), "floor": 3.0,
            "cores": cores, "hardware_limited": hardware_limited,
            "ok": (single["complete"] and multi["complete"]
                   and (speedup >= 3.0 or hardware_limited))}


# what names the device a row was measured on, and whether the run
# stayed on it — kept on every published row
ROW_DEVICE_KEYS = ("platform", "device_kind", "device_count",
                   "device_fallbacks")
ROW_KEYS = ("name", "pods_per_sec", "threshold", "vs_baseline", "passed",
            "pods_scheduled", "elapsed_s", "p50", "p90", "p95", "p99",
            "metrics", "quality", "measured_compiles", *ROW_DEVICE_KEYS)


def run_workloads(fns, args: list[str], env: dict,
                  run=None) -> tuple[dict, dict | None, list]:
    """One ``perf.run_one`` subprocess per workload. Returns (rows by
    short name, the SchedulingBasic row, names of workloads that failed
    or timed out). A wedged or failed workload must not kill the bench —
    the rest are still measured — but it is reported, and main() exits
    non-zero on it. ``run`` (default subprocess.run) is the seam the
    tests stub."""
    run = run or subprocess.run
    results: dict = {}
    headline = None
    failed: list[str] = []
    for fn in fns:
        try:
            proc = run(
                [sys.executable, "-m", "kubernetes_tpu.perf.run_one", fn,
                 *args],
                capture_output=True, text=True, timeout=1800, env=env,
                cwd=_repo)
        except subprocess.TimeoutExpired:
            print(f"{fn}: TIMEOUT after 1800s", file=sys.stderr)
            failed.append(fn)
            continue
        if proc.returncode != 0:
            print(f"{fn}: FAILED\n{proc.stderr[-2000:]}", file=sys.stderr)
            failed.append(fn)
            continue
        r = json.loads(proc.stdout.strip().splitlines()[-1])
        print(f"{r['name']}: {r.get('pods_per_sec', 0):.1f} pods/s "
              f"(threshold {r['threshold']}, warm {r.get('warm_s')}s, "
              f"run {r.get('run_s')}s, {r.get('platform')})",
              file=sys.stderr)
        short = r["name"].split("/")[0]
        if short in results:
            short = r["name"]   # variant rows (e.g. _QueueingHintsEnabled)
        results[short] = {k: r[k] for k in ROW_KEYS if k in r}
        if short == "SchedulingBasic":
            headline = r
    return results, headline, failed


def main() -> None:
    if "--readme-check" in sys.argv or "--readme-update" in sys.argv:
        # red-suite gate next to --chaos-smoke: published README numbers
        # must be the committed artifact's, mechanically
        ok = readme_check(write="--readme-update" in sys.argv)
        sys.exit(0 if ok else 1)
    if "--profile" in sys.argv:
        # per-phase attribution for the sub-10x offenders: the BENCH
        # artifact row the next VERDICT reads instead of guessing where
        # Daemonset/MixedChurn/DRA host time goes
        print(json.dumps(run_profile(smoke="--smoke" in sys.argv)))
        return
    if "--ab-scorer" in sys.argv:
        # learned-scoring quality gate: collection -> replay-train ->
        # paired hand-vs-learned A/B with one tie-break seed; artifact
        # rows carry the quality columns (incl. regret) for BENCH_r08+
        # files. --generations N additionally exercises N-1 learn-loop
        # refresh generations (retrain -> gate -> promote -> reload)
        scale = 0.1
        if "--scale" in sys.argv:
            scale = float(sys.argv[sys.argv.index("--scale") + 1])
        generations = 1
        if "--generations" in sys.argv:
            generations = int(
                sys.argv[sys.argv.index("--generations") + 1])
        r = run_ab_scorer(smoke="--smoke" in sys.argv, scale=scale,
                          generations=generations)
        print(json.dumps(r))
        if not r["latency_ok"]:
            print(f"ab-scorer: SchedulingBasic phase-total delta "
                  f"{r['workloads']['SchedulingBasic']['latency_delta_pct']}"
                  f"% exceeds {r['latency_budget_pct']:.0f}% budget",
                  file=sys.stderr)
        sys.exit(0 if r["latency_ok"] else 1)
    if "--scaleout" in sys.argv:
        # scale-out throughput gate (ISSUE 16 acceptance): N scheduler
        # processes over the slice ring must clear 3x one process's
        # pods/s, with the single-process arm measured fresh as the
        # no-regression reference
        r = run_scaleout_bench(smoke="--smoke" in sys.argv)
        print(json.dumps(r))
        if r["hardware_limited"]:
            print(f"scaleout: only {r['cores']} core(s) for "
                  f"{r['multi']['replicas']} replica processes — "
                  f"speedup {r['speedup']}x measures contention, not "
                  f"scaling; gating on correctness only",
                  file=sys.stderr)
        elif not r["ok"]:
            print(f"scaleout: {r['multi']['pods_per_sec']} pods/s with "
                  f"{r['multi']['replicas']} replicas is "
                  f"{r['speedup']}x single ({r['single']['pods_per_sec']}"
                  f" pods/s); floor {r['floor']}x", file=sys.stderr)
        sys.exit(0 if r["ok"] else 1)
    if "--trace-overhead" in sys.argv:
        # red-suite gate next to --chaos-smoke: the always-on recorder
        # must stay under its <2% p50 cycle-time budget
        r = trace_overhead_smoke()
        print(json.dumps(r))
        if not r["ok"]:
            print(f"trace overhead over budget: recorder-on p50 "
                  f"{r['cycle_p50_on_ms']}ms vs off "
                  f"{r['cycle_p50_off_ms']}ms "
                  f"({r['delta_pct']:+.2f}% > {r['budget_pct']:.0f}%)",
                  file=sys.stderr)
        sys.exit(0 if r["ok"] else 1)
    if "--fanout-smoke" in sys.argv:
        # red-suite gate for the control-plane fabric (ISSUE 9): 10k
        # kubelet-analog reflectors through a 2-level relay tree with
        # chaos watch cuts on the upstream streams. Invariants: the hub
        # holds <= relay-count pod sockets, every cut heals by journal
        # RESUME (0 relists, exact event counts at every subscriber),
        # downstream reconnects are served from relay rings, slow
        # subscribers are evicted + recover, the binary codec carries
        # the storm in <= 1/3 the JSON bytes, and a steady-state drift
        # sentinel pass issues 0 full LISTs.
        env = _control_plane_env()
        # both deployment modes, side by side in the artifact: the
        # in-process fabric (PR 9's tree) and the PROCESS-MODE fabric
        # (shard processes + stateless router + auto-discovered
        # relays, ISSUE 11) — the `procs` column proves the split
        # behaves identically where it matters (0 relists, exact
        # counts) and reports what it costs
        combined: dict = {}
        rc = 0
        for label, extra in (("inproc", []), ("procs", ["--procs"])):
            cmd = [sys.executable, "-m", "kubernetes_tpu.fabric.fanout",
                   *extra]
            if "--smoke" in sys.argv:
                cmd.append("--smoke")
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=1200, env=env, cwd=_repo)
            out = proc.stdout.strip().splitlines()
            try:
                combined[label] = json.loads(out[-1]) if out else \
                    {"ok": False, "error": "no output"}
            except ValueError:
                combined[label] = {"ok": False,
                                   "error": out[-1][:500]}
            if proc.returncode != 0:
                rc = proc.returncode or 1
                print(f"fanout smoke ({label}) FAILED\n"
                      f"{proc.stderr[-2000:]}", file=sys.stderr)
        combined["ok"] = all(combined[k].get("ok")
                             for k in ("inproc", "procs"))
        combined["platform"] = "cpu"
        print(json.dumps(combined))
        sys.exit(rc if rc else (0 if combined["ok"] else 1))
    if "--scenario" in sys.argv:
        # replay one named regime (or a trace file) against the real
        # fabric with trace-time SLO + exactly-once gates; the printed
        # row carries the scenario SLO columns for BENCH_* artifacts
        from kubernetes_tpu.scenario.generators import generate
        from kubernetes_tpu.scenario.replay import replay_trace
        from kubernetes_tpu.scenario.trace import load_trace

        arg = sys.argv[sys.argv.index("--scenario") + 1]
        speed = (float(sys.argv[sys.argv.index("--speed") + 1])
                 if "--speed" in sys.argv else 3.0)
        seed = (int(sys.argv[sys.argv.index("--seed") + 1])
                if "--seed" in sys.argv else 0)
        tr = (load_trace(arg) if os.path.exists(arg)
              else generate(arg, seed=seed))
        rep = replay_trace(tr, speed=speed)
        print(json.dumps({
            "metric": "scenario_replay",
            "scenario": rep["name"],
            "speed": rep["speed"],
            "time_to_bind_p50_ms": rep["stats"]["time_to_bind_p50_ms"],
            "time_to_bind_p99_ms": rep["stats"]["time_to_bind_p99_ms"],
            "time_to_bind_max_ms": rep["stats"]["time_to_bind_max_ms"],
            "slo_ok": rep["slo"]["ok"],
            "audit_ok": rep["audit"]["ok"],
            "hardware_limited": rep["pacing"]["hardware_limited"],
            "report": rep,
        }))
        sys.exit(0 if rep["ok"] else 1)
    if "--overload" in sys.argv:
        # overload row: priority-pod time-to-bind under the best-effort
        # stampede regime (SLO judged over priority uids only — the
        # shed best-effort tail is the protection working), plus the
        # flow-control shed accounting from the overload storm
        os.environ["JAX_PLATFORMS"] = "cpu"   # control-plane gate
        from kubernetes_tpu.chaos import run_overload_storm
        from kubernetes_tpu.scenario.generators import generate
        from kubernetes_tpu.scenario.replay import replay_trace

        seed = (int(sys.argv[sys.argv.index("--seed") + 1])
                if "--seed" in sys.argv else 0)
        tr = generate("overload_stampede", seed=seed)
        rep = replay_trace(tr, speed=3.0)
        storm = run_overload_storm(seed=seed)
        print(json.dumps({
            "metric": "overload",
            "platform": "cpu",
            "scenario": rep["name"],
            "speed": rep["speed"],
            "priority_pods": rep["slo_pods"],
            "pods": rep["pods"],
            "prio_time_to_bind_p50_ms":
                rep["stats"]["time_to_bind_p50_ms"],
            "prio_time_to_bind_p99_ms":
                rep["stats"]["time_to_bind_p99_ms"],
            "slo_ok": rep["slo"]["ok"],
            "audit_ok": rep["audit"]["ok"],
            "storm_shed_429s": storm["server_rejected"]["best-effort"],
            "storm_probe_p99_s": storm["probe_p99_s"],
            "storm_ok": storm["ok"],
            "hardware_limited": rep["pacing"]["hardware_limited"],
            "report": rep,
        }))
        sys.exit(0 if (rep["ok"] and storm["ok"]) else 1)
    if "--scenario-fuzz" in sys.argv:
        # EXPLICIT opt-in (not part of any battery): adversarial search
        # over regime parameter space under a wall-clock budget;
        # SLO-breaching traces are auto-filed as regression gates
        from kubernetes_tpu.scenario.fuzz import fuzz

        budget = (float(sys.argv[sys.argv.index("--budget") + 1])
                  if "--budget" in sys.argv else 120.0)
        seed = (int(sys.argv[sys.argv.index("--seed") + 1])
                if "--seed" in sys.argv else 0)
        objective = ("regret" if "--objective-regret" in sys.argv
                     else "p99")
        out_dir = os.path.join(_repo, "tests", "regression_traces")
        rep = fuzz(budget_s=budget, seed=seed, objective=objective,
                   out_dir=out_dir,
                   log=lambda s: print(s, file=sys.stderr, flush=True))
        print(json.dumps({
            "metric": "scenario_fuzz",
            "objective": rep["objective"],
            "budget_s": rep["budget_s"],
            "elapsed_s": rep["elapsed_s"],
            "candidates": rep["candidates"],
            "worst": rep["worst"],
            "filed": rep["filed"],
        }))
        sys.exit(0)
    if "--chaos-smoke" in sys.argv:
        # red-suite gate: the full storm battery — the smoke scenario
        # (call faults + watch cut + partition through the proxy), the
        # device-fault storm (fallback ladder + poison-pod quarantine),
        # and the 1k-pod crash storm (watch cuts + leader kill +
        # kill-and-restart). Invariants: every pod bound exactly once
        # (fencing + bind-once), zero daemon deaths, poison quarantined
        # with a hub Event, cache-hub converged.
        env = _control_plane_env()
        proc = subprocess.run(
            [sys.executable, "-m", "kubernetes_tpu.chaos",
             "--storm", "all"],
            capture_output=True, text=True, timeout=1200, env=env,
            cwd=_repo)
        out = proc.stdout.strip().splitlines()
        try:
            storm = json.loads(out[-1])
        except (IndexError, ValueError):
            storm = {"ok": False, "error": out[-1][:500] if out
                     else "no output"}
        storm["platform"] = "cpu"
        print(json.dumps(storm))
        if proc.returncode != 0:
            print(f"chaos smoke FAILED\n{proc.stderr[-2000:]}",
                  file=sys.stderr)
            sys.exit(proc.returncode)
        # the trace-overhead gate rides along: one red-suite invocation
        # covers both "survives storms" and "the always-on recorder
        # stays under its <2% budget"
        r = trace_overhead_smoke()
        print(json.dumps(r))
        if not r["ok"]:
            print("trace overhead over budget (see --trace-overhead)",
                  file=sys.stderr)
        sys.exit(0 if r["ok"] else 1)
    smoke = "--smoke" in sys.argv
    scale = "0.02" if smoke else "1.0"
    # --regret: every workload row additionally carries the
    # per-placement regret_mean/regret_p99 quality columns (runs with a
    # throwaway alt-exporting trace file — opt-in because the alt
    # top_k + export I/O are a measured-perf change)
    regret_args = ["--regret"] if "--regret" in sys.argv else []
    env = dict(os.environ)
    env["PYTHONPATH"] = _repo + os.pathsep + env.get("PYTHONPATH", "")
    if not smoke and "--no-test-gate" not in sys.argv:
        # a round must not publish benchmark numbers over a red suite:
        # run the CI gate first and REFUSE on failure (the tests force
        # the virtual-CPU platform via tests/conftest.py, so this never
        # touches the TPU the measurements need)
        print("bench: running the test gate (pytest -q)...",
              file=sys.stderr)
        try:
            gate = subprocess.run(
                [sys.executable, "-m", "pytest", "tests/", "-q",
                 "--maxfail", "5"],
                capture_output=True, text=True, timeout=3600, env=env,
                cwd=_repo)
        except subprocess.TimeoutExpired:
            print("bench: TEST SUITE TIMED OUT — refusing to benchmark",
                  file=sys.stderr)
            sys.exit(1)
        if gate.returncode != 0:
            print("bench: TEST SUITE RED — refusing to benchmark\n"
                  + gate.stdout[-3000:] + "\n" + gate.stderr[-1500:],
                  file=sys.stderr)
            sys.exit(1)
        print("bench: test gate green", file=sys.stderr)
    results, headline, failed = run_workloads(
        BENCH_WORKLOAD_FNS, ["--scale", scale, *regret_args], env)
    if headline is not None:
        print(json.dumps({
            "metric": "scheduling_throughput_5000nodes_production_path",
            "value": round(headline["pods_per_sec"], 1),
            "unit": "pods/sec",
            "vs_baseline": round(
                headline["pods_per_sec"] / BASELINE_PODS_PER_SEC, 2),
            **{k: headline[k] for k in ROW_DEVICE_KEYS},
            "workloads": results,
        }))
    if failed:
        # every workload was still measured first; a bench with a hole
        # in it must not read as green
        print("bench: FAILED or TIMED OUT: " + ", ".join(failed),
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
