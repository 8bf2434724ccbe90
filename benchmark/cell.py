"""One run of one cell: set-up, warm-up, the measured window, the grace
drain, the comparison with the plain reference, the result.

All four cells drive the production entry, `Scheduler.run` on its daemon
thread (what `python -m kubernetes_tpu` calls) over an in-process `Hub`,
with the benchmark's feeder and watcher on the hub's client side. Nothing
about a particular cell lives here: the configuration, the traffic mix, the
templates, the checks and the per-layer metric readers are files found by
the names in BENCHMARK.json.
"""

from __future__ import annotations

import gc
import json
import os
import random
import shutil
import sys
import tempfile
import threading
import time

from benchmark import compare as compare_mod
from benchmark import objects, stats, trace_reduce
from benchmark import traffic as traffic_mod
from benchmark.watch import BindWatcher

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SLICE_S = 3.0
BREAKDOWN_NAMES = 10    # the contract's limit on each list of `breakdown`
NOT_DEVICE = ".cpu_rehearsal_not_a_device_number"

clock = time.perf_counter


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ manifest


def load_manifest(repo: str = REPO) -> dict:
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: dict, workload: str) -> tuple[dict, dict]:
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == workload), None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    return cell, config


def metrics_of(manifest: dict, section: str, workload: str) -> list[dict]:
    """The metrics of one section that this cell reports: those that list
    it under `workloads`, and those that list none."""
    return [m for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


def load_config(entry: dict, rehearse: bool, repo: str = REPO) -> dict:
    with open(os.path.join(repo, entry["file"])) as f:
        cfg = json.load(f)
    if rehearse:
        cfg.update(cfg["rehearse"])
    return cfg


def load_reader(name: str, root: str = HERE):
    """The per-layer metric's reader: benchmark/layer_metrics/<name>.py,
    a module with `read(obs) -> number or None`."""
    return compare_mod.load_by_name("layer_metrics", name, root).read


def device_peaks(kind: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


# ------------------------------------------------------------ observers


class ProcessObserver:
    """Process-wide observations the window reads: every program JAX
    compiles (or fetches from its persistent cache), by its own
    `jax.monitoring` listener, and every collector pause, by `gc.callbacks`.
    It observes only: no collector policy is set. JAX's listeners cannot be
    unregistered, so one instance serves a process."""

    _instance = None

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles: list[tuple[float, str, float]] = []  # t, name, secs
        self.gc_pauses: list[tuple[float, float, int]] = []  # t, secs, gen
        self._gc_t0 = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    @classmethod
    def get(cls) -> "ProcessObserver":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append((clock(), str(kw.get("fun_name", "?")),
                                  secs))

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = clock()
        else:
            t = clock()
            self.gc_pauses.append((self._gc_t0, t - self._gc_t0,
                                   info.get("generation", -1)))

    def watch_gc(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def unwatch_gc(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


class PhaseSpans:
    """Traced runs only: the loop thread's flight-recorder phases as spans
    on the host clock, taken around CycleTrace.add from the benchmark's
    side (the program records durations, not instants)."""

    def __init__(self) -> None:
        self.spans: list[tuple[str, float, float]] = []
        self.thread_id: int | None = None
        self._orig = None

    def install(self) -> None:
        from kubernetes_tpu.utils.tracing import CycleTrace, FlightRecorder

        spans, me = self.spans, self

        def note(phase: str, secs: float) -> None:
            if secs > 0 and threading.get_ident() == me.thread_id:
                t = time.time_ns()
                spans.append((phase, t - secs * 1e9, float(t)))

        add, observe = CycleTrace.add, FlightRecorder.observe_phase
        self._orig = (add, observe)

        def traced_add(tr, phase: str, secs: float) -> None:
            note(phase, secs)
            add(tr, phase, secs)

        def traced_observe(fl, phase: str, secs: float) -> None:
            note(phase, secs)       # phases outside a cycle: binder_drain,
            observe(fl, phase, secs)  # eviction_flush, host_fallback

        CycleTrace.add = traced_add
        FlightRecorder.observe_phase = traced_observe

    def remove(self) -> None:
        if self._orig is not None:
            from kubernetes_tpu.utils.tracing import (
                CycleTrace, FlightRecorder)

            CycleTrace.add, FlightRecorder.observe_phase = self._orig
            self._orig = None


def pod_table_slots(sched) -> tuple[int, int] | None:
    """(slots in use, capacity) of the mirror's pod table as it stands:
    `caps.pods` less the free slots; None where the program keeps no such
    list. The scheduler's, not the one the run started with: `_grow`
    replaces the mirror."""
    mirror = getattr(sched, "mirror", None)
    free = getattr(mirror, "_free_slots", None)
    if free is None:
        return None
    capacity = int(mirror.caps.pods)
    return capacity - len(free), capacity


def phase_sums(sched) -> dict[str, float]:
    """Seconds per flight-recorder phase so far (the phase histogram's
    sums; a cycle lands there when it is recorded)."""
    out = {}
    for key, rec in sched.metrics.phase_duration.snapshot().items():
        phase = key.split("'")[3] if key.count("'") >= 4 else key
        out[phase] = rec["sum"]
    return out


# ------------------------------------------------------------ the run


def check_device(chips: int, rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} x {devs[0].platform}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def init_groups(cfg: dict) -> list[dict]:
    """The configuration's init pods as groups {"count", "template"}, in
    the order they are created; one group may stand as a dictionary."""
    init = cfg["init_pods"]
    return [init] if isinstance(init, dict) else list(init)


def build_cluster(cfg: dict, seed: int, fault=None, pod_templates=()):
    """Hub + production Scheduler, nodes in the seed's order, the
    namespaces that the init pods' templates and `pod_templates` (the
    mix's) name, the init pods group by group, already bound on the seed's
    nodes in one continued round over them. `fault` is a module of
    benchmark/faults/, for the control. Returns (hub, sched, token)."""
    from kubernetes_tpu.api.objects import Namespace, ObjectMeta
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.ops.features import Capacities
    from kubernetes_tpu.scheduler import Scheduler

    rng = random.Random(seed)
    token = f"{seed & 0xffffffff:08x}"
    hub = Hub(journal_capacity=int(cfg["hub"]["journal_capacity"]))
    node_tmpl = objects.load_template(cfg["nodes"]["template"])
    zones = list(cfg["nodes"].get("zones") or [])
    n_nodes = int(cfg["nodes"]["count"])
    nodes = [objects.make_node(node_tmpl, i, zones) for i in range(n_nodes)]
    if hasattr(fault, "wrap_hub"):   # before the Scheduler takes hub.bind
        fault.wrap_hub(hub, [n.metadata.name for n in nodes],
                       {n.metadata.name: n.metadata.labels[objects.ZONE_KEY]
                        for n in nodes if objects.ZONE_KEY in n.metadata.labels})
    sc = default_config()
    sc.batch_size = int(cfg["scheduler"]["batch_size"])
    sc.tie_break_seed = seed & 0xffffffff
    sched = Scheduler(hub, sc, caps=Capacities(
        **{k: int(v) for k, v in cfg["capacities"].items()}))
    if hasattr(fault, "after_scheduler"):
        fault.after_scheduler(sched)
    order = list(range(n_nodes))
    rng.shuffle(order)
    for i in order:
        hub.create_node(nodes[i])
    groups = [(int(g["count"]), objects.load_template(g["template"]))
              for g in init_groups(cfg)]
    spaces = {ns for tmpl in [*(t for _n, t in groups), *pod_templates]
              for ns in objects.template_namespaces(tmpl)}
    for ns in sorted(spaces):
        hub.create_namespace(Namespace(
            metadata=ObjectMeta(name=ns, uid=f"ns-{ns}")))
    homes = list(range(n_nodes))
    rng.shuffle(homes)
    made = 0
    for count, tmpl in groups:
        maker = objects.PodMaker(tmpl)
        for i in range(made, made + count):
            hub.create_pod(maker.make(
                f"init-{token}-{i}",
                node_name=nodes[homes[i % n_nodes]].metadata.name))
        made += count
    return hub, sched, token


def _wait(predicate, timeout: float, poll: float = 0.01) -> bool:
    deadline = clock() + timeout
    while not predicate():
        if clock() > deadline:
            return False
        time.sleep(poll)
    return True


def run_cell(workload: str, seed: int, seconds: int, trace: bool,
             rehearse: bool = False, fault: str | None = None,
             t_process: float | None = None, repo: str = REPO,
             keep_trace: str | None = None,
             log=lambda msg: print(f"[bench] {msg}", file=sys.stderr,
                                   flush=True)) -> dict:
    """Run the cell once; returns the result line as a dict. `fault` names
    a file of benchmark/faults/ to plant under the timed path."""
    t_enter = clock()
    t_process = t_enter if t_process is None else t_process
    manifest = load_manifest(repo)
    cell, cfg_entry = find_cell(manifest, workload)
    cfg = load_config(cfg_entry, rehearse, repo)
    mix = traffic_mod.load_mix(cell["traffic"], rehearse)
    e2e = metrics_of(manifest, "end_to_end", workload)
    layer = metrics_of(manifest, "per_layer", workload)
    readers = {m["name"]: load_reader(m["name"]) for m in layer}

    from kubernetes_tpu.utils import jaxsetup

    jaxsetup.setup()   # compile cache: where the environment says, else
    #                    the fixed <checkout>/.jax_cache
    device = check_device(int(cell["chips"]), rehearse)
    if device["platform"] == "tpu":
        device_peaks(device["kind"])     # an unknown device is an error
    t_device = clock()
    observer = ProcessObserver.get()
    spans = PhaseSpans()
    if trace:
        spans.install()

    fault_mod = compare_mod.load_by_name("faults", fault) if fault else None
    check_tmpl = objects.load_template(mix["pod_template"])
    pod_tmpl = objects.load_template(fault_mod.pod_template(mix)) \
        if hasattr(fault_mod, "pod_template") else check_tmpl
    hub, sched, token = build_cluster(cfg, seed, fault_mod,
                                      [check_tmpl, pod_tmpl])
    t_cluster = clock()
    maker = objects.PodMaker(pod_tmpl)
    watcher = BindWatcher(clock)
    watcher.attach(hub)
    batch = int(cfg["scheduler"]["batch_size"])
    observer.watch_gc()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = None
    feeder = None
    try:
        sched.start()
        spans.thread_id = sched._daemon.ident
        from kubernetes_tpu.models.pipeline import launch_cache_size

        def _binds_lost(_want: int) -> bool:
            # the scheduler counts a pod scheduled once its bind returned,
            # and the watcher saw that bind inside the call: a scheduler
            # that counts more than the hub has seen is losing binds, and
            # the run goes on to be found not correct instead of waiting
            return sched.stats["scheduled"] > watcher.bound_count()

        # ---------------- warm-up, untimed, with the cell's own traffic
        if mix["kind"] == "backlog":
            slab = batch if mix["slab"] == "batch_size" else int(mix["slab"])
            feeder = traffic_mod.BacklogFeeder(
                hub, lambda i: maker.make(f"m-{token}-{i}"),
                watcher.bound_count, int(mix["depth"]), slab, clock)
            feeder.start()
            warm_pods = int(mix["warm_pods_batches"]) * batch
            if not _wait(lambda: watcher.bound_count() >= warm_pods
                         or feeder.error or _binds_lost(warm_pods), 1100):
                raise RuntimeError("warm-up bound too few pods")
            t_first = min(watcher.first.values()) if watcher.first else clock()
            _wait(lambda: clock() - t_first >= float(mix["warm_seconds"]),
                  60)
            # a program still arriving from the compiler means the warm-up
            # is not over: wait until a second passes without one
            _wait(lambda: not observer.compiles
                  or clock() - observer.compiles[-1][0] > 1.0, 30)
            t0 = clock()
        else:
            schedule = traffic_mod.arrival_schedule(mix, seconds)
            pre = [maker.make(f"w-{token}-{i}")
                   for i in range(int(mix["prewarm_pods"]))]
            pods = [maker.make(f"m-{token}-{i}")
                    for i in range(sum(n for _o, n in schedule))]
            for p in pre:     # one group first, so that the launch program
                hub.create_pod(p)   # is compiled before the schedule runs
            if not _wait(lambda: watcher.bound_count() >= len(pre)
                         or _binds_lost(len(pre)), 1100):
                raise RuntimeError("pre-warm pods did not bind")
            n_pre = len(pre)
            t0 = clock() + 0.25 - schedule[0][0]
            feeder = traffic_mod.ArrivalGenerator(
                hub, pods, schedule, t0,
                lambda: watcher.bound_count() - n_pre, clock)
            feeder.start()
            time.sleep(max(0.0, t0 - clock()))
        # ---------------- the window
        setup_s = t0 - t_process
        phases0, launches0 = phase_sums(sched), sched.profiler.launches
        cache0 = launch_cache_size()
        log(f"window opens: setup_s={setup_s:.3f} bound={watcher.bound_count()}")
        slice_s = min(float(mix.get("trace_slice_s", TRACE_SLICE_S)),
                      seconds * 0.6)
        slice_at = t0 + seconds - slice_s - min(0.5, seconds / 10.0)
        slice_info: dict = {}
        if trace:
            tracer = threading.Thread(
                target=_trace_slice, name="bench-tracer", daemon=True,
                args=(trace_dir, slice_at, slice_s, slice_info))
            tracer.start()
        time.sleep(max(0.0, t0 + seconds - clock()))
        t1 = t0 + seconds
        phases1, launches1 = phase_sums(sched), sched.profiler.launches
        cache1 = launch_cache_size()
        bound_at_close = watcher.bound_count()
        table_at_close = pod_table_slots(sched)
        # ---------------- grace drain, outside the window
        withdrawn: set[str] = set()
        if mix["kind"] == "backlog":
            feeder.stop()
            # the controller cancels what is still pending, so a run does
            # not pay a drain of `depth` pods after every window: those
            # pods were never due. The oldest `keep_oldest` of them stay
            # and have to bind: the queue is first in, first out, so a pod
            # the scheduler lost is among them and shows as unbound
            pending = [u for u in feeder.offered if u not in watcher.first]
            withdrawn = set(hub.delete_pods(
                pending[int(mix.get("keep_oldest", mix["depth"])):]))
        else:
            feeder.join(timeout=5)
            feeder.stop()
        if feeder.error is not None:
            raise feeder.error
        offered = [u for u in feeder.offered if u not in withdrawn]
        extra = int(mix.get("prewarm_pods", 0)) \
            if mix["kind"] == "arrivals" else 0
        want = len(offered) + extra
        last = [watcher.bound_count(), clock()]
        stall_s = 3.0 if rehearse else 15.0

        def drained() -> bool:
            n = watcher.bound_count()
            if n != last[0]:
                last[0], last[1] = n, clock()
            return n >= want or clock() - last[1] > stall_s

        _wait(drained, float(mix["grace_seconds"]))
        t_end = clock()
        table_at_end = pod_table_slots(sched)
        if tracer is not None:
            tracer.join(timeout=120)
        import jax

        mem = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    finally:
        observer.unwatch_gc()
        spans.remove()
        if feeder is not None and feeder.is_alive():
            feeder.stop()
        sched.close()

    # ---------------- what the window read
    bind_times = sorted(watcher.first.values())
    in_window = sum(1 for t in bind_times if t0 <= t < t1)
    obs = {
        "seconds": seconds, "bound_in_window": in_window,
        "phase_s": {k: phases1.get(k, 0.0) - phases0.get(k, 0.0)
                    for k in phases1},
        "launches": launches1 - launches0,
        "launch_cache_delta": cache1 - cache0,
        "compiles": [(n, s) for t, n, s in observer.compiles
                     if t0 <= t < t1],
        "gc_pauses_ms": [(s * 1e3, g) for t, s, g in observer.gc_pauses
                         if t0 <= t < t1],
        "trace": None,
    }
    if table_at_close is not None and table_at_end is not None:
        # at the window's close and after the grace drain, in which the
        # pods kept at the close bind: a full table there is a _grow too
        obs["pod_table"] = {
            "capacity": table_at_end[1], "in_use_at_close": table_at_close[0],
            "in_use_at_end": table_at_end[0]}
    values: dict[str, float] = {"setup_s": setup_s}
    if mix["kind"] == "backlog":
        values["pods_per_s"] = stats.rate_in_window(bind_times, t0, seconds)
        failed_in_window = 0
    else:
        samples, failed_in_window = stats.wait_samples_ms(
            feeder.due, watcher.first, t0, seconds, t_end)
        obs["bind_ms"] = samples
        obs["late_ms"] = sorted(
            (feeder.sent[u] - d) * 1e3 for u, d in feeder.due.items()
            if t0 <= d < t1 and u in feeder.sent)
        values["bind_p95_ms"] = stats.percentile(samples, 95)
        values["bind_p50_ms"] = stats.percentile(samples, 50)
        # completed: pods due in the window whose bind landed inside it
        values["pods_per_s"] = stats.rate_in_window(
            [watcher.first[u] for u, d in feeder.due.items()
             if t0 <= d < t1 and u in watcher.first], t0, seconds)
        depth = feeder.depth_samples
        in_win = [(o, d) for o, d in depth if o >= 0]
        period = float(mix["burst_period_s"])
        obs["pending"] = {
            "after_first_burst": next(
                (d for o, d in in_win if o > 0), None),
            "at_end": in_win[-1][1] if in_win else None,
            "before_bursts": [d for o, d in in_win
                              if abs(o / period - round(o / period)) < 1e-9],
            "max": max((d for _o, d in in_win), default=None)}

    # ---------------- the trace, where one was taken
    breakdown = None
    if trace:
        red = _reduce_trace(trace_dir, slice_info, spans.spans, keep_trace,
                            log)
        if red is not None:
            a, b = slice_info["t0"], slice_info["t1"]
            red["pods_bound"] = sum(1 for t in bind_times if a <= t < b)
            obs["trace"] = red
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            # the contract's breakdown keeps ten names a list; the thirty
            # the reduction keeps go into the diag line, for the builder
            breakdown = {"device_ops": red["device_ops"][:BREAKDOWN_NAMES],
                         "idle_gaps": red["idle_gaps"][:BREAKDOWN_NAMES]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---------------- the comparison with the plain reference
    checks = list(cfg["checks"]) + [c for c in mix.get("checks", [])
                                    if c not in cfg["checks"]]
    compared = compare_mod.compare(
        compare_mod.EndState(hub, sched, check_tmpl, offered, watcher,
                             device["platform"]), checks)
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    if trace and not device.get("busy_s"):
        correct = False
        compared["device_busy_missing"] = {"value": 1, "limit": 0}
    failed = max(compared.get("unbound", {"value": 0})["value"],
                 failed_in_window)

    if trace:
        metrics = {}
        for m in layer:
            v = readers[m["name"]](obs)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in e2e if values.get(m["name"]) is not None}
    if rehearse:
        metrics = {k + NOT_DEVICE: v for k, v in metrics.items()}
    t_first_bind = min(watcher.first.values(), default=t0)
    diag = {
        # where set-up went: interpreter and imports, JAX and the device,
        # hub + scheduler + nodes + init pods, first launch (compile or
        # cache load) to the first bind, warm-up
        "setup_parts_s": {
            "process_to_main": round(t_enter - t_process, 2),
            "jax_and_device": round(t_device - t_enter, 2),
            "cluster": round(t_cluster - t_device, 2),
            "to_first_bind": round(t_first_bind - t_cluster, 2),
            "warm_up": round(t0 - t_first_bind, 2)},
        "t_window_s": seconds, "bound_in_window": in_window,
        "bound_at_close": bound_at_close, "offered": len(offered),
        "withdrawn": len(withdrawn),
        "grace_s": t_end - t1, "launches": obs["launches"],
        "pod_table": obs.get("pod_table"),
        "launch_cache_delta": obs["launch_cache_delta"],
        "compiles_in_window": [[n, round(t - t0, 2), round(s, 3)]
                               for t, n, s in observer.compiles
                               if t0 <= t < t1],
        "compiles_before_window": len(
            [1 for t, _n, _s in observer.compiles if t < t0]),
        "gc_pauses": len(obs["gc_pauses_ms"]),
        "gc_pause_max_ms": max((p for p, _g in obs["gc_pauses_ms"]),
                               default=0.0),
        "gc_gen2": sum(1 for _p, g in obs["gc_pauses_ms"] if g == 2),
    }
    if obs["trace"]:
        diag["device_ops"] = obs["trace"]["device_ops"]   # all thirty
    if mix["kind"] == "arrivals":
        s = obs["bind_ms"]
        diag.update(
            samples=len(s), bind_p50_ms=stats.percentile(s, 50),
            bind_p95_ms=stats.percentile(s, 95),
            bind_p99_ms=stats.percentile(s, 99),
            bind_max_ms=s[-1] if s else None,
            late_p99_ms=stats.percentile(obs["late_ms"], 99),
            pending=obs["pending"],
            burst_worst_ms=_burst_worst_ms(feeder.due, watcher.first, t0,
                                           seconds, mix, t_end),
            gc_long=[[round(t - t0, 3), round(s * 1e3, 1), g]
                     for t, s, g in observer.gc_pauses
                     if t0 <= t < t1 and s >= 0.02])
    result = {"correct": bool(correct), "attempted": len(offered),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared    # the contract's keys, and no other
    log("diag " + json.dumps(diag))  # what the builder reads, not the driver
    return result


def _burst_worst_ms(due, bound, t0, seconds, mix, t_end) -> list[float]:
    """The longest wait among the pods of each burst instant: how long
    each known burst took to drain."""
    period = float(mix["burst_period_s"])
    worst: dict[int, float] = {}
    for uid, t_due in due.items():
        k = (t_due - t0) / period
        if 0 <= t_due - t0 < seconds and abs(k - round(k)) < 1e-6:
            w = (bound.get(uid, t_end) - t_due) * 1e3
            worst[round(k)] = max(worst.get(round(k), 0.0), w)
    return [round(worst[k], 1) for k in sorted(worst)]


def _trace_slice(trace_dir: str, at: float, length: float,
                 info: dict) -> None:
    """Trace `length` seconds from instant `at`, with a marker that ties
    the trace's clock to the host's."""
    import jax

    time.sleep(max(0.0, at - clock()))
    # the device's operations and the runtime's own host events only: the
    # Python tracer costs the loop thread tens of percent
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace_reduce.SYNC_EVENT):
        info["sync_wall_ns"] = time.time_ns()
    info["t0"], info["t0_wall_ns"] = clock(), time.time_ns()
    time.sleep(length)
    info["t1"], info["t1_wall_ns"] = clock(), time.time_ns()
    jax.profiler.stop_trace()


def _reduce_trace(trace_dir, info, host_spans, keep_trace, log):
    path = trace_reduce.find_xplane(trace_dir)
    if path is None or "t1" not in info:
        log("no trace file was written")
        return None
    if keep_trace:
        os.makedirs(keep_trace, exist_ok=True)
        shutil.copy(path, os.path.join(keep_trace, "slice.xplane.pb"))
        with open(os.path.join(keep_trace, "slice.json"), "w") as f:
            json.dump({"info": info, "spans": host_spans[:200000]}, f)
    tr = trace_reduce.read_xplane(path)
    # host wall clock -> the trace's clock
    if tr["start_wall_ns"] is not None:
        shift = -float(tr["start_wall_ns"])
    elif tr["sync_ns"] is not None:
        shift = tr["sync_ns"] - info["sync_wall_ns"]
    else:
        log("the trace has neither a start time nor a clock-sync marker")
        return None
    return trace_reduce.reduce_events(
        tr, info["t0_wall_ns"] + shift, info["t1_wall_ns"] + shift,
        [(p, a + shift, b + shift) for p, a, b in host_spans])
