"""Metrics + async recorder + serving endpoints (reference:
metrics/metrics.go:147-335, metric_recorder.go, app/server.go:252)."""

import json
import urllib.request

from kubernetes_tpu.metrics import (
    AsyncRecorder,
    Counter,
    Histogram,
    Registry,
    SchedulerMetrics,
)
from kubernetes_tpu.serving import ServingEndpoints

from kubernetes_tpu.api.objects import (
    Container,
    LABEL_HOSTNAME,
    Node,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    ResourceRequirements,
)
from kubernetes_tpu.config.types import default_config
from kubernetes_tpu.hub import Hub
from kubernetes_tpu.ops.features import Capacities
from kubernetes_tpu.scheduler import Scheduler


def test_histogram_percentiles_and_text():
    h = Histogram("h", "help", buckets=(0.01, 0.1, 1.0), label_names=("r",))
    for _ in range(90):
        h.observe(0.005, r="ok")
    for _ in range(10):
        h.observe(0.5, r="ok")
    assert h.count(r="ok") == 100
    assert h.percentile(50) == 0.01
    assert h.percentile(95) == 1.0


def test_exposition_label_escaping():
    """Prometheus exposition spec: backslash, double quote and line feed
    in label VALUES must be escaped — a failure message or plugin name
    carrying any of them used to emit unparseable exposition text."""
    r = Registry()
    c = r.register(Counter("weird_total", "", ("msg",)))
    c.inc(msg='say "hi" to C:\\temp\nplease')
    text = r.render_text()
    assert ('weird_total{msg="say \\"hi\\" to C:\\\\temp\\nplease"} 1.0'
            in text)
    # no raw newline survives; every inner quote is backslash-escaped
    line = next(ln for ln in text.splitlines()
                if ln.startswith("weird_total"))
    assert "\n" not in line
    inner = line[line.index('="') + 2:line.rindex('"')]
    assert all(inner[i - 1] == "\\" for i, ch in enumerate(inner)
               if ch == '"')


def test_exposition_help_escaping():
    """HELP lines escape backslash and line feed (quotes stay raw)."""
    r = Registry()
    r.register(Counter("h_total", 'multi\nline "help" with \\slash'))
    text = r.render_text()
    assert ('# HELP h_total multi\\nline "help" with \\\\slash' in text)


def test_exposition_histogram_label_escaping():
    r = Registry()
    h = r.register(Histogram("lat", "", buckets=(0.1, 1.0),
                             label_names=("plugin",)))
    h.observe(0.05, plugin='odd"name\\')
    text = r.render_text()
    assert 'plugin="odd\\"name\\\\"' in text
    assert 'le="0.1"' in text


def test_counter_labels():
    c = Counter("c", label_names=("result",))
    c.inc(result="scheduled")
    c.inc(result="scheduled")
    c.inc(result="error")
    assert c.value(result="scheduled") == 2
    assert c.value(result="error") == 1


def test_async_recorder_buffers_until_flush():
    h = Histogram("h")
    c = Counter("c")
    t = [0.0]
    rec = AsyncRecorder(flush_interval=1.0, now=lambda: t[0])
    rec.observe(h, 0.25)
    rec.inc(c, 2.0)
    assert h.total_count() == 0 and c.value() == 0, "buffered"
    n = rec.flush()
    assert n == 2
    assert h.total_count() == 1
    assert c.value() == 2.0
    # non-forced flush respects the interval
    rec.observe(h, 0.25)
    rec.flush(force=True)
    rec.observe(h, 0.25)
    assert rec.flush(force=False) == 0, "interval not elapsed"
    t[0] = 2.0
    assert rec.flush(force=False) == 1


def mknode(i):
    return Node(metadata=ObjectMeta(name=f"node-{i}",
                                    labels={LABEL_HOSTNAME: f"node-{i}"}),
                status=NodeStatus(allocatable={"cpu": "8", "memory": "16Gi",
                                               "pods": "110"}))


def mkpod(name, cpu="100m"):
    return Pod(metadata=ObjectMeta(name=name),
               spec=PodSpec(containers=[Container(
                   name="c", resources=ResourceRequirements(
                       requests={"cpu": cpu}))]))


def _small_sched(hub):
    cfg = default_config()
    cfg.batch_size = 16
    return Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64))


def test_scheduler_records_attempts_and_durations():
    hub = Hub()
    sched = _small_sched(hub)
    hub.create_node(mknode(0))
    pods = [mkpod(f"p{i}") for i in range(5)]
    for p in pods:
        hub.create_pod(p)
    big = mkpod("big", cpu="64")
    hub.create_pod(big)
    sched.run_until_idle()
    m = sched.metrics
    assert m.schedule_attempts.value(
        result="scheduled", profile="default-scheduler") == 5
    assert m.schedule_attempts.value(
        result="unschedulable", profile="default-scheduler") >= 1
    assert m.attempt_duration.count(result="scheduled") == 5
    assert m.batch_duration.total_count() >= 1
    assert m.algorithm_duration.total_count() >= 1
    assert m.extension_point_duration.count(extension_point="Filter") >= 1
    # binder-thread observations land after the recorder flush
    assert m.extension_point_duration.count(extension_point="Bind") >= 1
    assert m.pod_scheduling_attempts.total_count() == 5
    snap = m.registry.snapshot()
    assert "schedule_attempts_total" in snap
    assert "pending_pods" in snap


def test_serving_endpoints():
    hub = Hub()
    sched = _small_sched(hub)
    hub.create_node(mknode(0))
    hub.create_pod(mkpod("p"))
    sched.run_until_idle()
    srv = ServingEndpoints(sched, port=0)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "schedule_attempts_total" in body
        assert 'result="scheduled"' in body
        assert "scheduling_attempt_duration_seconds_bucket" in body
        assert urllib.request.urlopen(
            f"{base}/healthz").read() == b"ok"
        cfg = json.loads(urllib.request.urlopen(
            f"{base}/configz").read().decode())
        assert cfg["batch_size"] == 16
    finally:
        srv.stop()


def test_pending_pods_gauge_live():
    hub = Hub()
    sched = _small_sched(hub)
    # no nodes: the pod parks unschedulable
    hub.create_pod(mkpod("p"))
    sched.run_until_idle()
    gauge = sched.metrics.pending_pods.snapshot()
    assert gauge["{'queue': 'unschedulable'}"] == 1


def test_slow_cycle_line_is_silent_under_the_threshold_and_whole_over_it():
    """CycleTrace.log_if_slow: nothing under the threshold; over it every
    phase of the cycle, each with its CPU seconds beside it where a span
    timed it, and the caller's fields in the head."""
    import logging

    from kubernetes_tpu.utils.tracing import FlightRecorder

    wall, cpu = iter([0.0, 0.08, 1.0, 1.05, 2.0]), iter([0.0, 0.002,
                                                          5.0, 5.04])
    rec = FlightRecorder(now=lambda: next(wall), cpu_now=lambda: next(cpu))
    tr = rec.begin(start=0.0, pods=4)
    with rec.span("device_launch", tr):
        pass
    with rec.span("commit", tr):
        pass
    tr.add("device_compile", 0.07)       # a view: no span, no CPU
    records = []

    class Cap(logging.Handler):
        def emit(self, rec):
            records.append(rec.getMessage())

    log = logging.getLogger("trace-test")
    log.addHandler(Cap())
    log.setLevel(logging.INFO)
    assert tr.log_if_slow(0.13, 1.0, log, pods=4) is False
    assert tr.log_if_slow(0.1, 0.1, log, pods=4) is False
    assert not records, "under the threshold: silent"
    assert tr.log_if_slow(0.13, 0.1, log, pods=4, scheduled=3) is True
    assert records[0].splitlines() == [
        "Trace[schedule_cycle] pods=4 scheduled=3 total=130ms",
        "  - device_launch: 80ms (cpu 2ms)",     # it waited
        "  - commit: 50ms (cpu 40ms)",           # it worked
        "  - device_compile: 70ms"]


def test_slow_cycle_emits_trace(caplog):
    """A scheduling cycle over the 100ms threshold logs the phase trace."""
    import logging

    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.scheduler import Scheduler

    class SlowClock:
        t = 1000.0
        calls = 0

        def now(self):
            # each clock read advances: any measured phase looks slow
            SlowClock.t += 0.05
            return SlowClock.t

    hub = Hub()
    cfg = default_config()
    cfg.batch_size = 16
    sched = Scheduler(hub, cfg, caps=Capacities(nodes=16, pods=64),
                      now=SlowClock().now)
    hub.create_node(mknode(0))
    hub.create_pod(mkpod("p"))
    with caplog.at_level(logging.INFO, logger="kubernetes_tpu.scheduler"):
        sched.run_until_idle()
    slow = [r.getMessage() for r in caplog.records
            if "Trace[schedule_cycle]" in r.getMessage()]
    assert slow and "pods=1 scheduled=1" in slow[0]
    # the cycle's own phases, each with the CPU its span read
    for phase in ("queue_pop", "pack", "device_launch", "commit"):
        assert f"  - {phase}: " in slow[0], phase
    assert "(cpu " in slow[0]
    sched.close()


# suite-tier discipline (tests/test_markers.py): area marker
import pytest  # noqa: E402
pytestmark = pytest.mark.observability


# -------------------------- metrics lint (ISSUE-10 satellite) --------


def test_registry_metric_names_and_labels_conform():
    """Every metric registered in metrics.py obeys the Prometheus
    grammar — name [a-zA-Z_:][a-zA-Z0-9_:]*, labels
    [a-zA-Z_][a-zA-Z0-9_]* — and mirrored-gauge vs true-counter naming
    stays honest (_total only on Counters)."""
    from kubernetes_tpu.metrics import (
        Counter as MCounter,
        Histogram as MHistogram,
        SchedulerMetrics,
    )
    from kubernetes_tpu.telemetry.fleet import (
        LABEL_NAME_RE,
        METRIC_NAME_RE,
    )

    m = SchedulerMetrics()
    for name, metric in m.registry._metrics.items():
        assert METRIC_NAME_RE.match(name), name
        assert name == metric.name
        for ln in getattr(metric, "label_names", ()) or ():
            assert LABEL_NAME_RE.match(ln), f"{name}{{{ln}}}"
        if name.endswith("_total"):
            assert isinstance(metric, MCounter), (
                f"{name}: _total is reserved for true counters")
        if isinstance(metric, MHistogram):
            assert not name.endswith(("_total", "_bucket", "_sum",
                                      "_count")), name


def test_full_exposition_round_trips_strict_parser():
    """The complete /metrics body — histograms, escaped label values,
    callback gauges — re-parses under telemetry.fleet's strict parser
    (locks in the PR-4 escaping fix; the fleet merge ingests this)."""
    from kubernetes_tpu.metrics import SchedulerMetrics
    from kubernetes_tpu.telemetry.fleet import parse_exposition

    m = SchedulerMetrics(pending_fn=lambda: {"activeQ": 3})
    m.schedule_attempts.inc(result='nasty "quotes" and \\slashes\n',
                            profile="default")
    m.phase_duration.observe(0.004, phase="device_launch")
    m.pod_e2e_duration.observe(0.5, attempts="2")
    m.device_compiles.inc(cause="rebucket")
    m.device_live_buffer_bytes.set(1024.0, buffer="cluster")
    # the watchdog/autopsy family (ISSUE-20) rides the same exposition
    m.watchdog_evals.inc()
    m.watchdog_incidents.inc(kind="slo_breach")
    m.watchdog_rules_tripped.inc(rule="slo")
    m.autopsy_bundles.inc(trigger="device_fallback")
    m.autopsy_bundles_dropped.inc(reason="rate_limited")
    m.autopsy_store_bytes.set(2048.0)
    exp = parse_exposition(m.registry.render_text())
    names = {s.name for s in exp.samples}
    assert "scheduler_device_compiles_total" in names
    assert "scheduling_phase_duration_seconds_bucket" in names
    assert "pending_pods" in names
    assert "scheduler_watchdog_evals_total" in names
    assert "scheduler_autopsy_store_bytes" in names
    assert any(s.name == "scheduler_watchdog_incidents_total"
               and s.labels.get("kind") == "slo_breach"
               for s in exp.samples)
    assert any(s.name == "scheduler_autopsy_bundles_total"
               and s.labels.get("trigger") == "device_fallback"
               for s in exp.samples)
    assert any(s.name == "scheduler_autopsy_bundles_dropped_total"
               and s.labels.get("reason") == "rate_limited"
               for s in exp.samples)
    # the nasty label survived the escape/unescape round trip
    assert any(s.labels.get("result") == 'nasty "quotes" and '
               "\\slashes\n" for s in exp.samples)
