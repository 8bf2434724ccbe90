"""Serving endpoints: /metrics, /healthz, /configz, authz-gated /debug.

The slice of the reference's component HTTP surface the scheduler exposes
(cmd/kube-scheduler/app/server.go:252 newHealthEndpointsAndMetricsHandler:
healthz/livez/readyz + /metrics + /configz): a tiny threaded HTTP server
over the metrics Registry and the component config.

Debug endpoints (/debug/cache, /debug/queue, /debug/journal,
/debug/trace, /debug/pod) follow the reference's discipline for its
debugging handlers (server.go:248-255: installed only behind the authz
filter): they are DENIED unless the caller passed a ``debug_auth``
callback, which receives the request's Authorization header value and
returns True to admit. ``token_auth("secret")`` builds the common
bearer-token check.

Flight-recorder surface:
- ``/debug/trace[?n=32]`` — the last-N cycle traces from the always-on
  recorder ring plus per-phase percentiles (p50/p90/p99) and the
  host-tail share.
- ``/debug/pod?name=X[&namespace=ns]`` (or ``?uid=``) — one pod's
  lifecycle timeline (enqueue/pop/bind/park stamps) and its last
  unschedulable diagnosis (which device filter rejected how many nodes,
  which host plugin rejected).
- ``/debug/scorer`` — per-profile learned-scorer state (active
  checkpoint version/fingerprint, learn-loop generation + the regret
  summaries stamped by the promotion gate, reload and load-error
  counts).
"""

from __future__ import annotations

import json
import threading
from dataclasses import asdict, is_dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional
from urllib.parse import parse_qs


def token_auth(token: str) -> Callable[[str], bool]:
    """The usual debug_auth: admit ``Authorization: Bearer <token>``."""
    import hmac

    expect = f"Bearer {token}"

    def check(authorization: str) -> bool:
        return hmac.compare_digest(authorization or "", expect)

    return check


class ServingEndpoints:
    def __init__(self, scheduler, host: str = "127.0.0.1", port: int = 0,
                 debug_auth: Optional[Callable[[str], bool]] = None):
        self.scheduler = scheduler
        sched = scheduler

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # silence per-request stderr spam
                pass

            def _send(self, code: int, body: str,
                      ctype: str = "text/plain; charset=utf-8") -> None:
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _debug(self, path: str, query: dict) -> None:
                # server.go:248-255: debug handlers exist only behind
                # authorization — no callback, no endpoints (403, not
                # 404: the surface is real but the caller is not allowed)
                if debug_auth is None:
                    self._send(403, "debug endpoints disabled "
                                    "(no debug_auth configured)")
                    return
                if not debug_auth(self.headers.get("Authorization", "")):
                    self._send(401, "unauthorized")
                    return
                if path == "/debug/cache":
                    body = json.dumps(sched.cache.dump(), indent=2,
                                      default=str)
                elif path == "/debug/queue":
                    payload = {"pending": sched.queue.pending_counts(),
                               "stats": sched.stats}
                    jq = getattr(sched, "jobqueue", None)
                    if jq is not None and jq.active:
                        # per-tenant job queues + assembling gangs
                        payload["job_queue"] = jq.debug_state()
                    gang = getattr(sched, "_gang", None)
                    if gang is not None:
                        payload["gangs"] = gang.debug_state()
                    payload["waiting_pods"] = {
                        name: [wp.uid for wp in fw.waiting_pods.iterate()]
                        for name, fw in getattr(sched, "frameworks",
                                                {}).items()
                        if len(fw.waiting_pods)}
                    body = json.dumps(payload, indent=2, default=str)
                elif path == "/debug/journal":
                    js_fn = getattr(sched.hub, "get_journal_stats", None)
                    body = json.dumps(js_fn() if js_fn else {}, indent=2,
                                      default=str)
                elif path == "/debug/trace":
                    flight = getattr(sched, "flight", None)
                    if flight is None:
                        self._send(404, "no flight recorder")
                        return
                    try:
                        n = int(query.get("n", ["32"])[0])
                    except ValueError:
                        n = 32
                    prof = getattr(sched, "profiler", None)
                    body = json.dumps({
                        "enabled": flight.enabled,
                        # each cycle carries its "spans": [name, start,
                        # end, thread] of every phase, in ending order
                        "cycles": flight.last(n),
                        # the daemon loop's own spans between the cycles
                        # (LOOP_PHASES + views): [name, start, end,
                        # thread, loop turn]
                        "loop_spans": flight.last_loop_spans(n * 8),
                        # the queue's in-flight event log: entries,
                        # high-water, trims that scanned and their seconds
                        "queue": sched.queue.trim_stats(),
                        # the loop's idle waits by how they ended: a pod
                        # event woke it, or the idle sleep ran out
                        "idle_waits": {
                            end: sched.metrics.loop_idle_waits.value(end=end)
                            for end in ("event", "timeout")},
                        # the mirror's packed-row cache: pods packed
                        # = hits + misses + bypass, clears at its bound
                        "pack_row_cache": sched.mirror.row_cache_stats(),
                        # the pod-table row cache of slots with terms:
                        # slots_packed_terms = hits + misses + bypass
                        "slot_row_cache":
                            sched.mirror.slot_row_cache_stats(),
                        # what the mirror's sync wrote: node rows, and
                        # pod-table slots packed, kept (a confirmation
                        # that changed nothing) and released
                        "mirror_sync": sched.mirror.sync_stats(),
                        "phases": flight.phase_percentiles(),
                        "host_tail_share": round(
                            flight.host_tail_share(), 4),
                        # the device-launch profiler rides the trace
                        # surface: compiles per bucket shape, recompile
                        # causes, resident HBM buffer bytes
                        "device": (prof.snapshot() if prof is not None
                                   else None),
                    }, indent=2, default=str)
                elif path == "/debug/scorer":
                    # learned-scorer state per profile: checkpoint
                    # path/version/fingerprint, learn-loop generation
                    # + promoted-meta regret view, reload + load-error
                    # counts (plugins/learned.py manager stats)
                    payload = {}
                    for name, pcfg in getattr(sched, "_profile_cfg",
                                              {}).items():
                        mgr = (pcfg or {}).get("learned")
                        payload[name] = (mgr.stats() if mgr is not None
                                         else {"enabled": False})
                    body = json.dumps(payload, indent=2, default=str)
                elif path == "/debug/fabric":
                    # control-plane fabric surface: the hub's shard map
                    # + per-shard journal state (ShardedHub), and the
                    # hub client's wire-codec accounting (RemoteHub).
                    # Relay topology/cursors live on each RelayServer's
                    # own token-gated /debug/fabric — relays are their
                    # own processes; the scheduler only sees its hub.
                    payload = {}
                    sm_fn = getattr(sched.hub, "shard_map", None)
                    if sm_fn is not None:
                        try:
                            payload["shard_map"] = sm_fn()
                        except Exception:  # noqa: BLE001 — hub down or
                            pass           # a pre-fabric peer
                    js_fn = getattr(sched.hub, "get_journal_stats",
                                    None)
                    if js_fn is not None:
                        try:
                            js = js_fn()
                        except Exception:  # noqa: BLE001 — hub down
                            js = {}
                        payload["shards"] = js.get("shards", {})
                        payload["journal_rv"] = js.get("rv")
                    rs_fn = getattr(sched.hub, "resilience_stats", None)
                    if rs_fn is not None:
                        s = rs_fn()
                        payload["wire"] = s.get("wire", {})
                        payload["codec"] = s.get("codec")
                    topo_fn = getattr(sched.hub, "fabric_topology",
                                      None)
                    if topo_fn is not None:
                        # replicated state core: who leads, each
                        # replica's term and log/commit indexes (served
                        # through the router's state forwarding; absent
                        # on pre-replica fabrics)
                        try:
                            topo = topo_fn()
                            replicas = topo.get("replicas")
                            if replicas:
                                payload["state_replicas"] = replicas
                            scheds = topo.get("schedulers")
                            if scheds:
                                # scale-out: the live scheduler-replica
                                # registry + slice-ring epoch
                                payload["scheduler_replicas"] = scheds
                                payload["sched_ring_epoch"] = \
                                    topo.get("sched_ring_epoch")
                        except Exception:  # noqa: BLE001 — quorum
                            pass           # mid-election / plain hub
                    sm = getattr(sched, "_slices", None)
                    if sm is not None:
                        # this replica's own slice view: which slots it
                        # drains, under which ring/fencing epochs, and
                        # how many peer-owned pods wait in the pen
                        payload["slices"] = {
                            "identity": sm.identity,
                            "owned_slots": sorted(sm.owned),
                            "ring_epoch": sm.ring_epoch,
                            "fence_epoch": sm.epoch,
                            "generation": sm.generation,
                            "rebalances": sm.rebalances,
                            "foreign_pending": len(
                                getattr(sched, "_foreign", {}))}
                    body = json.dumps(payload, indent=2, default=str)
                elif path == "/debug/fleet":
                    # fleet topology + health: the FleetView collector's
                    # summary (one row per fabric component endpoint,
                    # healthz verdicts + strict-parse scrape errors)
                    fleet = getattr(sched, "fleet", None)
                    if fleet is None:
                        self._send(404, "no fleet view attached")
                        return
                    payload = fleet.summary()
                    # this scheduler's own overload state rides the
                    # fleet view: brownout is exactly the fact an
                    # operator opens /debug/fleet to find
                    bs_fn = getattr(sched, "brownout_state", None)
                    if bs_fn is not None:
                        payload["scheduler_brownout"] = bs_fn()
                    body = json.dumps(payload, indent=2,
                                      default=str)
                elif path == "/debug/autopsy":
                    # incident black boxes: the bundle listing, or one
                    # parsed bundle (?name=). 404 without a store —
                    # capture is opt-in via config.autopsy_dir
                    store = getattr(sched, "autopsy", None)
                    if store is None:
                        self._send(404, "no autopsy store configured "
                                        "(set config.autopsy_dir)")
                        return
                    name = query.get("name", [""])[0]
                    if name:
                        try:
                            payload = store.load(name)
                        except (OSError, ValueError) as e:
                            self._send(404, f"bundle unreadable: {e}")
                            return
                    else:
                        wd = getattr(sched, "watchdog", None)
                        payload = {
                            "dir": store.directory,
                            "incidents": getattr(wd, "incidents", 0),
                            "bundles": store.list(),
                        }
                    body = json.dumps(payload, indent=2, default=str)
                elif path == "/debug/pod":
                    timelines = getattr(sched, "timelines", None)
                    if timelines is None:
                        self._send(404, "no pod timelines")
                        return
                    tl = timelines.get(
                        name=query.get("name", [""])[0],
                        uid=query.get("uid", [""])[0],
                        namespace=query.get("namespace",
                                            ["default"])[0])
                    if tl is None:
                        self._send(404, "pod not found (timelines keep "
                                        "the newest pods only)")
                        return
                    body = json.dumps(tl, indent=2, default=str)
                else:
                    self._send(404, "not found")
                    return
                self._send(200, body, "application/json")

            def do_GET(self):  # noqa: N802 (stdlib API)
                path, _, rawq = self.path.partition("?")
                if path == "/metrics":
                    self._send(200, sched.metrics.registry.render_text())
                elif path == "/metrics/fleet":
                    # the merged fleet exposition: every component's
                    # samples re-labeled with component/shard — one
                    # scrape target for the whole fabric
                    fleet = getattr(sched, "fleet", None)
                    if fleet is None:
                        self._send(404, "no fleet view attached")
                    else:
                        self._send(200, fleet.render_text())
                elif path == "/readyz":
                    # degraded (hub unreachable) = alive but NOT ready:
                    # load balancers should drain, probes should not kill
                    degraded_fn = getattr(sched, "hub_degraded", None)
                    if degraded_fn is not None and degraded_fn():
                        self._send(503, "degraded: hub unreachable")
                    else:
                        self._send(200, "ok")
                elif path in ("/healthz", "/livez"):
                    self._send(200, "ok")
                elif path == "/configz":
                    cfg = sched.config
                    body = json.dumps(
                        asdict(cfg) if is_dataclass(cfg) else str(cfg),
                        indent=2, default=str)
                    self._send(200, body, "application/json")
                elif path.startswith("/debug/"):
                    self._debug(path, parse_qs(rawq))
                else:
                    self._send(404, "not found")

        self._server = ThreadingHTTPServer((host, port), Handler)
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name="ktpu-serving")
        self._thread.start()

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
