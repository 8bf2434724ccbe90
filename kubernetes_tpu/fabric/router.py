"""The stateless bin1 router: the fabric's single-hub face.

One (or many — it holds no state beyond connection handles and a TTL'd
ring cache) process speaking hubserver's exact wire in front of the
shard processes:

* ``POST /call`` — the inherited hubserver handler, dispatching into a
  :class:`~kubernetes_tpu.fabric.cluster.ClusterClient`: by-kind verbs
  go whole to their shard, pod verbs route on the namespace-crc32
  ring, ``rv.*``/``leases.*`` go to the shared-state shard. Codec
  negotiation, typed errors, and retries are the stock machinery.
* ``GET /watch`` — a **pass-through merge**: one upstream stream per
  owning shard (``≤ (router watch connections)`` sockets per shard
  process, however many clients hang downstream of the relay tree),
  every event re-framed with its source-shard tag (``sh``), and ONE
  downstream sync marker once every upstream has synced, carrying the
  per-shard sync map. With ``cursors=`` the router dials each shard at
  that shard's own resume point — the composite-cursor discipline that
  makes cross-shard resume exact (see fabric.cluster's module doc).
  The router never buffers or heals streams: an upstream dying cuts
  the downstream, whose client resumes; statelessness IS the
  availability story.
* ``GET /topology`` — the served relay/router/shard map (open, cached
  briefly): clients and relays discover and re-parent through it
  instead of being pointed by flag.
"""

from __future__ import annotations

import http.client
import json
import queue
import threading
import time
import urllib.error
import urllib.request

from kubernetes_tpu.fabric import codec as binwire
from kubernetes_tpu.fabric.cluster import ClusterClient
from kubernetes_tpu.fabric.flowcontrol import watch_priority
from kubernetes_tpu.hub import NotFound, TooManyRequests
from kubernetes_tpu.hubserver import (
    FRAMES_CONTENT_TYPE,
    _Handler,
    make_stream_writers,
    parse_watch_query,
)


class _RouterHandler(_Handler):
    server_version = "kubernetes-tpu-router/1"

    # do_POST is inherited: self.hub is the ClusterClient, which is
    # Hub-shaped — /call routing IS the facade's routing.

    @property
    def cluster(self) -> ClusterClient:
        return self.server.hub  # type: ignore[attr-defined]

    def do_GET(self) -> None:  # noqa: N802
        from urllib.parse import parse_qs, urlparse

        parsed = urlparse(self.path)
        path = parsed.path
        if path in ("/healthz", "/livez"):
            self._text(200, "ok")
            return
        if path == "/metrics":
            from kubernetes_tpu.telemetry.fleet import (
                hub_metrics_text,
                process_identity_text,
            )

            body = process_identity_text(
                "router", self.server.server_address[1]) \
                + hub_metrics_text(self.cluster)
            flow = getattr(self.server, "flow", None)
            if flow is not None:
                body += flow.metrics_text()
            self._text(200, body)
            return
        if path == "/topology":
            topo = self.server.topology()  # type: ignore[attr-defined]
            self._json(200, topo)
            return
        if path != "/watch":
            self._json(404, {"error": "NotFound", "message": self.path})
            return
        q = parse_qs(parsed.query)
        params, err = parse_watch_query(
            q, self.server.codecs)  # type: ignore[attr-defined]
        if params is None:
            self._json(400, {"error": "ValueError", "message": err})
            return
        srv = self.server
        limit = getattr(srv, "watch_limit", None)
        if limit is None:
            self._watch_passthrough(params)
            return
        # admission before the expensive part: each passthrough opens
        # one upstream socket per owning shard, so NEW best-effort
        # subscriptions shed at the bound — existing streams (and any
        # attributed priority) are never cut to make room
        priority = watch_priority(q.get("identity", [""])[0])
        with srv.watch_lock:                # type: ignore[attr-defined]
            if priority == "best-effort" \
                    and srv.watch_active >= limit:
                srv.watch_sheds += 1
                shed = True
            else:
                srv.watch_active += 1
                shed = False
        if shed:
            e = TooManyRequests(
                "router watch capacity: best-effort subscriptions "
                "shed", retry_after=1.0)
            self._json(429, {"error": "TooManyRequests",
                             "message": str(e)},
                       headers={"Retry-After":
                                f"{e.retry_after:.3f}"})
            return
        try:
            self._watch_passthrough(params)
        finally:
            with srv.watch_lock:            # type: ignore[attr-defined]
                srv.watch_active -= 1

    # ------------- the pass-through merge -------------

    def _dial_upstreams(self, params):
        """One upstream /watch per owning shard, each multiplexed over
        that shard's subset of the requested kinds and resumed at that
        shard's cursor. Returns [(shard, response)] or raises with the
        downstream answer already sent."""
        cluster = self.cluster
        try:
            targets = cluster.watch_targets(list(params.kinds))
        except NotFound as e:
            self._json(400, {"error": "ValueError", "message": str(e)})
            return None
        opened: list[tuple[str, object]] = []
        try:
            for shard, kinds in sorted(targets.items()):
                base = cluster.shard_url(shard)
                url = f"{base}/watch?kinds={','.join(kinds)}"
                since = None
                if params.cursors is not None:
                    since = params.cursors.get(shard, params.since_rv)
                elif params.since_rv is not None:
                    since = params.since_rv
                if since is not None:
                    url += f"&since_rv={since}"
                else:
                    url += f"&replay={'1' if params.replay else '0'}"
                url += (f"&codec={binwire.CODEC_BINARY}"
                        f"&fp={binwire.registry_fingerprint()}")
                opened.append((shard, urllib.request.urlopen(
                    url, timeout=30.0)))
            return opened
        except urllib.error.HTTPError as e:
            for _, r in opened:
                self._close_quiet(r)
            if e.code == 410:
                try:
                    payload = json.loads(e.read())
                except (ValueError, OSError):
                    payload = {}
                self._json(410, {
                    "error": "RvTooOld",
                    "message": payload.get("message", "compacted"),
                    "compacted_rv": payload.get("compacted_rv", 0)})
            else:
                try:
                    body = e.read().decode("utf-8", "replace")[:200]
                except OSError:
                    body = ""
                self._json(502, {"error": "Upstream",
                                 "message": f"shard HTTP {e.code}: "
                                            f"{body}"})
            self._close_quiet(e)
            return None
        except OSError as e:
            for _, r in opened:
                self._close_quiet(r)
            # the shard may have restarted on a new port: refresh the
            # registry so the CLIENT'S retry dials the fresh URL
            try:
                cluster.refresh_shards()
            except Exception:  # noqa: BLE001 — state shard down too
                pass
            self._json(503, {"error": "Unavailable",
                             "message": f"shard unreachable: {e}"})
            return None

    @staticmethod
    def _close_quiet(resp) -> None:
        try:
            resp.close()
        except OSError:
            pass

    def _watch_passthrough(self, params) -> None:
        upstreams = self._dial_upstreams(params)
        if upstreams is None:
            return
        events: queue.Queue = queue.Queue(maxsize=100000)
        _DONE = object()

        def read_upstream(shard: str, resp) -> None:
            """Decode one shard's stream into the merge queue. Values
            pass through UNTOUCHED (bin1 frames decode to real objects,
            JSON lines to wire dicts — the downstream writer and every
            client's from_wire accept either), so the router never pays
            an object re-materialization."""
            try:
                ctype = resp.headers.get("Content-Type", "")
                if ctype.startswith(FRAMES_CONTENT_TYPE):
                    while True:
                        payload = binwire.read_frame(resp)
                        if payload is None:
                            return
                        events.put((shard, binwire.decode(payload)))
                else:
                    for raw in resp:
                        line = raw.strip()
                        if line:
                            events.put((shard, json.loads(line)))
            except (OSError, ValueError, AttributeError,
                    http.client.HTTPException):
                # a shard dying mid-frame surfaces IncompleteRead (an
                # HTTPException) from the exact-length frame read —
                # the same error classes hubclient's consume() handles
                pass
            finally:
                events.put((shard, _DONE))

        readers = [threading.Thread(target=read_upstream, args=(s, r),
                                    daemon=True,
                                    name=f"router-watch-{s}")
                   for s, r in upstreams]
        for t in readers:
            t.start()

        self.send_response(200)
        self.send_header("Content-Type",
                         FRAMES_CONTENT_TYPE if params.use_bin
                         else "application/jsonlines")
        if params.use_bin:
            self.send_header(binwire.WIRE_HEADER, binwire.offer())
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        write_obj, write_event = make_stream_writers(
            self.wfile, params.use_bin, params.mux)

        synced: dict[str, int] = {}
        sync_sent = False
        last_write = time.monotonic()
        try:
            while not self.server.stopping:  # type: ignore[attr-defined]
                # time-based keepalive: upstream keepalives arrive once
                # per shard per second and are swallowed below, so the
                # queue-empty branch alone would never fire — and a
                # silent downstream wedges its client's close() and
                # dead-peer detection
                if time.monotonic() - last_write >= 1.0:
                    write_obj({})
                    last_write = time.monotonic()
                try:
                    shard, ev = events.get(timeout=1.0)
                except queue.Empty:
                    continue
                if ev is _DONE:
                    # a shard stream died (kill -9, restart, cut): a
                    # partial fabric stream must never masquerade as a
                    # complete one — cut downstream, the client resumes
                    # with its per-shard cursors
                    return
                if not ev:
                    continue                 # upstream keepalive
                if ev.get("synced"):
                    if shard not in synced:
                        synced[shard] = ev.get("rv") or 0
                        if not sync_sent and len(synced) == len(upstreams):
                            # every shard's replay (LIST or journal
                            # suffix) has drained: one merged marker,
                            # carrying the per-shard cursor seeds
                            write_obj({"synced": True,
                                       "rv": max(synced.values(),
                                                 default=0),
                                       "shards": dict(synced)})
                            sync_sent = True
                            last_write = time.monotonic()
                    continue
                # replay events flow through BEFORE the merged sync
                # marker; clients treat a resumed stream's pre-sync
                # events as ordinary incremental events and a replay's
                # as LIST entries — exactly the single-hub contract
                write_event(ev.get("kind") or params.kinds[0],
                            ev.get("type"), ev.get("rv") or 0,
                            ev.get("old"), ev.get("new"),
                            ev.get("trace"), shard)
                last_write = time.monotonic()
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass
        finally:
            for _, r in upstreams:
                self._close_quiet(r)


class RouterServer:
    """``RouterServer(state_url).start()`` → the fabric's single-hub
    wire on ``address``; point RemoteHub clients, relays, schedulers,
    and kubemark feeders at it."""

    def __init__(self, state_url: str, host: str = "127.0.0.1",
                 port: int = 0, name: str = "router-0",
                 codecs: tuple[str, ...] = (binwire.CODEC_BINARY,
                                            binwire.CODEC_JSON),
                 cluster: ClusterClient | None = None,
                 topology_ttl_s: float = 1.0,
                 flow=None, watch_limit: int | None = None):
        import os

        from http.server import ThreadingHTTPServer

        self.cluster = cluster or ClusterClient(state_url)
        self.name = name
        self.flow = flow
        self._httpd = ThreadingHTTPServer((host, port), _RouterHandler)
        self._httpd.daemon_threads = True
        self._httpd.hub = self.cluster        # type: ignore[attr-defined]
        self._httpd.codecs = codecs           # type: ignore[attr-defined]
        self._httpd.stopping = False          # type: ignore[attr-defined]
        # flow control: ``flow`` bounds /call admission (the inherited
        # hubserver handler reads it); ``watch_limit`` bounds live
        # passthrough streams — past it, new best-effort watch
        # subscriptions answer 429 (None = legacy unbounded)
        self._httpd.flow = flow               # type: ignore[attr-defined]
        self._httpd.watch_limit = watch_limit  # type: ignore[attr-defined]
        self._httpd.watch_active = 0          # type: ignore[attr-defined]
        self._httpd.watch_sheds = 0           # type: ignore[attr-defined]
        self._httpd.watch_lock = threading.Lock()  # type: ignore[attr-defined]
        self._httpd.topology = self._topology  # type: ignore[attr-defined]
        self._topo_cache: tuple[float, dict] | None = None
        self._topo_ttl = topology_ttl_s
        self._topo_lock = threading.Lock()
        self._thread: threading.Thread | None = None
        # announce ourselves so the topology map names the router(s)
        try:
            self.cluster.state.fabric_register_router(
                name, self.address, os.getpid())
        except Exception:  # noqa: BLE001 — the state shard may still be
            pass           # coming up; registration is best-effort

    def _topology(self) -> dict:
        now = time.monotonic()
        with self._topo_lock:
            if self._topo_cache is not None \
                    and now - self._topo_cache[0] < self._topo_ttl:
                return self._topo_cache[1]
        try:
            topo = self.cluster.state.fabric_topology()
        except Exception:
            # state quorum mid-election: serve the stale map rather
            # than cutting discovery — wiring degrades, never vanishes
            with self._topo_lock:
                if self._topo_cache is not None:
                    return self._topo_cache[1]
            raise
        with self._topo_lock:
            self._topo_cache = (now, topo)
        return topo

    @property
    def address(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def watch_sheds(self) -> int:
        """Best-effort watch subscriptions answered 429 (watch_limit)."""
        return self._httpd.watch_sheds    # type: ignore[attr-defined]

    def start(self) -> "RouterServer":
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="fabric-router")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.stopping = True           # type: ignore[attr-defined]
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self.cluster.close()


def fetch_topology(url: str, timeout: float = 5.0) -> dict:
    """GET a served topology map from a router (``/topology``); falls
    back to the state shard's ``fabric_topology`` verb over /call so
    either endpoint works."""
    try:
        with urllib.request.urlopen(url.rstrip("/") + "/topology",
                                    timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError:
        from kubernetes_tpu.hubclient import RemoteHub

        client = RemoteHub(url, timeout=timeout)
        try:
            return client.fabric_topology()
        finally:
            client.close()


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="kubernetes_tpu.fabric.router",
        description="stateless fabric router (multi-host deployment: "
                    "one or more per cluster)")
    ap.add_argument("--state", required=True,
                    help="shared-state shard URL")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--name", default="router-0")
    args = ap.parse_args(argv)
    server = RouterServer(args.state, host=args.host, port=args.port,
                          name=args.name).start()
    # the supervisor parses this line to learn the bound port
    print(f"LISTENING {server.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
