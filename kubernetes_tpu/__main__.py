"""The scheduler binary: ``python -m kubernetes_tpu``.

Equivalent of cmd/kube-scheduler (app/server.go:89 Setup + Run): load the
component config, stand up the hub + scheduler + serving endpoints, run
the daemon under optional leader election until interrupted. The
in-process hub doubles as the demo API surface; a real deployment would
swap it for an apiserver-backed client implementing the same interface.
"""

from __future__ import annotations

import argparse
import signal
import socket
import sys
import threading
import uuid


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="kubernetes-tpu-scheduler")
    parser.add_argument("--config", help="component config file (JSON/YAML)")
    parser.add_argument("--hub", default=None,
                        help="remote hub URL (http://host:port); default "
                             "is an in-process demo hub")
    parser.add_argument("--bind-address", default="127.0.0.1")
    parser.add_argument("--secure-port", type=int, default=10259,
                        help="serving port for /metrics,/healthz,/configz "
                             "(0 = disabled)")
    parser.add_argument("--debug-token", default=None,
                        help="bearer token admitting /debug endpoints "
                             "(unset = /debug disabled, per the "
                             "reference's authz-gated debugging handlers)")
    parser.add_argument("--wal", default=None,
                        help="WAL file for the in-process hub's event "
                             "journal (restart replays it); with "
                             "--hub-shards, a WAL DIRECTORY (one file "
                             "per shard); ignored with --hub")
    parser.add_argument("--hub-shards", type=int, default=0,
                        help="shard the in-process hub (fabric."
                             "sharded.ShardedHub) with N pod shards "
                             "(0 = single hub); ignored with --hub")
    parser.add_argument("--fabric", type=int, default=0,
                        help="spawn the OUT-OF-PROCESS control-plane "
                             "fabric with N pod-shard processes (plus "
                             "the shared-state shard, nodes/events/"
                             "meta shards, and a stateless router, "
                             "each its own OS process; fabric."
                             "supervisor); the scheduler connects "
                             "through the router. --wal names the "
                             "shard WAL directory (bin1 codec). "
                             "Ignored with --hub")
    parser.add_argument("--fabric-wal-codec", default="bin1",
                        choices=("json", "bin1"),
                        help="journal WAL codec for --fabric shard "
                             "processes (bin1 ≈ 6x smaller replay)")
    parser.add_argument("--state-replicas", type=int, default=1,
                        help="with --fabric: run the shared-state core "
                             "as an N-member replicated quorum (3 = "
                             "the etcd model; leader kill -9 fails "
                             "over without losing rv/fencing/ring "
                             "state)")
    parser.add_argument("--journal-capacity", type=int, default=16384,
                        help="event-journal ring capacity per resource "
                             "kind (the watch-resume window)")
    parser.add_argument("--trace-export", default=None,
                        help="append each scheduling cycle's flight-"
                             "recorder trace as a JSON line to this file "
                             "(offline phase analysis)")
    parser.add_argument("--trace-export-learn", action="store_true",
                        help="with --trace-export: also export each "
                             "placement's feature vector AND top-K "
                             "alternative scores (the learn-loop "
                             "daemon's training + regret substrate)")
    parser.add_argument("--leader-elect", action="store_true")
    parser.add_argument("--leader-elect-lease-duration", type=float,
                        default=15.0)
    parser.add_argument("--id", default=None,
                        help="leader election identity")
    parser.add_argument("--slices", action="store_true",
                        help="horizontal scale-out: join the scheduler-"
                             "replica slice ring and drain only pods "
                             "whose namespace hashes into this "
                             "replica's owned slices (run N such "
                             "processes against one --hub; supersedes "
                             "--leader-elect)")
    parser.add_argument("--slice-heartbeat", type=float, default=2.0,
                        help="with --slices: registry heartbeat period "
                             "seconds (the TTL is 5x this, floor 10s)")
    parser.add_argument("--feature-gates", default="",
                        help="comma-separated gate=bool overrides")
    parser.add_argument("--fleet-endpoint", action="append", default=[],
                        metavar="COMPONENT[/SHARD]=URL",
                        help="register a fabric component with the "
                             "fleet collector (repeatable); serves the "
                             "merged exposition at /metrics/fleet and "
                             "the health summary at /debug/fleet")
    parser.add_argument("--validate-only", action="store_true",
                        help="load + validate the config, then exit")
    args = parser.parse_args(argv)

    from kubernetes_tpu.utils import jaxsetup

    jaxsetup.setup()

    from kubernetes_tpu.config.load import load_config
    from kubernetes_tpu.config.types import default_config
    from kubernetes_tpu.config.validation import validate_config
    from kubernetes_tpu.hub import Hub
    from kubernetes_tpu.plugins.registry import in_tree_registry
    from kubernetes_tpu.scheduler import Scheduler

    cfg = load_config(args.config) if args.config else default_config()
    if args.trace_export:
        cfg.trace_export_path = args.trace_export
        if args.trace_export_learn:
            cfg.trace_export_features = True
            cfg.trace_export_alts = True
    for part in filter(None, args.feature_gates.split(",")):
        name, _, val = part.partition("=")
        cfg.feature_gates[name.strip()] = val.strip().lower() in (
            "1", "true", "yes", "")
    errs = validate_config(cfg, in_tree_registry())
    if errs:
        for e in errs:
            print(f"invalid configuration: {e}", file=sys.stderr)
        return 1
    if args.validate_only:
        print("configuration valid")
        return 0

    fabric_cluster = None
    if args.hub:
        # the kubemark/hubserver deployment shape: this process holds no
        # state, it list/watches a hub in another process and rides the
        # hub-client resilience machinery through its outages
        from kubernetes_tpu.hubclient import RemoteHub

        hub = RemoteHub(args.hub)
        print(f"using remote hub {args.hub}", file=sys.stderr)
    elif args.fabric > 0:
        # process-mode fabric: every shard its own OS process with its
        # own WAL and port, a stateless router in front; this process
        # is a pure client of the router (kill -9 a shard and watch
        # the supervisor + WAL replay + re-registration heal it)
        from kubernetes_tpu.fabric.supervisor import spawn_local_cluster
        from kubernetes_tpu.hubclient import RemoteHub

        fabric_cluster = spawn_local_cluster(
            pod_shards=args.fabric, wal_dir=args.wal,
            journal_capacity=args.journal_capacity,
            wal_codec=args.fabric_wal_codec,
            state_replicas=args.state_replicas)
        hub = RemoteHub(fabric_cluster.router_url)
        print(f"fabric: {args.fabric} pod-shard processes + state/"
              f"nodes/events/meta + router at "
              f"{fabric_cluster.router_url}", file=sys.stderr)
    elif args.hub_shards > 0:
        from kubernetes_tpu.fabric.sharded import ShardedHub

        hub = ShardedHub(pod_shards=args.hub_shards,
                         journal_capacity=args.journal_capacity,
                         wal_dir=args.wal)
        print(f"sharded hub: {args.hub_shards} pod shards + "
              f"nodes/events/meta (rv={hub.current_rv})",
              file=sys.stderr)
    else:
        hub = Hub(journal_capacity=args.journal_capacity,
                  wal_path=args.wal)
        if args.wal:
            print(f"hub journal WAL at {args.wal} "
                  f"(replayed rv={hub.current_rv})", file=sys.stderr)
    # name the device the fused launches will run on, before any work
    import json

    print("device: " + json.dumps(jaxsetup.device_info()), file=sys.stderr)
    sched = Scheduler(hub, cfg)

    if args.fleet_endpoint:
        from kubernetes_tpu.telemetry.fleet import FleetView

        endpoints = []
        for spec in args.fleet_endpoint:
            name, _, url = spec.partition("=")
            if not url:
                print(f"bad --fleet-endpoint {spec!r} (want "
                      "COMPONENT[/SHARD]=URL)", file=sys.stderr)
                return 1
            component, _, shard = name.partition("/")
            endpoints.append({"component": component, "shard": shard,
                              "url": url})
        sched.fleet = FleetView(endpoints)
        print(f"fleet view over {len(endpoints)} endpoints "
              "(/metrics/fleet, /debug/fleet)", file=sys.stderr)

    serving = None
    if args.secure_port:
        from kubernetes_tpu.serving import ServingEndpoints, token_auth

        serving = ServingEndpoints(
            sched, host=args.bind_address, port=args.secure_port,
            debug_auth=token_auth(args.debug_token)
            if args.debug_token else None)
        serving.start()
        print(f"serving /metrics,/healthz,/configz on "
              f"{args.bind_address}:{serving.port}", file=sys.stderr)

    elector = None
    if args.slices:
        from kubernetes_tpu.leaderelection import SliceManager

        identity = args.id or f"{socket.gethostname()}_{uuid.uuid4().hex[:8]}"
        url = (f"http://{args.bind_address}:{serving.port}"
               if serving is not None else "")
        elector = SliceManager(
            hub, identity, url=url,
            heartbeat_s=args.slice_heartbeat,
            ttl_s=max(10.0, 5 * args.slice_heartbeat))
        print(f"slice scale-out enabled, id={identity} "
              f"(heartbeat {args.slice_heartbeat}s)", file=sys.stderr)
    elif args.leader_elect:
        from kubernetes_tpu.leaderelection import LeaderElector

        identity = args.id or f"{socket.gethostname()}_{uuid.uuid4().hex[:8]}"
        elector = LeaderElector(
            hub.leases, identity,
            lease_duration=args.leader_elect_lease_duration)
        print(f"leader election enabled, id={identity}", file=sys.stderr)

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())

    def _debug_dump_body() -> None:
        import json as _json

        out = _json.dumps({"cache": sched.cache.dump(),
                           "pending": sched.queue.pending_counts()},
                          default=str)
        if len(out) > 100000:
            out = out[:100000] + f'... [truncated, {len(out)} chars total]'
        print(out, file=sys.stderr)
        for line in sched.cache.compare_with_hub(hub):
            print(f"cache-vs-hub: {line}", file=sys.stderr)

    def _swallow(fn) -> None:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — diagnostics only
            try:
                print(f"cache-debugger failed: {e!r}", file=sys.stderr)
            except OSError:
                pass

    def _debug_dump(*_sig) -> None:
        """SIGUSR2 cache debugger (backend/cache/debugger/debugger.go:31):
        dump the cache and run the cache-vs-hub comparer — on its OWN
        thread, like the reference's debugger goroutine: the handler
        itself interrupts the scheduling loop mid-bytecode, where the
        RLock would let an inline dump read half-applied cache state (and
        a raising handler would crash the loop). The WHOLE handler body
        (thread start included — it can raise at the thread limit) is
        guarded: a debug signal must never take the daemon down."""
        _swallow(lambda: threading.Thread(
            target=lambda: _swallow(_debug_dump_body),
            daemon=True, name="cache-debugger").start())

    if hasattr(signal, "SIGUSR2"):
        signal.signal(signal.SIGUSR2, _debug_dump)
    print("scheduler running (ctrl-c to stop)", file=sys.stderr)
    try:
        sched.run(stop, elector=elector)
    finally:
        if serving is not None:
            serving.stop()
        sched.close()
        hub.close()   # RemoteHub: drain streams; local Hub: release WAL
        if fabric_cluster is not None:
            fabric_cluster.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
