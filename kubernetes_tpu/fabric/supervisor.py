"""Local fabric supervisor: spawn, watch, kill, and restart the shard
processes.

The process-mode deployment story on one host (the multi-host story is
the same commands run per machine — README "Multi-host deployment"):
``spawn_local_cluster(pod_shards=2)`` brings up

    state shard  ──  nodes / events / meta shards  ──  pods-0..N-1
                                │
                             router

each as its own OS process (``python -m kubernetes_tpu.fabric.proc``),
each announcing its bound port on stdout (``LISTENING <port>``) and
registering with the state shard. The supervisor's restart path reuses
a dead shard's WAL file and name — the restarted process replays its
journal, re-registers on a NEW port, and the router re-resolves it:
that sequence is exactly what the chaos battery ``kill -9``s to prove.

This is an orchestration convenience for benchmarks, tests, and the
``--fabric`` flag — not an init system: processes are daemonic to the
supervisor's host process and die with it.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class FabricProc:
    """One spawned fabric process: role, args, handle, bound port."""

    def __init__(self, name: str, role: str, args: list[str],
                 popen: subprocess.Popen, port: int):
        self.name = name
        self.role = role
        self.args = args
        self.popen = popen
        self.port = port

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    @property
    def pid(self) -> int:
        return self.popen.pid

    def alive(self) -> bool:
        return self.popen.poll() is None


class FabricSupervisor:
    """Spawns fabric processes and keeps their handles; the chaos
    battery drives ``kill_shard``/``restart_shard`` against it."""

    def __init__(self, spawn_timeout_s: float = 20.0):
        self.procs: dict[str, FabricProc] = {}
        self._timeout = spawn_timeout_s

    def spawn(self, name: str, role: str, extra: list[str]) -> FabricProc:
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        # shard/router/state processes are control plane: they must
        # never take the chip from the scheduler process that needs it
        env["JAX_PLATFORMS"] = "cpu"
        args = [sys.executable, "-m", "kubernetes_tpu.fabric.proc",
                "--role", role, "--name", name, *extra]
        popen = subprocess.Popen(args, stdout=subprocess.PIPE,
                                 stderr=subprocess.DEVNULL,
                                 text=True, env=env, cwd=_REPO)
        port = self._await_port(popen, name)
        proc = FabricProc(name, role, extra, popen, port)
        self.procs[name] = proc
        return proc

    def _await_port(self, popen: subprocess.Popen, name: str) -> int:
        # readline() blocks, so the timeout must live on a reader
        # thread — a process that stays alive without ever binding
        # (wedged startup, runaway WAL replay) must fail the spawn
        # after spawn_timeout_s, not hang the caller forever
        import threading

        found: dict = {}

        def read() -> None:
            for line in popen.stdout:
                if line.startswith("LISTENING "):
                    found["port"] = int(line.split()[1])
                    return

        t = threading.Thread(target=read, daemon=True,
                             name=f"await-port-{name}")
        t.start()
        t.join(self._timeout)
        if "port" in found:
            return found["port"]
        if popen.poll() is not None:
            raise RuntimeError(
                f"fabric process {name!r} exited rc="
                f"{popen.returncode} before binding")
        popen.kill()
        raise RuntimeError(f"fabric process {name!r} never announced "
                           f"its port within {self._timeout}s")

    def wait_healthy(self, proc: FabricProc,
                     timeout_s: float = 15.0) -> None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(proc.url + "/healthz",
                                            timeout=2.0) as resp:
                    if resp.status == 200:
                        return
            except OSError:
                pass
            time.sleep(0.05)
        raise RuntimeError(f"{proc.name} never answered /healthz")

    def kill_shard(self, name: str, sig: int = signal.SIGKILL) -> int:
        """The chaos verb: SIGKILL by default — no drain, no WAL
        close, exactly the failure the replay path must absorb."""
        proc = self.procs[name]
        pid = proc.pid
        proc.popen.send_signal(sig)
        proc.popen.wait(timeout=10)
        return pid

    def restart_shard(self, name: str) -> FabricProc:
        """Re-spawn a dead shard with its original args (same WAL,
        same name, new port): WAL replay + re-registration heal the
        fabric without touching any other process."""
        old = self.procs[name]
        if old.alive():
            raise RuntimeError(f"{name} is still alive; kill it first")
        proc = self.spawn(name, old.role, old.args)
        self.wait_healthy(proc)
        return proc

    def stop(self) -> None:
        for proc in self.procs.values():
            if proc.alive():
                proc.popen.terminate()
        deadline = time.monotonic() + 5.0
        for proc in self.procs.values():
            try:
                proc.popen.wait(timeout=max(
                    0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.popen.kill()


class LocalCluster:
    """A running process-mode fabric: the supervisor plus the resolved
    URLs a client needs. ``state_url`` is the comma-joined replica set
    when the state core is replicated (every fabric client accepts the
    comma form); ``state_urls`` lists the members individually."""

    def __init__(self, sup: FabricSupervisor, state_url: str,
                 router_url: str, pod_shards: list[str],
                 state_urls: list[str] | None = None):
        self.sup = sup
        self.state_url = state_url
        self.router_url = router_url
        self.pod_shards = pod_shards
        self.state_urls = state_urls or [state_url]

    def shard_names(self) -> list[str]:
        return [n for n, p in self.sup.procs.items()
                if p.role == "shard"]

    def state_leader(self, timeout_s: float = 15.0) -> str:
        """Name of the state replica currently leading (replicated
        clusters only) — the chaos storms' kill target."""
        from kubernetes_tpu.fabric.replica import ReplicaClient

        client = ReplicaClient(self.state_urls)
        try:
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                for st in client.replica_status():
                    if st.get("role") == "leader":
                        return st["name"]
                time.sleep(0.1)
            raise RuntimeError("no state leader elected in time")
        finally:
            client.close()

    def stop(self) -> None:
        self.sup.stop()


def _free_port() -> int:
    """Pre-assign a listen port (the replica peer map must be known
    before any replica starts — etcd's static bootstrap). The tiny
    race between close and rebind is acceptable on a lab host."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def spawn_local_cluster(pod_shards: int = 2,
                        wal_dir: str | None = None,
                        journal_capacity: int = 65536,
                        wal_codec: str = "bin1",
                        kind_shards: bool = True,
                        router: bool = True,
                        state_replicas: int = 1) -> LocalCluster:
    """Bring up the whole fabric on this host. ``kind_shards=False``
    collapses nodes/events/meta into pods-0 (the minimal two-process
    cluster the tier-1 smoke uses: state + one all-kinds shard).
    ``state_replicas=3`` runs the REPLICATED state core: three replica
    processes with pinned ports and per-replica log WALs; a ``kill
    -9``'d member restarts onto the same port and catches up from the
    leader's log."""
    sup = FabricSupervisor()
    pod_names = [f"pods-{i}" for i in range(pod_shards)]
    try:
        if state_replicas > 1:
            ports = [_free_port() for _ in range(state_replicas)]
            names = [f"state-{i}" for i in range(state_replicas)]
            peers = ",".join(f"{n}=http://127.0.0.1:{p}"
                             for n, p in zip(names, ports))
            state_procs = []
            for n, p in zip(names, ports):
                extra = ["--port", str(p), "--replica-id", n,
                         "--peers", peers,
                         "--pod-shards", ",".join(pod_names)]
                if wal_dir:
                    os.makedirs(wal_dir, exist_ok=True)
                    extra += ["--wal",
                              os.path.join(wal_dir, f"{n}.wal")]
                state_procs.append(sup.spawn(n, "state", extra))
            for proc in state_procs:
                sup.wait_healthy(proc)
            state_urls = [proc.url for proc in state_procs]
            state_url = ",".join(state_urls)
            # shards registering before the first election would burn
            # their redirect budget: wait for a leader once, here
            LocalCluster(sup, state_url, "", pod_names,
                         state_urls).state_leader()
        else:
            state = sup.spawn("state", "state",
                              ["--pod-shards", ",".join(pod_names)])
            sup.wait_healthy(state)
            state_urls = [state.url]
            state_url = state.url

        def shard_args(name: str, kinds: str) -> list[str]:
            extra = ["--state", state_url, "--kinds", kinds,
                     "--journal-capacity", str(journal_capacity),
                     "--wal-codec", wal_codec]
            if wal_dir:
                os.makedirs(wal_dir, exist_ok=True)
                extra += ["--wal", os.path.join(wal_dir, f"{name}.wal")]
            return extra

        shard_procs = []
        if kind_shards:
            shard_procs.append(sup.spawn(
                "nodes", "shard", shard_args("nodes", "nodes")))
            shard_procs.append(sup.spawn(
                "events", "shard", shard_args("events", "events")))
            shard_procs.append(sup.spawn(
                "meta", "shard", shard_args("meta", "*")))
            pod_kinds = "pods"
        else:
            # the minimal cluster: pods-0 owns everything
            pod_kinds = "pods,nodes,events,*"
        for name in pod_names:
            shard_procs.append(sup.spawn(
                name, "shard", shard_args(name, pod_kinds)))
        for p in shard_procs:
            sup.wait_healthy(p)
        router_url = ""
        if router:
            r = sup.spawn("router-0", "router", ["--state", state_url])
            sup.wait_healthy(r)
            router_url = r.url
        return LocalCluster(sup, state_url, router_url, pod_names,
                            state_urls)
    except BaseException:
        sup.stop()
        raise
