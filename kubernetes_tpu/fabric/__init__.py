"""Control-plane fabric: hub scale-out for million-user traffic.

Three pillars (ROADMAP item 3):

* :mod:`kubernetes_tpu.fabric.codec` — a compact binary wire codec
  (length-prefixed msgpack-style framing, versioned, negotiated
  per-connection with JSON fallback) replacing JSON on the
  hubserver/hubclient hot path.
* :mod:`kubernetes_tpu.fabric.sharded` — :class:`ShardedHub`, the hub
  sharded by kind (and namespace-hash within the pod kind) over the
  existing rv journal; each shard owns its rings/WAL behind a thin
  router that preserves the single-hub ``Hub``/``RemoteHub`` API,
  fencing epochs, and cross-shard watch-resume semantics.
* :mod:`kubernetes_tpu.fabric.relay` — the watch relay tree: relay
  nodes subscribe upstream once per kind set and fan events out to
  thousands of downstream reflectors with per-subscriber resume
  cursors and backpressure-aware slow-subscriber eviction.

:mod:`kubernetes_tpu.fabric.fanout` drives the 10k-client smoke
(``python -m kubernetes_tpu.fabric.fanout [--procs]``).

Submodules other than ``codec`` load lazily (PEP 562): the transport
layer (hubserver/hubclient) imports ``fabric.codec``, and the relay
imports the transport — eager re-exports here would close that loop.
"""

from kubernetes_tpu.fabric import codec  # noqa: F401
from kubernetes_tpu.fabric.codec import (  # noqa: F401
    CODEC_BINARY,
    CODEC_JSON,
    decode,
    encode,
    registry_fingerprint,
)

_LAZY = {
    "ShardedHub": ("kubernetes_tpu.fabric.sharded", "ShardedHub"),
    "RelayCore": ("kubernetes_tpu.fabric.relay", "RelayCore"),
    "RelayServer": ("kubernetes_tpu.fabric.relay", "RelayServer"),
    "run_fanout_smoke": ("kubernetes_tpu.fabric.fanout",
                         "run_fanout_smoke"),
    # out-of-process fabric (ISSUE 11): shard processes, the shared-
    # state shard, the stateless router, and the local supervisor
    "StateCore": ("kubernetes_tpu.fabric.cluster", "StateCore"),
    "ProcShardHub": ("kubernetes_tpu.fabric.cluster", "ProcShardHub"),
    "ClusterClient": ("kubernetes_tpu.fabric.cluster", "ClusterClient"),
    "RouterServer": ("kubernetes_tpu.fabric.router", "RouterServer"),
    "spawn_local_cluster": ("kubernetes_tpu.fabric.supervisor",
                            "spawn_local_cluster"),
    "run_fanout_smoke_procs": ("kubernetes_tpu.fabric.fanout",
                               "run_fanout_smoke_procs"),
    # replicated state core (ISSUE 13): the Raft-lite quorum for
    # rv / fencing / ring, and its leader-routing client
    "StateReplica": ("kubernetes_tpu.fabric.replica", "StateReplica"),
    "ReplicaClient": ("kubernetes_tpu.fabric.replica", "ReplicaClient"),
    "make_state_client": ("kubernetes_tpu.fabric.replica",
                          "make_state_client"),
}


def __getattr__(name: str):
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(name)
    import importlib

    return getattr(importlib.import_module(target[0]), target[1])
