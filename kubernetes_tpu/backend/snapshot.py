"""Per-cycle immutable cluster view.

Equivalent of /root/reference/pkg/scheduler/backend/cache/snapshot.go:29-44:
a node map plus a zone-interleaved node list. The reference's two affinity
sublists are left out: nothing reads them (the device kernels read the
mirror's pod table, the host fallback a NodeInfo's own lists).

The snapshot is refreshed *incrementally* by Cache.update_snapshot (the
generation-diff walk of cache.go:186 UpdateSnapshot); the device mirror in
``backend.mirror`` applies the same diff to HBM rows.
"""

from __future__ import annotations

from typing import Optional

from kubernetes_tpu.backend.node_info import NodeInfo


class Snapshot:
    def __init__(self) -> None:
        self.node_info_map: dict[str, NodeInfo] = {}
        self.node_info_list: list[NodeInfo] = []
        # node name -> its position in node_info_list: a refresh over an
        # unchanged node set writes the re-cloned NodeInfos there
        self.node_index: dict[str, int] = {}
        self.generation: int = 0
        # namespace name -> labels, for affinity namespaceSelector unrolling
        # (the nsLister surface of interpodaffinity/plugin.go:123)
        self.namespaces: dict[str, dict[str, str]] = {}
        self.ns_generation: int = 0
        # monotonically bumped by Cache.update_snapshot whenever anything in
        # the snapshot changed — lets downstream consumers (Mirror.sync) be
        # O(1) no-ops between changes
        self.version: int = 0
        self.node_set_version: int = -1
        # names of the nodes the newest refresh (the one that made
        # ``version``) re-cloned, or None where it added or removed a
        # node: a Mirror that synced version - 1 visits these alone
        self.changed_nodes: Optional[list[str]] = None

    # --- lister surface (snapshot.go:158-199) ---

    def num_nodes(self) -> int:
        return len(self.node_info_list)

    def get(self, name: str) -> Optional[NodeInfo]:
        return self.node_info_map.get(name)

    def list_all(self) -> list[NodeInfo]:
        return self.node_info_list

    def index_of(self, name: str) -> int:
        """Position of a node in node_info_list, or -1."""
        return self.node_index.get(name, -1)
