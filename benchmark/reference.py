"""The plain reference: what a correct scheduler's end state must satisfy.

A scheduler's placements are not unique, so the reference is not a second
scheduler but a straightforward check of the guarantees the configuration
states, in integer arithmetic on plain data. It imports nothing of the
program and takes nothing the program computed but the end state itself:
the hub's journal rows, the bound pods and the nodes, as plain tuples. The
requests, allocatables and constraints it holds them to are read from the
benchmark's own template files (started from chip_smoke.host_check and
testing/audit.audit_bind_journal, which stay in the program).
"""

from __future__ import annotations

_SUFFIX = {"Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30}


def milli(q: str) -> int:
    return int(q[:-1]) if q.endswith("m") else int(q) * 1000


def to_bytes(q: str) -> int:
    for suf, mul in _SUFFIX.items():
        if q.endswith(suf):
            return int(q[:-2]) * mul
    return int(q)


def audit_journal(rows: list[tuple], offered: set[str]) -> dict:
    """``rows``: (rv, type, uid, node) of every pod event, any order.
    A pod is bound exactly once when an update takes its node from empty
    to a name once and the node never changes after."""
    node_of: dict[str, str] = {}
    binds: dict[str, int] = {}
    moved = 0
    for _rv, etype, uid, node in sorted(rows):
        if etype == "delete":
            node_of.pop(uid, None)
            continue
        prev = node_of.get(uid, "")
        if node and not prev and etype == "update":
            binds[uid] = binds.get(uid, 0) + 1   # created bound: no bind
        elif node != prev and prev:
            moved += 1
        node_of[uid] = node
    return {
        "unbound": sum(1 for u in offered if not node_of.get(u)),
        "double_binds": moved + sum(1 for u, n in binds.items() if n > 1),
        "binds_audited": sum(binds.values()),
    }


def check_nodes(nodes: list[tuple], pods: list[tuple]) -> dict:
    """``nodes``: (name, cpu, memory, pods) as allocatable quantity strings;
    ``pods``: (uid, node, cpu, memory) as request quantity strings, bound
    pods only. Counts nodes over any allocatable and binds to no node."""
    alloc = {n: (milli(c), to_bytes(m), int(p)) for n, c, m, p in nodes}
    used = {n: [0, 0, 0] for n in alloc}
    unknown = 0
    for _uid, node, cpu, mem in pods:
        u = used.get(node)
        if u is None:
            unknown += 1
            continue
        u[0] += milli(cpu)
        u[1] += to_bytes(mem)
        u[2] += 1
    over = sum(1 for n, u in used.items()
               if u[0] > alloc[n][0] or u[1] > alloc[n][1]
               or u[2] > alloc[n][2])
    return {"overpacked_nodes": over, "unknown_node_binds": unknown}


def skew_excess(rule: dict, node_labels: dict[str, dict],
                pods: list[tuple]) -> int:
    """How far the pods a DoNotSchedule constraint selects exceed its
    maxSkew over its topology key, at the end state: every placement kept
    count[domain] + 1 - min <= maxSkew, counts only grow, so max - min
    <= maxSkew must still hold. ``pods``: (uid, node, labels)."""
    key = rule["topology_key"]
    counts = {lab[key]: 0 for lab in node_labels.values() if key in lab}
    want = rule["match_labels"].items()
    for _uid, node, labels in pods:
        dom = node_labels.get(node, {}).get(key)
        if dom is not None and all(labels.get(k) == v for k, v in want):
            counts[dom] += 1
    if not counts:
        return 0
    return max(0, max(counts.values()) - min(counts.values())
               - rule["max_skew"])


def required_rules(pod_template: dict) -> list[dict]:
    return [c for c in pod_template.get("spread", [])
            if c["when_unsatisfiable"] == "DoNotSchedule"]
