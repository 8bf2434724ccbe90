"""The plain reference for required inter-pod anti-affinity: what the end
state of a correct scheduler must satisfy where pods carry a required
`podAntiAffinity` term (upstream's InterPodAffinity Filter, filtering.go
`satisfyPodAntiAffinity` and `satisfyExistingPodsAntiAffinity`).

Like reference.py and reference_affinity.py it is no second scheduler: it
holds the placements the timed path produced to the guarantee the
configuration states, on plain data (tuples, dictionaries, strings), in
whole numbers, and it imports nothing of the program. The terms come from
the benchmark's own template file, never from the pods the program handled.

The guarantee: no two pods that a required podAntiAffinity term of either
selects share a domain of that term's topology key, in the term's
namespaces. A term selects a pod whose namespace is among the term's
`namespaces` (the carrier's own namespace where the term lists none) and
whose labels carry every pair of `match_labels`. A domain is the set of
nodes with one value of the key; a node without the key has no domain of
it and cannot violate (upstream skips such a node in both functions). A pod
never violates against itself: upstream counts existing pods only.

Upstream holds it in two directions when a pod is placed: the incoming
pod's own terms against the pods that are there (`satisfyPodAntiAffinity`),
and the terms of the pods that are there against the incoming pod
(`satisfyExistingPodsAntiAffinity`). At the end state they are the same
pairs: a carrier of a term and another pod the term selects in one domain
is a breach whichever of the two was placed last. `anti_affinity_violated`
counts the carriers, which finds every such pair at least once; where
every pod the term selects also carries it (upstream's template: green pods
against green pods) the count is the same from either side, and one breach
counts both of its pods.

The end state is exact here, which it is not for affinity
(reference_affinity.py): no bound pod is deleted in the cells that use
this, so a violation once made is still there at the end, and a node only
ever becomes forbidden to a pod, never allowed again. For the same reason a
pod that is still pending at the end with a node in
`anti_affinity_feasible_nodes` was wrongly left: that node was feasible at
every earlier instant too.
"""

from __future__ import annotations


def required_anti_terms(pod_template: dict) -> list[dict]:
    """The required podAntiAffinity terms of a pod template of
    benchmark/templates/: {"topology_key", "match_labels", "namespaces"}."""
    return [{"topology_key": t["topology_key"],
             "match_labels": dict(t.get("match_labels", {})),
             "namespaces": list(t.get("namespaces", []))}
            for t in pod_template.get("pod_anti_affinity", {})
            .get("required", [])]


def _selects(term: dict, carrier_namespace: str, namespace: str,
             labels: dict) -> bool:
    spaces = term["namespaces"] or (carrier_namespace,)
    return namespace in spaces and all(
        labels.get(k) == v for k, v in term["match_labels"].items())


def selected_by(terms: list[dict], carrier_namespace: str,
                pods: list[tuple]) -> list[str]:
    """The uids of the `pods` that one of `terms` selects, for a carrier
    in `carrier_namespace`."""
    return [uid for uid, _node, ns, labels in pods
            if any(_selects(t, carrier_namespace, ns, labels)
                   for t in terms)]


def anti_affinity_violated(terms: list[dict], node_labels: dict[str, dict],
                           pods: list[tuple], judged) -> int:
    """How many of the `judged` pods share a domain of the key of one of
    `terms` with ANOTHER pod that term selects. ``pods``: (uid, node,
    namespace, labels) of every bound pod; ``node_labels``: {node:
    labels}; ``judged``: the uids of the pods that carry `terms` (one not
    among `pods` is not bound, and not judged). A pod on a node without a
    term's key, or on a node that is not in the cluster, cannot violate
    that term."""
    if not terms:
        return 0
    by_uid = {uid: (node, ns, labels) for uid, node, ns, labels in pods}
    tallies: dict[tuple, dict[str, int]] = {}

    def tally(i: int, carrier_ns: str) -> dict[str, int]:
        """Pods term i selects per domain of its key, for a carrier in
        one namespace (all carriers read one tally where the term lists
        its namespaces)."""
        spaces = tuple(terms[i]["namespaces"]) or (carrier_ns,)
        got = tallies.get((i, spaces))
        if got is None:
            got = tallies[(i, spaces)] = {}
            key = terms[i]["topology_key"]
            for _uid, node, ns, labels in pods:
                dom = node_labels.get(node, {}).get(key)
                if dom is not None and _selects(terms[i], carrier_ns, ns,
                                                labels):
                    got[dom] = got.get(dom, 0) + 1
        return got

    bad = 0
    for uid in set(judged):
        if uid not in by_uid:
            continue
        node, ns, labels = by_uid[uid]
        here = node_labels.get(node, {})
        for i, t in enumerate(terms):
            dom = here.get(t["topology_key"])
            if dom is None:
                continue
            own = 1 if _selects(t, ns, ns, labels) else 0
            if tally(i, ns).get(dom, 0) - own > 0:
                bad += 1
                break
    return bad


def anti_affinity_feasible_nodes(terms: list[dict],
                                 node_labels: dict[str, dict],
                                 pods: list[tuple], carried: dict[str, list],
                                 namespace: str, labels: dict) -> set[str]:
    """The nodes on which a pod of `namespace` and `labels` that carries
    `terms` breaks no required anti-affinity, its own or a bound pod's.
    ``pods`` as above; ``carried``: {uid: that bound pod's own required
    anti-affinity terms} (a uid not in it carries none). Anti-affinity
    alone: resources and the other filters are not looked at."""
    forbidden: set[tuple[str, str]] = set()      # (topology key, domain)
    for uid, node, ns, pod_labels in pods:
        there = node_labels.get(node, {})
        # the incoming pod's terms against this bound pod
        for t in terms:
            dom = there.get(t["topology_key"])
            if dom is not None and _selects(t, namespace, ns, pod_labels):
                forbidden.add((t["topology_key"], dom))
        # this bound pod's terms against the incoming pod
        for t in carried.get(uid, ()):
            dom = there.get(t["topology_key"])
            if dom is not None and _selects(t, ns, namespace, labels):
                forbidden.add((t["topology_key"], dom))
    return {node for node, here in node_labels.items()
            if not any(here.get(key) == dom for key, dom in forbidden)}
