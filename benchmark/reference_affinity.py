"""The plain reference for required inter-pod affinity: what the end state
of a correct scheduler must satisfy where pods carry a required
`podAffinity` term (upstream's InterPodAffinity Filter, filtering.go
`satisfyPodAffinity`).

Like reference.py it is no second scheduler: it holds the placements the
timed path produced to the guarantee the configuration states, on plain
data (tuples, dictionaries, strings), in whole numbers, and it imports
nothing of the program. The terms come from the benchmark's own template
file, never from the pods the program handled.

The guarantee: a pod with a required podAffinity term is bound only to a
node that carries the term's topology key and whose domain (the nodes with
the same value of that key) holds ANOTHER pod the term selects: one whose
namespace is among the term's `namespaces` (the pod's own namespace where
the term lists none) and whose labels carry every pair of `match_labels`.
Each term is held by itself; upstream counts an existing pod only if it
matches all of the incoming pod's terms, which is the same thing for the
one-term pods of upstream's templates. Upstream's exception is kept: a pod
that matches its own terms may be the first of its series, on any node that
carries every term's key, where no other pod on any node with that key
matches them.

It is held at the end state, and that cannot see everything. Upstream
judges a pod against the pods that were there when it was placed. At the
end a match that was only assumed then (placed by the same or an earlier
launch) and bound later is indistinguishable from one bound earlier: both
are simply there. No bound pod is deleted in the cells that use this, and
the pending pods that the close withdraws are younger than any pod the
scheduler has popped and assumed (the mix keeps the oldest), so a match
cannot have gone, and a placement that was right when it was made is right
at the end. The other way round, a pod placed in a domain that was empty at
the time and filled later passes here; the controls plant a breach that
stays one.
"""

from __future__ import annotations


def required_terms(pod_template: dict) -> list[dict]:
    """The required podAffinity terms of a pod template of
    benchmark/templates/: {"topology_key", "match_labels", "namespaces"}."""
    return [{"topology_key": t["topology_key"],
             "match_labels": dict(t.get("match_labels", {})),
             "namespaces": list(t.get("namespaces", []))}
            for t in pod_template.get("pod_affinity", {}).get("required", [])]


def _selected(term: dict, spaces, namespace: str, labels: dict) -> bool:
    return namespace in spaces and all(
        labels.get(k) == v for k, v in term["match_labels"].items())


def affinity_unsatisfied(terms: list[dict], node_labels: dict[str, dict],
                         pods: list[tuple], judged) -> int:
    """How many of the `judged` pods sit where one of `terms` fails.
    ``pods``: (uid, node, namespace, labels) of every bound pod;
    ``node_labels``: {node: labels}; ``judged``: the uids of the pods that
    carry `terms` and are held to them (one not among `pods` is not bound,
    and not judged). A term fails for a pod when its node lacks the term's
    topology key, or when no other pod the term selects sits in the node's
    domain of that key, unless the pod is the first of its series."""
    if not terms:
        return 0
    by_uid = {uid: (node, ns, labels) for uid, node, ns, labels in pods}
    tallies: dict[tuple, tuple[dict[str, int], int]] = {}

    def tally(i: int, spaces: tuple) -> tuple[dict[str, int], int]:
        """Selected pods per domain of term i's key, and in all of them,
        for one namespace set."""
        got = tallies.get((i, spaces))
        if got is None:
            per_domain: dict[str, int] = {}
            key = terms[i]["topology_key"]
            for _uid, node, ns, labels in pods:
                dom = node_labels.get(node, {}).get(key)
                if dom is not None and _selected(terms[i], spaces, ns, labels):
                    per_domain[dom] = per_domain.get(dom, 0) + 1
            got = tallies[(i, spaces)] = (per_domain,
                                          sum(per_domain.values()))
        return got

    bad = 0
    for uid in set(judged):
        if uid not in by_uid:
            continue
        node, ns, labels = by_uid[uid]
        here = node_labels.get(node, {})
        if any(t["topology_key"] not in here for t in terms):
            bad += 1
            continue
        alone = others_anywhere = False
        selects_itself = True
        for i, t in enumerate(terms):
            spaces = tuple(t["namespaces"]) or (ns,)
            own = 1 if _selected(t, spaces, ns, labels) else 0
            selects_itself = selects_itself and bool(own)
            per_domain, everywhere = tally(i, spaces)
            if per_domain.get(here[t["topology_key"]], 0) - own <= 0:
                alone = True
            if everywhere - own > 0:
                others_anywhere = True
        if alone and not (selects_itself and not others_anywhere):
            bad += 1
    return bad
