"""The plain reference for required node affinity: what the end state of a
correct scheduler must satisfy where pods carry
`requiredDuringSchedulingIgnoredDuringExecution` node affinity (upstream's
NodeAffinity Filter, which calls component-helpers'
`MatchNodeSelectorTerms`).

Like reference.py it is no second scheduler: it holds the placements the
timed path produced to the guarantee the configuration states, on plain
data (tuples, dictionaries, strings), in whole numbers, and it imports
nothing of the program. The terms come from the benchmark's own template
file, never from the pods the program handled.

The guarantee: a pod is bound only to a node that satisfies at least one of
its terms (terms are ORed), and a node satisfies a term when it satisfies
every requirement of the term (ANDed): each of `match_expressions` on the
node's labels, each of `match_fields` on the node's fields (only
`metadata.name` exists). A term with no requirement matches nothing. The
operators, where the node has the key / where it does not:

    In            its value is one of `values`         / fails
    NotIn         its value is none of `values`        / passes
    Exists        passes                               / fails
    DoesNotExist  fails                                / passes
    Gt, Lt        its value, a whole number, is greater / fails
                  (less) than the one value; a value
                  that is no whole number fails

**The end state is exact here.** A node's labels do not change in the cells
that use this, and a bound pod is not moved, so a placement that was right
when it was made is right at the end, and one that was wrong still shows.
"""

from __future__ import annotations

NAME_FIELD = "metadata.name"


def _requirement(e: dict) -> dict:
    return {"key": e["key"], "operator": e["operator"],
            "values": [str(v) for v in e.get("values", [])]}


def required_node_terms(pod_template: dict) -> list[dict]:
    """The required node-affinity terms of a pod template of
    benchmark/templates/ (`node_affinity.required`: terms of expressions),
    each as {"match_expressions": [req], "match_fields": [req]}, a
    requirement being {"key", "operator", "values"}. Templates carry
    expressions only."""
    return [{"match_expressions": [_requirement(e) for e in exprs],
             "match_fields": []}
            for exprs in pod_template.get("node_affinity", {})
            .get("required", [])]


def _whole(s: str) -> int | None:
    """A label value as upstream's strconv.ParseInt reads it, or None."""
    t = s[1:] if s[:1] in "+-" else s
    return int(s) if t.isascii() and t.isdigit() else None


def _holds(req: dict, have: dict[str, str]) -> bool:
    op, key, values = req["operator"], req["key"], req["values"]
    if key not in have:
        return op in ("NotIn", "DoesNotExist")
    value = have[key]
    if op == "In":
        return value in values
    if op == "NotIn":
        return value not in values
    if op == "Exists":
        return True
    if op == "DoesNotExist":
        return False
    if op in ("Gt", "Lt"):
        mine = _whole(value)
        bound = _whole(values[0]) if len(values) == 1 else None
        if mine is None or bound is None:
            return False
        return mine > bound if op == "Gt" else mine < bound
    return False                                     # an unknown operator


def node_matches(terms: list[dict], name: str, labels: dict[str, str]
                 ) -> bool:
    """Does the node (its name, its labels) satisfy at least one term?
    No terms: every node does."""
    if not terms:
        return True
    fields = {NAME_FIELD: name}
    for t in terms:
        reqs = [(r, labels) for r in t["match_expressions"]] \
            + [(r, fields) for r in t["match_fields"]]
        if reqs and all(_holds(r, have) for r, have in reqs):
            return True
    return False


def node_affinity_violated(terms: list[dict], node_labels: dict[str, dict],
                           pods: list[tuple], judged) -> int:
    """How many of the `judged` pods are bound on a node that satisfies
    none of `terms`. ``pods``: (uid, node) of every bound pod;
    ``node_labels``: {node: labels} of the cluster's nodes (a node not
    among them satisfies nothing); ``judged``: the uids of the pods that
    carry `terms` and are held to them (one not among `pods` is not bound,
    and not judged)."""
    if not terms:
        return 0
    where = dict(pods)
    bad = 0
    for uid in set(judged):
        node = where.get(uid)
        if node is None:
            continue
        if node not in node_labels \
                or not node_matches(terms, node, node_labels[node]):
            bad += 1
    return bad


def node_affinity_feasible_nodes(terms: list[dict],
                                 node_labels: dict[str, dict]) -> list[str]:
    """The nodes a pod with `terms` may be bound to, in name order (for the
    tests: a pod left pending must have none of them with room)."""
    return sorted(n for n, labels in node_labels.items()
                  if node_matches(terms, n, labels))
