"""A DoNotSchedule constraint's maxSkew is never exceeded by the pods it
selects, at the end state. The rules are the mix's own pod template's."""

from benchmark import reference


def check(end):
    labels = {n.metadata.name: n.metadata.labels for n in end.nodes}
    pods = [(p.metadata.uid, p.spec.node_name, p.metadata.labels)
            for p in end.bound]
    rules = reference.required_rules(end.pod_template)
    return {"skew_excess": max(
        (reference.skew_excess(r, labels, pods) for r in rules), default=0)}
