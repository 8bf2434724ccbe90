"""Every offered pod that is bound sits where its required podAffinity
terms hold, at the end state: on a node with the term's topology key, in a
domain that holds another pod the term selects in the term's namespaces
(reference_affinity.py says what the end state can and cannot show). The
terms are the mix's own pod template's, not the pods'."""

from benchmark import reference_affinity


def check(end):
    labels = {n.metadata.name: n.metadata.labels for n in end.nodes}
    pods = [(p.metadata.uid, p.spec.node_name, p.metadata.namespace,
             p.metadata.labels) for p in end.bound]
    terms = reference_affinity.required_terms(end.pod_template)
    return {"affinity_unsatisfied": reference_affinity.affinity_unsatisfied(
        terms, labels, pods, end.offered)}
