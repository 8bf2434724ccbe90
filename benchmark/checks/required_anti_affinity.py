"""No two bound pods that a required podAntiAffinity term selects share a
domain of the term's topology key, at the end state (which is exact here:
reference_anti_affinity.py says why). The terms are the mix's own pod
template's, not the pods'. Judged are the offered pods and every other
bound pod the terms select: the init pods and the pre-warm pods are of the
same upstream template, so every green pod of the run is counted, whoever
bound it."""

from benchmark import reference_anti_affinity as ref


def check(end):
    labels = {n.metadata.name: n.metadata.labels for n in end.nodes}
    pods = [(p.metadata.uid, p.spec.node_name, p.metadata.namespace,
             p.metadata.labels) for p in end.bound]
    terms = ref.required_anti_terms(end.pod_template)
    green = ref.selected_by(
        terms, end.pod_template.get("namespace", "default"), pods)
    return {"anti_affinity_violated": ref.anti_affinity_violated(
        terms, labels, pods, [*end.offered, *green])}
