"""Every judged pod that is bound sits on a node whose labels satisfy at
least one of its required node-affinity terms (every expression of the
term), at the end state, which is exact here (reference_node_affinity.py
says why). The terms are the mix's own pod template's, not the pods'.
Judged are the offered pods and the init pods (named `init-` by
cell.build_cluster): the configuration that names this check makes its init
pods of the same template, as upstream's test case does, so a broken
initial state is not correct either."""

from benchmark import reference_node_affinity as ref


def check(end):
    labels = {n.metadata.name: n.metadata.labels for n in end.nodes}
    pods = [(p.metadata.uid, p.spec.node_name) for p in end.bound]
    init = [p.metadata.uid for p in end.bound
            if p.metadata.name.startswith("init-")]
    terms = ref.required_node_terms(end.pod_template)
    return {"node_affinity_violated": ref.node_affinity_violated(
        terms, labels, pods, [*end.offered, *init])}
