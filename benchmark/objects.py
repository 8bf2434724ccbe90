"""Node and pod makers, from the template files under benchmark/templates/.

Copies of the shapes kubernetes_tpu/perf/workloads.py transcribes from
upstream's scheduler_perf YAML (`_node`, `_pod`, `_spreading_pod`,
`_pod_affinity_pod`, `_node_affinity_pod`, `_high_priority_pod` and the
like), kept here so that a later PR cannot change the traffic. One builder
reads every template; a new pod or node shape is a new JSON file.

A pod template may carry: `labels`, `requests`, `container`, `spread`,
`namespace`, `priority`, `pod_affinity`, `pod_anti_affinity` (each
{"required": [term], "preferred": [{"weight": n, ...term}]}, a term being
{"topology_key", "match_labels", "namespaces"}) and `node_affinity`
({"required": [[{"key", "operator", "values"}]]}: terms of expressions).
Any other key is an error, so that a misspelt rule cannot pass as a plain pod.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"
POD_TEMPLATE_KEYS = frozenset((
    "kind", "source", "container", "requests", "labels", "spread",
    "namespace", "priority", "pod_affinity", "pod_anti_affinity",
    "node_affinity"))


def load_template(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "templates", f"{name}.json")) as f:
        return json.load(f)


def make_node(tmpl: dict, index: int, zones: list[str]):
    """node-default.yaml plus labelNodePrepareStrategy's zone label."""
    from kubernetes_tpu.api.objects import (
        Node, NodeSpec, NodeStatus, ObjectMeta)

    name = f"{tmpl['name_prefix']}-{index}"
    labels = {HOST_KEY: name}
    if zones:
        labels[ZONE_KEY] = zones[index % len(zones)]
    return Node(metadata=ObjectMeta(name=name, uid=f"n-{name}", labels=labels),
                spec=NodeSpec(),
                status=NodeStatus(allocatable=dict(tmpl["allocatable"])))


def _pod_terms(rule: dict) -> dict:
    """{"required": [term], "preferred": [{"weight": n, ...term}]} as the
    arguments of PodAffinity / PodAntiAffinity."""
    from kubernetes_tpu.api.objects import (
        LabelSelector, PodAffinityTerm, WeightedPodAffinityTerm)

    def term(t: dict):
        return PodAffinityTerm(
            topology_key=t["topology_key"],
            label_selector=LabelSelector(
                match_labels=dict(t["match_labels"])),
            namespaces=list(t.get("namespaces", [])))

    return {"required": [term(t) for t in rule.get("required", [])],
            "preferred": [WeightedPodAffinityTerm(
                weight=int(t["weight"]), pod_affinity_term=term(t))
                for t in rule.get("preferred", [])]}


def _affinity(tmpl: dict):
    """spec.affinity of a template, or None where it carries no rule."""
    from kubernetes_tpu.api.objects import (
        Affinity, NodeAffinity, NodeSelector, NodeSelectorRequirement,
        NodeSelectorTerm, PodAffinity, PodAntiAffinity)

    parts = {}
    if "node_affinity" in tmpl:
        parts["node_affinity"] = NodeAffinity(required=NodeSelector(
            node_selector_terms=[NodeSelectorTerm(match_expressions=[
                NodeSelectorRequirement(key=e["key"], operator=e["operator"],
                                        values=list(e.get("values", [])))
                for e in exprs])
                for exprs in tmpl["node_affinity"]["required"]]))
    for key, rule_type in (("pod_affinity", PodAffinity),
                           ("pod_anti_affinity", PodAntiAffinity)):
        if key in tmpl:
            parts[key] = rule_type(**_pod_terms(tmpl[key]))
    return Affinity(**parts) if parts else None


class PodMaker:
    """Builds pods of one template. The parts every pod of the template
    shares (container, constraints, affinity) are built once and shared, as
    Pod.clone() shares them."""

    def __init__(self, tmpl: dict):
        from kubernetes_tpu.api.objects import (
            Container, LabelSelector, ResourceRequirements,
            TopologySpreadConstraint)

        unknown = sorted(set(tmpl) - POD_TEMPLATE_KEYS)
        if unknown:
            raise ValueError(f"pod template has unknown keys {unknown}; "
                             f"it may carry {sorted(POD_TEMPLATE_KEYS)}")
        self._labels = dict(tmpl.get("labels", {}))
        self._containers = [Container(
            name=tmpl.get("container", "pause"),
            resources=ResourceRequirements(
                requests=dict(tmpl["requests"])))]
        self._tsc = [TopologySpreadConstraint(
            max_skew=c["max_skew"], topology_key=c["topology_key"],
            when_unsatisfiable=c["when_unsatisfiable"],
            label_selector=LabelSelector(
                match_labels=dict(c["match_labels"])))
            for c in tmpl.get("spread", [])]
        # absent keys leave ObjectMeta's and PodSpec's own defaults
        self._meta = {"namespace": tmpl["namespace"]} \
            if "namespace" in tmpl else {}
        self._spec = {"priority": int(tmpl["priority"])} \
            if "priority" in tmpl else {}
        affinity = _affinity(tmpl)
        if affinity is not None:
            self._spec["affinity"] = affinity

    def make(self, name: str, node_name: str = ""):
        from kubernetes_tpu.api.objects import ObjectMeta, Pod, PodSpec

        return Pod(
            metadata=ObjectMeta(name=name, uid=f"p-{name}",
                                labels=dict(self._labels), **self._meta),
            spec=PodSpec(node_name=node_name,
                         containers=list(self._containers),
                         topology_spread_constraints=list(self._tsc),
                         **self._spec))


def template_namespaces(tmpl: dict) -> list[str]:
    """Every namespace a pod template names: its own and those its
    pod-(anti-)affinity terms select in."""
    out = [tmpl["namespace"]] if "namespace" in tmpl else []
    for key in ("pod_affinity", "pod_anti_affinity"):
        rule = tmpl.get(key, {})
        for t in rule.get("required", []) + rule.get("preferred", []):
            out.extend(t.get("namespaces", []))
    return out
