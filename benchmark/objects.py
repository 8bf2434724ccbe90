"""Node and pod makers, from the template files under benchmark/templates/.

Copies of the shapes kubernetes_tpu/perf/workloads.py transcribes from
upstream's scheduler_perf YAML (`_node`, `_pod`, `_spreading_pod`,
`_preferred_spreading_pod`), kept here so that a later PR cannot change the
traffic. One builder reads every template; a new pod or node shape is a new
JSON file.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"


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


class PodMaker:
    """Builds pods of one template. The parts every pod of the template
    shares (container, constraints) are built once and shared, as
    Pod.clone() shares them."""

    def __init__(self, tmpl: dict):
        from kubernetes_tpu.api.objects import (
            Container, LabelSelector, ResourceRequirements,
            TopologySpreadConstraint)

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

    def make(self, name: str, node_name: str = ""):
        from kubernetes_tpu.api.objects import ObjectMeta, Pod, PodSpec

        return Pod(
            metadata=ObjectMeta(name=name, uid=f"p-{name}",
                                labels=dict(self._labels)),
            spec=PodSpec(node_name=node_name,
                         containers=list(self._containers),
                         topology_spread_constraints=list(self._tsc)))
