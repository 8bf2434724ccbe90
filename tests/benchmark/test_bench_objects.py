"""The pod builder against the program's own transcriptions of upstream's
templates, and against a copy of the builder as it stood before it learned
namespace, priority and affinity; and every template that a cell names
against what bench_invariants.py holds of it. No JAX here."""

import copy
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_invariants as inv  # noqa: E402
from benchmark import cell, objects  # noqa: E402
from kubernetes_tpu.perf import workloads  # noqa: E402

# template -> the function of perf/workloads.py that transcribes its YAML
UPSTREAM = {
    "pod-with-pod-affinity":
        lambda: workloads._pod_affinity_pod(0, "sched-1"),
    "pod-with-pod-anti-affinity":
        lambda: workloads._anti_affinity_pod(0, "sched-1"),
    "pod-with-preferred-pod-affinity":
        lambda: workloads._preferred_affinity_pod(0, anti=False),
    "pod-with-preferred-pod-anti-affinity":
        lambda: workloads._preferred_affinity_pod(0, anti=True),
    "pod-with-node-affinity": lambda: workloads._node_affinity_pod(0),
    "pod-low-priority": lambda: workloads._low_priority_pod(0),
    "pod-high-priority": lambda: workloads._high_priority_pod(0),
}
BEFORE = ["pod-default", "pod-spread-required", "pod-spread-preferred"]


class PodMakerBefore:
    """objects.PodMaker as PR 24 wrote it, kept to hold today's cells'
    pods still: four template keys, nothing else set."""

    def __init__(self, tmpl):
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

    def make(self, name, node_name=""):
        from kubernetes_tpu.api.objects import ObjectMeta, Pod, PodSpec

        return Pod(
            metadata=ObjectMeta(name=name, uid=f"p-{name}",
                                labels=dict(self._labels)),
            spec=PodSpec(node_name=node_name,
                         containers=list(self._containers),
                         topology_spread_constraints=list(self._tsc)))


@pytest.mark.parametrize("name", sorted(UPSTREAM))
def test_new_template_builds_the_pod_the_program_transcribes(name):
    tmpl = objects.load_template(name)
    assert tmpl["kind"] == "pod"
    assert "scheduler_perf/templates/" + name + ".yaml" in tmpl["source"]
    assert "perf/workloads.py:_" in tmpl["source"]
    got = objects.PodMaker(tmpl).make("x")
    want = UPSTREAM[name]()
    assert got.metadata.namespace == want.metadata.namespace
    assert got.metadata.labels == want.metadata.labels
    assert [c.resources.requests for c in got.spec.containers] \
        == [c.resources.requests for c in want.spec.containers]
    assert got.spec.priority == want.spec.priority
    assert got.spec.affinity == want.spec.affinity     # dataclasses, deep
    assert got.spec.topology_spread_constraints == []
    assert got.spec.node_name == ""


def test_every_template_a_cell_names_exists_and_builds():
    inv.every_template_named_exists_and_builds(cell.load_manifest(REPO))


def _edit_json(path, **keys):
    with open(path) as f:
        data = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(data, **keys), f)


TEMPLATE_BREACHES = {
    "a mix names a template that does not exist": (
        FileNotFoundError, "benchmark/traffic/required.json",
        {"pod_template": "pod-gone"}),
    "an init group names a template that does not exist": (
        FileNotFoundError, "benchmark/configs/sched-perf-basic-5k.json",
        {"init_pods": [{"count": 10, "template": "pod-default"},
                       {"count": 10, "template": "pod-gone"}]}),
    "a rehearsal size names a pod as its node template": (
        AssertionError, "benchmark/configs/sched-perf-basic-5k.json",
        {"rehearse": {"nodes": {"count": 8, "template": "pod-default"}}}),
    "a mix offers a node": (
        AssertionError, "benchmark/traffic/backlog.json",
        {"pod_template": "node-default"}),
    "a template carries a key the builder does not know": (
        ValueError, "benchmark/templates/pod-default.json",
        {"tolerations": []}),
}


@pytest.mark.parametrize("breach", sorted(TEMPLATE_BREACHES))
def test_a_breach_among_the_templates_fails_the_invariant(tmp_path, breach):
    error, path, keys = TEMPLATE_BREACHES[breach]
    manifest, _before = inv.copy_benchmark(tmp_path)
    inv.every_template_named_exists_and_builds(manifest, tmp_path)
    _edit_json(tmp_path / path, **keys)
    with pytest.raises(error):
        inv.every_template_named_exists_and_builds(manifest, tmp_path)


def test_a_namespace_that_nobody_creates_fails_the_invariant(
        tmp_path, monkeypatch):
    """The namespaces are read off the built pod, not through
    objects.template_namespaces, which is what build_cluster creates them
    from: one that the pod names and that function misses is caught."""
    manifest, _before = inv.copy_benchmark(tmp_path)
    tmpl = objects.load_template("pod-with-pod-affinity")
    _edit_json(tmp_path / "benchmark/templates/pod-spread-required.json",
               namespace="sched-1", pod_affinity=tmpl["pod_affinity"])
    inv.every_template_named_exists_and_builds(manifest, tmp_path)
    real = objects.template_namespaces
    monkeypatch.setattr(objects, "template_namespaces", lambda t: [
        ns for ns in real(t) if ns != "sched-0"])
    with pytest.raises(AssertionError):
        inv.every_template_named_exists_and_builds(manifest, tmp_path)


def test_a_node_template_is_refused_by_the_pod_builder():
    # the fourth file of templates/ that was there is a node's
    with pytest.raises(ValueError, match="unknown keys"):
        objects.PodMaker(dict(objects.load_template("node-default"),
                              requests={}))


@pytest.mark.parametrize("name", BEFORE)
def test_todays_templates_build_what_they_built(name):
    tmpl = objects.load_template(name)
    new, old = objects.PodMaker(tmpl), PodMakerBefore(tmpl)
    for node in ("", "node-7"):
        a, b = new.make("m-1", node), old.make("m-1", node)
        a.metadata.creation_timestamp = b.metadata.creation_timestamp = 0.0
        assert a == b                       # every field of Pod, deep
        assert a.metadata.namespace == "default"
        assert a.spec.priority is None and a.spec.affinity is None
    # the per-template parts are shared between pods, the labels are not
    p, q = new.make("a"), new.make("b")
    assert p.spec.containers[0] is q.spec.containers[0]
    assert all(x is y for x, y in zip(p.spec.topology_spread_constraints,
                                      q.spec.topology_spread_constraints))
    assert p.metadata.labels is not q.metadata.labels


@pytest.mark.parametrize("key,value", [
    ("pod_afinity", {"required": []}),        # a misspelt rule
    ("affinity", {}),
    ("name_prefix", "node"),                  # a node template's key
    ("tolerations", []),                      # not carried yet
])
def test_unknown_template_key_is_an_error(key, value):
    tmpl = dict(objects.load_template("pod-default"), **{key: value})
    with pytest.raises(ValueError, match=key):
        objects.PodMaker(tmpl)


def test_template_namespaces_are_its_own_and_its_terms():
    t = objects.load_template("pod-with-pod-affinity")
    assert sorted(set(objects.template_namespaces(t))) \
        == ["sched-0", "sched-1"]
    assert objects.template_namespaces(
        objects.load_template("pod-default")) == []
    assert objects.template_namespaces(
        objects.load_template("pod-with-preferred-pod-affinity")) == []
    pref = copy.deepcopy(t)
    pref["pod_anti_affinity"] = {"preferred": [
        dict(pref["pod_affinity"]["required"][0], weight=3,
             namespaces=["other"])]}
    assert "other" in objects.template_namespaces(pref)
    pod = objects.PodMaker(pref).make("x")
    term = pod.spec.affinity.pod_anti_affinity.preferred[0]
    assert term.weight == 3 and term.pod_affinity_term.namespaces == ["other"]
    assert pod.spec.affinity.pod_affinity.required[0].namespaces \
        == ["sched-1", "sched-0"]
