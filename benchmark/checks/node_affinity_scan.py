"""The mix's pods carry a required node-affinity term, and the launches
that carried them compiled the full node-affinity kernels: a DeviceProfiler
shape whose active launch features hold `nodeaffinity` (the selector
kernels of ops/filters.node_affinity and ops/scores.node_affinity_score;
`nodeaffinity_pin`, the metadata.name pin alone, does not count). A program
without that feature never evaluates the term on the device.

A program whose shape key does not name the launch features (no `active`
keyword of telemetry/profiler.shape_key, as before they were named) cannot
show which programs its launches took: there `node_affinity_launches_missing`
is left out, not guessed, and the placements are still held to the terms by
required_node_affinity."""

import inspect

from benchmark import reference_node_affinity as ref


def check(end):
    from kubernetes_tpu.telemetry.profiler import shape_key

    out = {"node_affinity_terms_missing":
           not ref.required_node_terms(end.pod_template)}
    if "active" in inspect.signature(shape_key).parameters:
        launches = sum(rec["launches"]
                       for shape, rec in end.sched.profiler.shapes.items()
                       if "nodeaffinity" in dict(shape).get("active", ()))
        out["node_affinity_launches_missing"] = not launches
    return out
