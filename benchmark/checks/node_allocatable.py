"""No node holds more than its allocatable cpu, memory or pod count."""

from benchmark import reference


def check(end):
    return reference.check_nodes(
        [(n.metadata.name, n.status.allocatable["cpu"],
          n.status.allocatable["memory"], n.status.allocatable["pods"])
         for n in end.nodes],
        [(p.metadata.uid, p.spec.node_name,
          p.spec.containers[0].resources.requests.get("cpu", "0"),
          p.spec.containers[0].resources.requests.get("memory", "0"))
         for p in end.bound])
