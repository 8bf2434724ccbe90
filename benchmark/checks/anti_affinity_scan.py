"""The mix's pods carry a required podAntiAffinity term, and its launches
took the hard serial commit scan over hostname-wide domain maps:
DeviceProfiler shapes with `topo` and `serial`, without `soft` (the init
pods' slots carry terms, so even a batch of plain pods is a topology launch
in this deployment, a soft-only one: affinity_scan.py), and with a `d_cap`
of at least the node count, since the term's key is the hostname and every
node is a domain of its own."""

from benchmark import reference_anti_affinity as ref


def check(end):
    shapes = [(dict(shape), rec["launches"])
              for shape, rec in end.sched.profiler.shapes.items()]
    scans = sum(n for s, n in shapes
                if s.get("topo") and s.get("serial") and not s.get("soft")
                and (s.get("d_cap") or 0) >= len(end.nodes))
    return {"anti_affinity_terms_missing":
            not ref.required_anti_terms(end.pod_template),
            "hostname_scan_launches_missing": not scans}
