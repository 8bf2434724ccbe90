"""The mix's pods carry a required podAffinity term and its launches took
the hard serial commit scan (DeviceProfiler shapes with `topo` and `serial`
and without `soft`), as serial_scan says of a DoNotSchedule spread rule.
`soft` has to be looked at here: the init pods' slots carry terms, so even
a batch of plain pods is a topology launch in this deployment, a soft-only
one, which takes the auction off the CPU and the soft scan on it."""

from benchmark import reference_affinity


def check(end):
    shapes = [(dict(shape), rec["launches"])
              for shape, rec in end.sched.profiler.shapes.items()]
    scans = sum(n for s, n in shapes
                if s.get("topo") and s.get("serial") and not s.get("soft"))
    return {"affinity_terms_missing":
            not reference_affinity.required_terms(end.pod_template),
            "affinity_scan_launches_missing": not scans}
