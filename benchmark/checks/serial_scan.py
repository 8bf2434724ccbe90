"""The mix's pods carry a DoNotSchedule rule and its launches took the
serial commit scan (DeviceProfiler shapes with `topo` and `serial`)."""

from benchmark import reference


def check(end):
    scans = sum(rec["launches"]
                for shape, rec in end.sched.profiler.shapes.items()
                if dict(shape).get("topo") and dict(shape).get("serial"))
    return {"required_rules_missing":
            not reference.required_rules(end.pod_template),
            "scan_launches_missing": not scans}
