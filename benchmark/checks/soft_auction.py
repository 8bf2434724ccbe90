"""The mix's launches took the soft-topology program, and off the CPU the
auction and not the scan (on the CPU the program takes the scan)."""


def check(end):
    soft = [(dict(shape), rec["launches"])
            for shape, rec in end.sched.profiler.shapes.items()
            if dict(shape).get("soft")]
    out = {"soft_launches_missing": not sum(n for _s, n in soft)}
    if end.platform != "cpu":
        out["soft_launches_off_auction"] = sum(
            n for s, n in soft if s.get("serial"))
    return out
