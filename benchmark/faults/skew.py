"""Every bind is moved into the first zone, round-robin over its nodes, so
no node fills and only the spread constraint breaks."""


def wrap_hub(hub, node_names, zone_of):
    real_bind, count = hub.bind, [0]
    zones = sorted(set(zone_of.values()))
    pool = [n for n in node_names if zone_of.get(n) == zones[0]] \
        if zones else node_names

    def bind(pod, node_name, *args, **kw):
        count[0] += 1
        return real_bind(pod, pool[count[0] % len(pool)], *args, **kw)

    hub.bind = bind
