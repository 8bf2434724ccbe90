"""Now and then a bind is sent to the node of an earlier bind instead of
where the scheduler chose: 1 bind in 97 goes where the bind before it
went, 30 at most. A node holds 40 of these pods by cpu and 64 by memory,
so nothing is lost, overpacked or bound twice: only a required hostname
anti-affinity breaks, for both pods of each pair. The control of a
deployment in which every pod needs a node of its own."""

EVERY, AT_MOST = 97, 30


def wrap_hub(hub, node_names, zone_of):
    real_bind, count, last = hub.bind, [0, 0], [None]

    def bind(pod, node_name, *args, **kw):
        count[0] += 1
        if count[0] % EVERY == 0 and count[1] < AT_MOST \
                and last[0] is not None:
            count[1] += 1
            node_name = last[0]
        last[0] = node_name
        return real_bind(pod, node_name, *args, **kw)

    hub.bind = bind
