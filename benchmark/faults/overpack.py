"""The answer altered where it is produced: every bind lands on one of the
first four nodes, whatever the scheduler chose."""


def wrap_hub(hub, node_names, zone_of):
    real_bind, count = hub.bind, [0]

    def bind(pod, node_name, *args, **kw):
        count[0] += 1
        return real_bind(pod, node_names[count[0] % 4], *args, **kw)

    hub.bind = bind
