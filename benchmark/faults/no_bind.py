"""An acknowledged bind that never lands: the scheduler believes the pod
bound and the hub never sees it. Here: every bind (a step that returns its state unchanged)."""

EVERY = 1


def wrap_hub(hub, node_names, zone_of):
    real_bind, count = hub.bind, [0]

    def bind(pod, node_name, *args, **kw):
        count[0] += 1
        if count[0] % EVERY == 0:
            return None
        return real_bind(pod, node_name, *args, **kw)

    hub.bind = bind
