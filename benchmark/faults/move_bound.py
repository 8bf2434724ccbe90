"""1 bound pod in 997 is written again with another node: bound twice."""


def wrap_hub(hub, node_names, zone_of):
    real_bind, count = hub.bind, [0]

    def bind(pod, node_name, *args, **kw):
        count[0] += 1
        real_bind(pod, node_name, *args, **kw)
        if count[0] % 997 == 0:
            moved = hub.get_pod(pod.metadata.uid).clone()
            moved.spec.node_name = next(
                n for n in node_names if n != node_name)
            hub.update_pod(moved)

    hub.bind = bind
