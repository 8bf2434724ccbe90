"""One node more than the deployment has, of the same template and WITHOUT
a zone label, and 1 bind in 997 sent to it whatever the scheduler chose,
30 binds at most. The node holds 40 of these pods by cpu, so it never fills
and no pod is lost or bound twice: only a required zone term breaks, for
the pods that land where the term's topology key is missing. The control of
a one-zone deployment, where no move between its own nodes can break
affinity; the stray node exists only under this fault."""

EVERY, AT_MOST = 997, 30


def wrap_hub(hub, node_names, zone_of):
    from benchmark import objects

    stray = objects.make_node(objects.load_template("node-default"),
                              len(node_names), [])
    if stray.metadata.name in node_names:
        raise ValueError(f"{stray.metadata.name} is one of the cluster's own")
    hub.create_node(stray)
    real_bind, count = hub.bind, [0, 0]

    def bind(pod, node_name, *args, **kw):
        count[0] += 1
        if count[0] % EVERY == 0 and count[1] < AT_MOST:
            count[1] += 1
            node_name = stray.metadata.name
        return real_bind(pod, node_name, *args, **kw)

    hub.bind = bind
