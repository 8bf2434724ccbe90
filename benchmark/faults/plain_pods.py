"""The traffic offers upstream's plain pod instead of the mix's own, so the
topology programs the cell names are never taken."""


def pod_template(mix):
    return "pod-default"
