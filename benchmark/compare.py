"""The comparison that decides `correct`: the end state of the timed path,
held to the guarantees the configuration and the traffic mix name.

Each name under `checks` in a configuration's or a mix's file is a file of
its own, benchmark/checks/<name>.py, with `check(end) -> {number: count}`:
it takes what it needs from the end state as plain data and hands it to the
plain reference. Every number is a count of broken guarantees, so every
limit is 0. A later configuration brings a new guarantee as a new file.
"""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_by_name(kind: str, name: str, root: str = HERE):
    """The module benchmark/<kind>/<name>.py, found by the name alone."""
    path = os.path.join(root, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no file for {kind} {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def names_in(kind: str, root: str = HERE) -> list[str]:
    return sorted(f[:-3] for f in os.listdir(os.path.join(root, kind))
                  if f.endswith(".py"))


class EndState:
    """What a check may look at, once the window has closed and the
    scheduler is stopped."""

    def __init__(self, hub, sched, pod_template: dict, offered: list[str],
                 watcher, platform: str) -> None:
        self.hub, self.sched = hub, sched
        self.pod_template = pod_template    # the mix's own, not a fault's
        self.offered, self.watcher = offered, watcher
        self.platform = platform
        self.nodes = hub.list_nodes()
        self.bound = [p for p in hub.list_pods() if p.spec.node_name]


def compare(end: EndState, checks: list[str], root: str = HERE) -> dict:
    """Every number compared, beside its limit."""
    out: dict[str, dict] = {}
    for name in checks:
        for number, value in load_by_name("checks", name,
                                          root).check(end).items():
            out[number] = {"value": int(value), "limit": 0}
    return out
