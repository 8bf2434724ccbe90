"""Scheduling loop: share of the window the loop thread spent in idle_wait, which is 1 - its utilisation under the cell's fixed offered rate."""


def read(obs):
    if "idle_wait" not in obs["phase_s"] or not obs["seconds"]:
        return None
    return obs["phase_s"]["idle_wait"] / obs["seconds"]
