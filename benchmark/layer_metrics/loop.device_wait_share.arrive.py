"""Scheduling loop: share of the window the loop thread spent in device_launch, its blocked wait for a launch's verdicts (the twin of loop.idle_share.arrive: idle_wait is waiting for pods, this is waiting for the device); nothing where the program reports no such phase."""


def read(obs):
    if "device_launch" not in obs["phase_s"] or not obs["seconds"]:
        return None
    return obs["phase_s"]["device_launch"] / obs["seconds"]
