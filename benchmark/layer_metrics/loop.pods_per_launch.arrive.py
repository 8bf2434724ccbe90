"""Scheduling loop: pods bound over DeviceProfiler launches, in the window."""

from benchmark import readers


def read(obs):
    return readers.pods_per_launch(obs)
