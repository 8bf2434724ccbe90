"""Scheduling loop: share of the window the loop thread stood off the CPU inside its host-work phases (wall less CPU seconds of benchmark/host_wait.py's HOST_WORK_PHASES); nothing where the program reports no .cpu series."""

from benchmark import host_wait


def read(obs):
    return host_wait.host_wait_share(obs)
