"""Every launch fails on the device (the program's own chaos injector), so
every batch falls down the containment ladder to the host path."""


def after_scheduler(sched):
    from kubernetes_tpu.chaos import DeviceChaos, DeviceChaosConfig

    sched.fault_injector = DeviceChaos(DeviceChaosConfig(
        seed=1, launch_error_rate=1.0))
