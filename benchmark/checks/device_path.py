"""No batch left the device path: no device fallback, no quarantine, no
device-fault gang fallback (perf/harness.assert_device_path)."""


def check(end):
    from kubernetes_tpu.perf.harness import DeviceFallback, assert_device_path

    try:
        assert_device_path(end.sched)
        left = 0
    except DeviceFallback:
        left = 1
    return {"device_fallbacks": end.sched.stats["device_fallbacks"],
            "quarantined": end.sched.stats["quarantined"],
            "left_device_path": left}
