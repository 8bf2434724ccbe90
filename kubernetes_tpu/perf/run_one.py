"""Run ONE perf workload in a fresh process and print its result as JSON.

`python -m kubernetes_tpu.perf.run_one <workload_fn> [--scale X]`

`chip_smoke.py` leg A shells out here — the same isolation the
reference harness gets from one integration-test process per workload.
Process isolation matters empirically: in-process back-to-back
workloads interfere (device-memory/executable-cache pressure from
earlier workloads shows up as multi-second stalls in later measured
phases), while solo runs are clean and reproducible. The on-disk XLA
compile cache keeps each fresh process warm.
"""

from __future__ import annotations

import json
import sys


def main() -> None:
    name = sys.argv[1]
    scale = 1.0
    if "--scale" in sys.argv:
        scale = float(sys.argv[sys.argv.index("--scale") + 1])
    from kubernetes_tpu.utils import jaxsetup

    jaxsetup.setup()
    meter = jaxsetup.CompileMeter()
    import time

    from kubernetes_tpu.perf import workloads as W
    from kubernetes_tpu.perf.harness import run_workload

    factory = getattr(W, name)
    t0 = time.time()
    run_workload(factory(), scale=0.005)   # compile pass, same shapes
    t_warm = time.time() - t0
    from kubernetes_tpu.models.pipeline import (
        launch_cache_size,
        launch_programs,
    )

    t0 = time.time()
    # zero-recompile gate: the warm pass (and the chain-patch warmup it
    # triggers) must have compiled every kernel the measured phase needs —
    # a non-zero delta here is a mid-drain recompile eating measured time
    compiles_pre = launch_cache_size()
    r = run_workload(factory(), scale=scale)
    r["measured_compiles"] = launch_cache_size() - compiles_pre
    r["warm_s"] = round(t_warm, 1)
    r["run_s"] = round(time.time() - t0, 1)
    # the device the row was measured on, and whether the run stayed on
    # it (run_workload already refused a run that fell back)
    r.update(jaxsetup.device_info())
    r["device_fallbacks"] = r["stats"]["device_fallbacks"]
    # persistent-cache verdicts over the whole process: a second process
    # of the same workload must hit on every launch program
    r["compile_cache"] = {
        **meter.totals(),
        "launch_misses": meter.misses(
            tuple(fn.__name__ for fn in launch_programs()))}
    print(json.dumps(r))


if __name__ == "__main__":
    main()
