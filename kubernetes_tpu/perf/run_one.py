"""Run ONE perf workload in a fresh process and print its result as JSON.

`python -m kubernetes_tpu.perf.run_one <workload_fn> [--scale X]
 [--profile] [--recorder off] [--regret] [--pipelined on|off]`

--profile includes the flight recorder's per-phase/per-plugin breakdown
in the JSON result (bench.py --profile consumes it); --recorder off
disables the always-on recorder (flight_recorder_capacity=0) for the
--trace-overhead on/off comparison; --regret runs with a throwaway
trace export + the v3 alternative rows on so the result's quality
block carries the per-placement regret_mean/regret_p99 columns
(opt-in: the alt top_k + export I/O are a measured-perf change).

The bench driver (bench.py) shells out here per workload — the same
isolation the reference harness gets from one integration-test process
per workload. Process isolation matters empirically: in-process
back-to-back workloads interfere (device-memory/executable-cache
pressure from earlier workloads shows up as multi-second stalls in later
measured phases), while solo runs are clean and reproducible. The
on-disk XLA compile cache keeps each fresh process warm.
"""

from __future__ import annotations

import json
import os
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
    profile = "--profile" in sys.argv
    config = None
    if "--recorder" in sys.argv:
        idx = sys.argv.index("--recorder")
        mode = sys.argv[idx + 1] if idx + 1 < len(sys.argv) else ""
        if mode not in ("on", "off"):
            sys.exit("--recorder expects 'on' or 'off'")
        if mode == "off":
            from kubernetes_tpu.config.types import default_config

            config = default_config()
            config.flight_recorder_capacity = 0
    if "--pipelined" in sys.argv:
        # the pipelined-waves A/B arm selector (paired threshold-ratchet
        # instrumentation): off = strict launch->commit alternation with
        # whole-chain invalidation on every informer event
        idx = sys.argv.index("--pipelined")
        mode = sys.argv[idx + 1] if idx + 1 < len(sys.argv) else ""
        if mode not in ("on", "off"):
            sys.exit("--pipelined expects 'on' or 'off'")
        if config is None:
            from kubernetes_tpu.config.types import default_config

            config = default_config()
        config.pipelined_waves = mode == "on"
    regret_dir = None
    if "--regret" in sys.argv:
        import tempfile

        from kubernetes_tpu.config.types import default_config

        if config is None:
            config = default_config()
        regret_dir = tempfile.mkdtemp(prefix="bench_regret_")
        config.trace_export_path = os.path.join(regret_dir,
                                                "traces.jsonl")
        # regret needs scores + alternatives, not feature vectors; the
        # default keep-last-1 rotation bounds the run's disk footprint
        # (the summary then covers the newest window)
        config.trace_export_alts = True
    t0 = time.time()
    run_workload(factory(), scale=0.005,   # compile pass, same shapes
                 config=config)
    t_warm = time.time() - t0
    if regret_dir is not None:
        # the measured run's regret summary must not include the warm
        # pass's placements
        open(config.trace_export_path, "w").close()
    from kubernetes_tpu.models.pipeline import (
        launch_cache_size,
        launch_programs,
    )

    t0 = time.time()
    # zero-recompile gate: the warm pass (and the chain-patch warmup it
    # triggers) must have compiled every kernel the measured phase needs —
    # a non-zero delta here is a mid-drain recompile eating measured time
    compiles_pre = launch_cache_size()
    r = run_workload(factory(), scale=scale, config=config,
                     profile=profile)
    r["measured_compiles"] = launch_cache_size() - compiles_pre
    if regret_dir is not None:
        import shutil

        shutil.rmtree(regret_dir, ignore_errors=True)
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
