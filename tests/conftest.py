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
