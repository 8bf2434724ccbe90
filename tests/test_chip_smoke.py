"""chip_smoke.py on the CPU (tier-1): the rehearsal passes, the smoke
cannot pass without a TPU or on the host fallback path, and the compile
cache directory is placed from outside.

Everything runs in subprocesses — the smoke's parent must stay off JAX,
and the cache-directory check needs a JAX that has read its environment
fresh. tests/conftest.py already put JAX_PLATFORMS=cpu in os.environ.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, SMOKE, *args],
                          capture_output=True, text=True, cwd=REPO,
                          timeout=600)


def _result(proc: subprocess.CompletedProcess) -> dict:
    """The summary (first line), after holding the verdict (LAST line) to
    the driver's contract: exactly these keys, nothing beside them."""
    summary, verdict = (json.loads(line)
                        for line in proc.stdout.strip().splitlines())
    assert set(verdict) == {"ok", "device"}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["ok"], bool)
    assert isinstance(verdict["device"]["count"], int)
    assert verdict == {"ok": summary["ok"], "device": summary["device"]}
    return summary


def test_rehearsal_passes_and_a_machine_without_tpu_fails():
    # no flag, no TPU: non-zero, and NO result on stdout
    proc = _smoke()
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
    # the same legs at tiny sizes on the CPU (leg D needs no rehearsal
    # here: tests/test_multichip.py drives the same scenario)
    proc = _smoke("--rehearse", "--legs", "A,B,C")
    assert proc.returncode == 0, proc.stderr[-4000:]
    r = _result(proc)
    assert r["ok"] is True and r["rehearsal"] is True
    assert r["device"]["platform"] == "cpu"
    assert r["claim"] is None
    assert [r["legs"][leg]["ok"] for leg in "ABC"] == [True] * 3
    assert (r["device_fallbacks"], r["gang_fallbacks"],
            r["quarantined"]) == (0, 0, 0)
    a = r["legs"]["A"]
    # the second run_one process found every launch program in the
    # persistent cache the first one filled
    assert a["warm_compile"]["cache_hits"] > 0
    assert a["warm_compile"]["launch_misses"] == {}
    assert a["measured_compiles"] == [0, 0]
    scenes = r["legs"]["B"]["scenes"]
    assert set(scenes) == {"auction_churn", "required_topology",
                           "soft_topology", "preemption", "gang_wave",
                           "dra_templates"}
    assert all(s["launch_compiles_after_warm"] == 0
               for s in scenes.values())
    assert scenes["auction_churn"]["chain_patches"] >= 2
    assert scenes["preemption"]["victims_evicted"] >= 3
    assert scenes["gang_wave"]["gang_device_launches"] >= 1
    assert scenes["dra_templates"]["dra_host_fallback_pods"] == 0
    assert r["legs"]["C"]["sigterm_rc"] == 0


def test_injected_device_fault_fails_leg_b():
    """With every launch of leg B's first scene faulted through the
    scheduler's fault_injector seam the pods still bind — on the serial
    host path. The smoke must not pass on it."""
    proc = _smoke("--rehearse", "--legs", "B", "--inject-device-fault")
    assert proc.returncode != 0
    r = _result(proc)
    assert r["ok"] is False and r["legs"]["B"]["ok"] is False
    assert "left the device path" in proc.stderr
    # the seam is for the rehearsal only
    assert _smoke("--inject-device-fault").returncode != 0


_CACHE_PROBE = (
    "from kubernetes_tpu.utils import jaxsetup; jaxsetup.setup(); "
    "import jax, jax.numpy as jnp; "
    "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready(); "
    "print(jax.config.jax_compilation_cache_dir)")


def _cache_dir_seen(env: dict) -> str:
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                          capture_output=True, text=True, cwd=REPO,
                          env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_compile_cache_directory_is_placed_from_outside(tmp_path):
    default = os.path.join(REPO, ".jax_cache")
    before = set(os.listdir(default)) if os.path.isdir(default) else set()
    outside = tmp_path / "x"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(outside))
    assert _cache_dir_seen(env) == str(outside)
    assert os.listdir(outside), "the program was not cached where told"
    after = set(os.listdir(default)) if os.path.isdir(default) else set()
    # the suite's other workers compile into the default directory all the
    # while: only an entry of a program the probe ran is the probe's
    probe = {name.rsplit("-", 2)[0] for name in os.listdir(outside)}
    gained = {name for name in after - before
              if name.rsplit("-", 2)[0] in probe}
    assert not gained, f".jax_cache gained {sorted(gained)}"
    env.pop("JAX_COMPILATION_CACHE_DIR")
    assert _cache_dir_seen(env) == default
