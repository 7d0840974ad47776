"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Must set the env vars before jax is imported anywhere, so this executes at
conftest import time (pytest loads conftest before test modules).
"""

import os

# Force CPU: the suite runs on the CPU backend (8 virtual devices) even on
# a machine with a chip — the chip belongs to one process at a time, and
# the test workers are several. Both the env var and the jax config are
# pinned before any jax operation runs.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def vocab():
    from volcano_tpu.api import ResourceVocab
    return ResourceVocab(["nvidia.com/gpu"])


@pytest.fixture(scope="session")
def eight_device_subprocess():
    """Run a python snippet in a SUBPROCESS whose jax is guaranteed an
    8-device CPU host platform (JAX_PLATFORMS=cpu +
    xla_force_host_platform_device_count=8 forced unconditionally).

    The in-process conftest above only appends the device-count flag when
    XLA_FLAGS is unset, so an outer environment that pre-set XLA_FLAGS
    (a TPU CI rig, a debugging session) can leave this process with one
    device — the subprocess runner keeps the real multi-device
    shard_map collective tests exercising D=8 regardless. Returns
    ``run(code) -> CompletedProcess`` with repo root + tests/ on
    sys.path; asserts rc==0 and returns the process for stdout checks.
    """
    import subprocess
    import sys as _sys

    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)

    def run(code: str, timeout: float = 300.0):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = os.pathsep.join(
            [root, here, env.get("PYTHONPATH", "")])
        proc = subprocess.run(
            [_sys.executable, "-c", code], env=env, cwd=root,
            capture_output=True, text=True, timeout=timeout)
        assert proc.returncode == 0, (
            f"subprocess failed rc={proc.returncode}\n"
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}")
        return proc

    return run
