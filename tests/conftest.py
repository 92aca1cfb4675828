"""Test configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def empty_engine():
    """A fresh world-of-1 engine, finalized afterwards."""
    import rabit_tpu

    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    yield
    rabit_tpu.finalize()


@pytest.fixture(scope="session")
def native_lib():
    """Build librabit_tpu.so once per session (skip tests if build fails)."""
    import pathlib
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    lib = root / "rabit_tpu" / "native" / "lib" / "librabit_tpu.so"
    proc = subprocess.run(["make", "-C", str(root / "rabit_tpu" / "native")],
                          capture_output=True, text=True)
    if proc.returncode != 0 or not lib.exists():
        pytest.skip(f"native library build failed:\n{proc.stderr}")
    return lib
