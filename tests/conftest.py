"""Test fixture: simulate an 8-device TPU pod slice with CPU devices.

Mirrors the reference's multi-worker-on-one-host simulation strategy
(reference ``tests/internal/multi_process.py:9-52`` spawns N processes, one
per CUDA device).  On TPU/JAX the analog is a single process with N virtual
devices: we force the host platform to expose 8 CPU devices and run every
sharded computation over a real ``jax.sharding.Mesh``, so collectives execute
with genuine SPMD semantics.
"""

import os

# The suite is a CPU simulation wherever it runs (a host with a real chip
# included): both are plain environment settings, read by JAX at import.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_enable_x64", False)

# Persistent compilation cache: the suite's wall time is dominated by XLA
# compiles, most of which are identical run to run.  One rule, one function
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache).
from bagua_tpu.env import setup_compile_cache  # noqa: E402

setup_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs


@pytest.fixture()
def group():
    import bagua_tpu

    return bagua_tpu.init_process_group(intra_size=4)
