"""Test env: the tests run on the CPU (``JAX_PLATFORMS=cpu``) with 8
virtual devices, so every 'distributed' behavior is tested on a fake
mesh with no real cluster — the TPU transfer of the reference's
local-Spark fixture (SURVEY.md §4: SparkContextSpec -> virtual-device
mesh). The chip is exercised by ``python chip_smoke.py`` through the
chip tool, never from pytest; tests/test_tpu_compile.py compiles the
main path for a described v5e chip without one."""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# Hermetic compile cache: loading a persistent-cache executable written
# earlier in the same session aborts the whole process (SIGABRT inside
# XLA CPU) in test_differential's mesh test on this jax build —
# reproducibly, even with a freshly-emptied cache directory. Disable
# the cache for tests; the suite recompiles everything and stays well
# inside the timing budget.
os.environ["DEEQU_TPU_COMPILE_CACHE"] = ""
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", None)

import pytest  # noqa: E402


@pytest.fixture
def cpu_mesh():
    import numpy as np
    from jax.sharding import Mesh

    devices = np.array(jax.devices("cpu")[:8])
    return Mesh(devices, ("dp",))
