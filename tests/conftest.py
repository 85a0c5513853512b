"""Test harness setup (SURVEY.md §4).

All unit tests run on CPU with 8 fake XLA devices so mesh/DP/TP logic is
exercised without TPU hardware (the standard JAX trick; SURVEY.md §4-3).
Environment must be set before jax imports — hence at conftest import time.
Set TPUSERVE_TEST_TPU=1 to run the suite against the real accelerator.
"""

import os

if not os.environ.get("TPUSERVE_TEST_TPU"):
    # The suite runs on the CPU whatever the environment pre-set: the env var
    # is what child processes inherit and what the serve path's device guard
    # (runtime.check_backend) reads as "the host, on purpose".
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The suite keeps its compile cache apart from a served checkout's
# (<checkout>/.jaxcache): through the environment variable, the one way to
# move the cache (runtime.configure_compile_cache), never a config.update.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jaxcache", "tests"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def toy_cfg():
    from tpuserve.config import ModelConfig

    return ModelConfig(
        name="toy",
        family="toy",
        batch_buckets=[1, 2, 4],
        deadline_ms=10.0,
        dtype="float32",
        num_classes=10,
        parallelism="single",
    )
