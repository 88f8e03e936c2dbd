"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax initializes.

The reference has no tests at all (SURVEY.md §4); its distributed semantics were
only ever exercised on 2 real GPUs. The TPU-native answer is
``--xla_force_host_platform_device_count=8`` so every sharding/collective test
runs against a real 8-way mesh on CPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep CPU matmuls deterministic-ish and fast on the single-core test host.
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the heavyweight sharded-step compiles dominate
# suite runtime; cache them across pytest runs. JAX_COMPILATION_CACHE_DIR, when
# set, places it (jax reads the variable itself) and nothing is set here.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _CACHE_DIR = os.path.join(os.path.dirname(__file__), "..", ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", os.path.abspath(_CACHE_DIR))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
