"""Test configuration.

Tests run on an 8-device virtual CPU mesh (the reference runs its suite
against a pluggable nd4j backend via Maven profiles, SURVEY.md §4; the TPU
analog is XLA's host-platform device-count simulation) with x64 enabled so
gradient checks run in double precision.
"""

import os
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Hermetic compile cache, a new directory for every run: tests must not
# hit a warm cache (the checkout's, or one the caller's environment names) —
# cached executables from an earlier run would turn expected compiles into
# AOT hits. Set before jax is imported, which is when jax reads it.
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="dl4j-test-compile-cache-")
os.environ.pop("DL4J_TPU_COMPILE_CACHE", None)

# Tests run on the CPU backend's virtual devices whatever the machine holds.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

if jax.config.jax_compilation_cache_dir != os.environ["JAX_COMPILATION_CACHE_DIR"]:
    raise RuntimeError(
        "jax was imported before tests/conftest.py could name its compile "
        "cache; the tests would share a cache with earlier runs")
jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest

if len(jax.devices()) < 8:
    pytest.exit(
        f"Tests need >=8 virtual CPU devices (got {len(jax.devices())}). "
        "Unset any conflicting --xla_force_host_platform_device_count in XLA_FLAGS.",
        returncode=3,
    )


@pytest.fixture
def rng():
    return np.random.RandomState(12345)


def make_classification_data(rng, n=64, n_features=4, n_classes=3, dtype="float64"):
    X = rng.randn(n, n_features).astype(dtype)
    W = rng.randn(n_features, n_classes)
    y_idx = np.argmax(X @ W + 0.1 * rng.randn(n, n_classes), axis=1)
    Y = np.eye(n_classes)[y_idx].astype(dtype)
    return X, Y
