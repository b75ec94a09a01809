"""Test harness config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's multi-process localhost strategy (SURVEY §4.4) the
TPU-native way: instead of spawning one process per rank with env-var
rendezvous, we give XLA 8 host devices and exercise the same SPMD code paths
(shard_map/pjit/collectives) in-process.
"""
import os
import tempfile

# keep the kernel-autotune cache out of the user's home and isolated per
# test session — unconditional, so an exported PADDLE_TPU_AUTOTUNE_CACHE
# can neither leak test winners out nor make test dispatch history-dependent
os.environ["PADDLE_TPU_AUTOTUNE_CACHE"] = os.path.join(
    tempfile.gettempdir(), f"paddle_tpu_test_autotune_{os.getpid()}.json")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
# entry points under test (servers, bench tools) place the persistent
# compilation cache in the checkout; tests neither write there nor
# depend on what an earlier run left
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 gate "
        "(-m 'not slow')")


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import paddle_tpu as paddle

    paddle.seed(0)
    yield
