"""Test harness config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's multi-process localhost strategy (SURVEY §4.4) the
TPU-native way: instead of spawning one process per rank with env-var
rendezvous, we give XLA 8 host devices and exercise the same SPMD code paths
(shard_map/pjit/collectives) in-process.
"""
import os
import shutil
import signal
import tempfile
import threading
import traceback

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

# One compilation cache for the SESSION, in a temp dir of its own: the
# suite compiles the same tiny programs over and over (every engine a
# test builds jits its own copies; every xdist worker and every child
# process starts cold), and the tier-1 run sits at its time limit
# (1,472 s without the cache, 1,103 s with it, six workers).  The
# directory is named after the process that runs the session (an xdist
# worker's parent), so the workers share it, and that process removes
# it when the session ends: tests neither write into the checkout nor
# depend on what an earlier run left.  With the variable set, the entry
# points under test (servers, bench tools: framework/compile_cache.py)
# set no directory of their own, and their child processes share it.
_SESSION_OWNER = (os.getppid() if "PYTEST_XDIST_WORKER" in os.environ
                  else os.getpid())
_SESSION_CACHE = os.path.join(
    tempfile.gettempdir(), f"paddle_tpu_test_jax_cache_{_SESSION_OWNER}")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _SESSION_CACHE
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_sessionfinish(session):
    if _SESSION_OWNER == os.getpid():
        shutil.rmtree(_SESSION_CACHE, ignore_errors=True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 gate "
        "(-m 'not slow')")


_TEST_LIMIT_S = 300.0


@pytest.fixture(autouse=True)
def _time_limit(request):
    """A hang costs one test 300 s, not the suite its 1,470: when the
    timer fires, the test fails with its name and the stack it stood
    at.  A guard, not a budget — no test should come near it.  (SIGALRM
    reaches the main thread, where pytest and its xdist workers run the
    tests; a test blocked inside one C call fails when that returns.)"""
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    limit = _TEST_LIMIT_S

    def over(signum, frame):
        pytest.fail(
            f"{request.node.nodeid} ran over the {limit:g} s a test may "
            "take; it stood at:\n"
            + "".join(traceback.format_stack(frame, limit=12)),
            pytrace=False)

    previous = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import paddle_tpu as paddle

    paddle.seed(0)
    yield
