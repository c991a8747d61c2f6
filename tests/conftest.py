"""Test config: force the CPU backend with 8 virtual devices so mesh/
collective tests run without TPU hardware (SURVEY.md §4). Must run before
jax is imported anywhere."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Persistent compilation cache: the suite's wall-time is dominated by XLA CPU
# compiles; caching them makes repeat runs (CI re-runs, -x iterating) start
# hot. Safe to delete the directory at any time. JAX_COMPILATION_CACHE_DIR,
# when set, is the only directory used (jax reads it itself); otherwise the
# fixed tests/.jax_test_cache.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".jax_test_cache"))
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption("--run-slow", action="store_true", default=False,
                     help="also run tests marked slow (full-size model "
                          "compiles, heavyweight parity checks)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavyweight test, skipped unless --run-slow "
                   "or RUN_SLOW=1")
    config.addinivalue_line(
        "markers", "serial: must not run concurrently with other tests "
                   "(multi-process rendezvous on a reserved port); tier-1 "
                   "runs with xdist disabled, and any parallel runner "
                   "must isolate these")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-slow") or os.environ.get("RUN_SLOW") == "1":
        return
    skip = pytest.mark.skip(reason="slow: use --run-slow / RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _seed():
    import incubator_mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    yield
