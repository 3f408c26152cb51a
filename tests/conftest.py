"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the reference's Gloo-on-localhost trick for
testing collective logic without accelerators — see SURVEY.md §4): env must be set
before jax initializes any backend, hence at conftest import time.
"""

import os

# Force-assign (not setdefault): on a machine with a chip jax would take the TPU by
# default; tests must run on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

assert jax.default_backend() == "cpu" and jax.device_count() >= 8

jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def _load_slowlist():
    path = os.path.join(os.path.dirname(__file__), "slowlist.txt")
    try:
        with open(path) as f:
            return {ln.strip() for ln in f
                    if ln.strip() and not ln.startswith("#")}
    except OSError:
        return set()


def pytest_collection_modifyitems(config, items):
    """Auto-mark measured-slow tests (tests/slowlist.txt) so the default run
    (pytest.ini addopts = -m "not slow") is a fast green signal; explicit
    @pytest.mark.slow still works for new tests (SURVEY §4 CI discipline)."""
    slow = _load_slowlist()
    for item in items:
        if item.nodeid in slow:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _seeded():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture
def compile_cache_config_restored():
    """For tests that call ``paddle_tpu.jit.enable_compile_cache()`` (or an
    entry point that does) in this process: put jax's cache settings back,
    so the rest of the session compiles uncached as before."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


@pytest.fixture(scope="session")
def tp_platform():
    """The multi-device host platform the serving tensor-parallel tests
    (@pytest.mark.tp) shard over. This conftest provisions (and asserts,
    above) the 8-way virtual CPU mesh for the whole suite — XLA_FLAGS is
    set before jax initializes, so it cannot be toggled per test. This
    fixture is the TP tests' explicit CONTRACT with that mesh: it names
    the dependency, returns the device count so tests size their meshes,
    and — belt and braces for a harness that bootstraps the platform
    differently (e.g. tests invoked without this conftest's env control)
    — skips rather than erroring deep inside device_put when fewer than
    2 devices resolved. Session-scoped so MODULE-scoped engine fixtures
    can depend on it (a skip must fire before an engine fixture builds a
    mesh, which would ERROR instead)."""
    n = jax.device_count()
    if n < 2:
        pytest.skip("serving TP tests need >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
    return n
