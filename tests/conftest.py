"""Test configuration.

Tests run on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``) so distributed/fleet paths
are actually exercised in CI without TPU hardware — the improvement over
the reference's YAML-only "distributed" tests called out in SURVEY.md §4.
Env vars must be set before jax initializes, hence here at import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the shell may preset a TPU platform
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# persistent compilation cache OFF for the suite (and the subprocesses it
# spawns): the entry points place it at <checkout>/.jax_cache by default
# (utils/profiling.resolve_compile_cache), and CPU test programs must not
# fill the checkout. Tests of the cache itself re-enable it around their
# own tmp directory.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax

import asyncio
import contextlib
import inspect

import numpy as np
import pandas as pd
import pytest


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (no pytest-asyncio in the
    image)."""
    if inspect.iscoroutinefunction(pyfuncitem.function):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(pyfuncitem.function(**kwargs))
        return True
    return None


@pytest.fixture(scope="session")
def live_server():
    """Factory: async context manager serving a model collection dir on a
    real localhost port (for clients that own their own HTTP session)."""
    from aiohttp.test_utils import TestServer

    from gordo_components_tpu.server import build_app

    @contextlib.asynccontextmanager
    async def _live(model_dir: str):
        server = TestServer(build_app(model_dir))
        await server.start_server()
        try:
            yield f"http://{server.host}:{server.port}"
        finally:
            await server.close()

    return _live


@pytest.fixture(scope="session")
def sensor_frame() -> pd.DataFrame:
    """Small deterministic multi-tag frame used across model tests."""
    rng = np.random.RandomState(42)
    n = 200
    t = np.arange(n)
    data = {
        f"tag-{i}": np.sin(0.05 * (i + 1) * t) + rng.normal(scale=0.05, size=n)
        for i in range(4)
    }
    index = pd.date_range("2020-01-01", periods=n, freq="10min", tz="UTC")
    return pd.DataFrame(data, index=index).astype("float32")


@pytest.fixture(scope="session")
def X(sensor_frame) -> np.ndarray:
    return sensor_frame.values


@pytest.fixture(autouse=True, scope="module")
def _bound_compiled_program_state():
    """Free compiled-program state at module boundaries.

    The round-4 suite compiles many hundreds of XLA programs into ONE
    pytest process (fleet buckets x shapes x families x impl A/Bs), and
    jax's per-function executable caches are unbounded — full-suite runs
    started segfaulting inside XLA CPU compilation ~half-way through
    (observed 2026-07-31: 'Fatal Python error: Segmentation fault' in
    backend_compile_and_load at test #~220, while the same test passes in
    isolation). Clearing jax's caches (and the fleet engine's program
    LRU, which would otherwise pin executables alive) at module teardown
    bounds process compile-state; modules rarely share shapes, so the
    recompile cost is near-zero.
    """
    yield
    import gc

    from gordo_components_tpu.parallel import fleet as fleet_mod

    fleet_mod._PROGRAM_CACHE.clear()
    jax.clear_caches()
    gc.collect()
