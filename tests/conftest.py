"""Test-session configuration.

JAX-touching tests (ops/parallel/graft-entry) run on a virtual 8-device
CPU mesh; the env vars must be set before jax is first imported, so they
are set here at conftest import time.
"""

import asyncio
import inspect

import pytest

# Force CPU: unit tests run on 8 virtual CPU devices whatever the
# machine holds (kernels in interpret mode); the chip is chip_smoke.py's.
from zkstream_tpu.utils.platform import force_cpu  # noqa: E402

force_cpu(n_devices=8)

# The session compiles everything itself: FleetIngest turns JAX's
# persistent cache on (utils/platform.enable_compile_cache), and a suite
# whose programs came from an earlier run's .jax_cache would not be
# testing this tree's.  The cache has its own tests, in subprocesses.
import jax  # noqa: E402

jax.config.update('jax_enable_compilation_cache', False)


# -- minimal async-test support (pytest-asyncio is not in the image) --

@pytest.fixture
def event_loop():
    """One fresh event loop per test; fixtures drive it explicitly."""
    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    yield loop
    loop.run_until_complete(loop.shutdown_asyncgens())
    asyncio.set_event_loop(None)
    loop.close()


@pytest.fixture
def server(event_loop):
    """One in-process ZK server per test (shared by the single-server
    integration suites)."""
    from zkstream_tpu.server import ZKServer

    srv = event_loop.run_until_complete(ZKServer().start())
    yield srv
    event_loop.run_until_complete(srv.stop())


def pytest_configure(config):
    config.addinivalue_line(
        'markers', 'timeout(seconds): per-test budget override for '
        'the async runner (default 30 s)')
    config.addinivalue_line(
        'markers', 'slow: excluded from the tier-1 fast suite '
        "(run with -m 'not slow'); the chaos campaign and every "
        'default test stay tier-1 compatible')


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests on the test's event_loop fixture."""
    if not inspect.iscoroutinefunction(pyfuncitem.obj):
        return None
    loop = pyfuncitem.funcargs.get('event_loop')
    own_loop = loop is None
    if own_loop:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
    try:
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames
                  if name in pyfuncitem.funcargs}
        mark = pyfuncitem.get_closest_marker('timeout')
        budget = mark.args[0] if mark else 30
        loop.run_until_complete(
            asyncio.wait_for(pyfuncitem.obj(**kwargs), timeout=budget))
    finally:
        if own_loop:
            loop.run_until_complete(loop.shutdown_asyncgens())
            asyncio.set_event_loop(None)
            loop.close()
    return True
