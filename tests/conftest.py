"""Test configuration.

Tests run JAX on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-runs the multichip
path; chip_smoke.py uses the real chip). These env vars must be set before any
jax import, hence here at conftest import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# tests share the persistent compile cache the product places
# (<checkout>/.jax_cache): a rerun loads what the last one compiled.
# jaxlib 0.9's XLA:CPU logs a 4 KB false "machine feature" ERROR line
# on every cached load; keep it out of the captured output.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import asyncio
import inspect

import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio_auto: run coroutine test via asyncio.run")


def pytest_collection_modifyitems(items):
    for item in items:
        if inspect.iscoroutinefunction(getattr(item, "function", None)):
            item.add_marker(pytest.mark.asyncio_auto)


@pytest.hookimpl(hookwrapper=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal asyncio test support (no pytest-asyncio dependency)."""
    fn = pyfuncitem.function
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=60))
        pyfuncitem.obj = lambda *a, **k: None
    yield


@pytest.fixture
def heap_steward():
    """The process's heap steward (server/heap.py) for one test, with the
    collector put back as found whatever the test did: the suite runs
    `--dist loadfile`, and a leaked freeze or threshold would change the
    tests of other files in the same worker. The baseline is thawed first:
    CPython 3.12 itself starts with 375 frozen objects, which no process
    can freeze again once `gc.unfreeze()` has run."""
    import gc

    from hocuspocus_tpu.server.heap import get_heap_steward

    steward = get_heap_steward()
    tuned = ("min_interval_s", "max_interval_s", "growth_share", "load_settle_s", "churn_share", "churn_floor")
    threshold, tuning = gc.get_threshold(), {key: getattr(steward, key) for key in tuned}
    gc.unfreeze()
    try:
        yield steward
    finally:
        while steward.installed:
            steward.uninstall()
        vars(steward).update(tuning)
        gc.unfreeze()
        gc.set_threshold(*threshold)


@pytest.fixture(autouse=True)
def tracer_ring_as_found():
    """The process tracer's ring at the size the test found it: a test that
    shrinks it (`enable_tracing(max_spans=4)`) would leave the tests of other
    files in the same worker a ring that drops the span they look for."""
    from collections import deque

    from hocuspocus_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    size = tracer._spans.maxlen
    yield
    if tracer._spans.maxlen != size:
        tracer._spans = deque(tracer._spans, maxlen=size)
