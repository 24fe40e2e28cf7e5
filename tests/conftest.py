"""Test config: force JAX onto a virtual 8-device CPU mesh.

Chip runs happen only through ``chip_smoke.py`` / ``bench.py``; all tests
(including the sharded multi-chip relay-step tests) run on the CPU backend
with ``--xla_force_host_platform_device_count=8`` so they are hermetic and
fast.  The two environment variables below are enough: nothing else in the
installation selects a platform.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Minimal async-test support (pytest-asyncio isn't in the image): any test
# coroutine function runs under asyncio.run with a 30 s watchdog.
import asyncio  # noqa: E402
import inspect  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {k: pyfuncitem.funcargs[k]
                  for k in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=30))
        return True
    return None
