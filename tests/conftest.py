"""Test env: force an 8-device virtual CPU mesh.

Mirrors the reference's CI posture (closed GPU libs absent, tests run the
open pipeline on CPU; SURVEY.md §4): sharding/collective paths are exercised
on a virtual device mesh; the real-TPU path is covered by chip_smoke.py.
The suite pins the CPU platform itself so it never takes the chip from
another process, whatever JAX_PLATFORMS says.
"""

import os

# tests never write a compile cache into the checkout (utils/compilecache);
# the environment carries it to the worker processes some tests spawn
os.environ.setdefault("PBOX_COMPILE_CACHE_DIR", "off")

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True)
def _isolate_compile_cache():
    """The persistent XLA compile cache (utils/compilecache) is
    process-global jax state. A test that turns it on must not change
    compile behavior for every later test in the process: detach it after
    each test so suite results never depend on test order."""
    yield
    from paddlebox_tpu.utils import compilecache

    if compilecache.enabled_dir() is not None:
        compilecache.disable()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "chaos: fault-injected robustness schedules (fast ones run in tier-1)"
    )
    config.addinivalue_line("markers", "slow: excluded from the tier-1 suite")
