"""In-process backend bring-up (utils/backendguard.py) and the persistent
XLA compile cache's placement rule (utils/compilecache.py): a missing
platform is a typed error, never a switch to the CPU; the cache goes where
JAX_COMPILATION_CACHE_DIR says, else to one fixed path in the checkout."""

import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu import config
from paddlebox_tpu.utils import compilecache
from paddlebox_tpu.utils.backendguard import (
    BackendInfo,
    BackendUnavailableError,
    bring_up,
)
from paddlebox_tpu.utils.monitor import STAT_GET

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_missing_platform_raises_typed_error_and_never_switches():
    """The acceptance scenario: a TPU is required, jax has the CPU. The
    error names both, and the process is left on the platform it had."""
    with pytest.raises(BackendUnavailableError) as ei:
        bring_up(require="tpu")
    assert "tpu" in str(ei.value) and "cpu" in str(ei.value)
    assert isinstance(ei.value, RuntimeError)
    assert jax.default_backend() == "cpu"
    assert float(jnp.sum(jnp.ones(4))) == 4.0  # still usable, still CPU


def test_backend_info_serializes_for_artifacts():
    d = bring_up().as_dict()
    assert d == {
        "platform": "cpu",
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": jax.device_count(),
    }
    assert BackendInfo(**d) == bring_up()


def test_bring_up_is_in_process(monkeypatch):
    """No probe child: a second process would ask for a chip this one may
    already hold."""

    def no_children(*a, **k):
        raise AssertionError("backend bring-up spawned a process")

    monkeypatch.setattr(subprocess, "run", no_children)
    monkeypatch.setattr(subprocess, "Popen", no_children)
    info = bring_up(require=jax.default_backend())
    assert info.platform == jax.default_backend()
    assert info.n_devices == jax.device_count()


def _run_without_chip(cmd, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=300
    )


@pytest.mark.slow
def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    """The driver runs the script alone, without the program: it must fail
    there, and without a chip, and print no result either way."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    alone = _run_without_chip([sys.executable, "chip_smoke.py"], str(tmp_path))
    assert alone.returncode != 0 and '"ok"' not in alone.stdout
    here = _run_without_chip([sys.executable, "chip_smoke.py"], REPO)
    assert here.returncode != 0 and '"ok"' not in here.stdout
    assert "platform=cpu" in here.stdout


def test_backend_init_failure_is_the_typed_error(monkeypatch):
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(BackendUnavailableError, match="Unable to initialize"):
        bring_up()


@pytest.fixture
def cache_on(tmp_path, monkeypatch):
    """compile_cache_dir=auto (the suite runs "off") with the fixed
    in-checkout path pointed into tmp_path."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compilecache, "DEFAULT_DIR", str(tmp_path / ".jax_cache"))
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    config.set_flag("compile_cache_dir", "auto")
    yield tmp_path
    config.set_flag("compile_cache_dir", "off")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)


def test_compile_cache_flag_is_auto_or_off(cache_on):
    assert os.path.basename(compilecache.DEFAULT_DIR) == ".jax_cache"
    config.set_flag("compile_cache_dir", "off")
    assert compilecache.enable() is None
    assert compilecache.enabled_dir() is None
    assert jax.config.jax_compilation_cache_dir is None
    assert not os.path.exists(compilecache.DEFAULT_DIR)
    # a path is not a value: placement belongs to the environment variable
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        config.set_flag("compile_cache_dir", "/tmp/some/dir")


def test_compile_cache_default_path_counts_hits(cache_on):
    """Unset environment: entries land in the one fixed directory, and the
    same program compiled twice from distinct function objects is served
    from disk the second time and counted as a hit."""
    got = compilecache.enable()
    assert got == compilecache.DEFAULT_DIR == str(cache_on / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compilecache.enabled_dir() == got

    hits0 = STAT_GET("compile_cache.hits")
    misses0 = STAT_GET("compile_cache.misses")
    x = jnp.arange(64, dtype=jnp.float32)
    cold = np.asarray(jax.jit(lambda v: v * 3.0 + 1.0)(x))
    assert STAT_GET("compile_cache.misses") > misses0  # populated disk
    # a DISTINCT function object with an identical jaxpr: jax's in-memory
    # jit cache can't serve it, the persistent cache must
    warm = np.asarray(jax.jit(lambda v: v * 3.0 + 1.0)(x))
    assert STAT_GET("compile_cache.hits") > hits0
    np.testing.assert_array_equal(cold, warm)

    s = compilecache.stats()
    assert s["enabled"] and s["dir"] == got and s["entries"] >= 1
    assert s["hits"] >= 1 and s["misses"] >= 1
    assert s["requests"] >= s["hits"] + s["misses"] - 1


def test_environment_places_the_cache_and_code_sets_no_directory(
    cache_on, monkeypatch
):
    """JAX_COMPILATION_CACHE_DIR=/x: jax read it at import, so the module
    must use it, count in it, and never call
    jax.config.update("jax_compilation_cache_dir", ...) — enabling or
    disabling."""
    env_dir = str(cache_on / "x")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    jax.config.update("jax_compilation_cache_dir", env_dir)  # jax's import
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real_update(name, val)),
    )
    try:
        assert compilecache.enable() == env_dir
        np.asarray(jax.jit(lambda v: v * 5.0 - 2.0)(jnp.arange(32.0)))
        assert any(n.endswith("-cache") for n in os.listdir(env_dir))
        assert not os.path.exists(compilecache.DEFAULT_DIR)
        assert compilecache.stats()["dir"] == env_dir
        compilecache.disable()
        assert "jax_compilation_cache_dir" not in updates
        assert "jax_persistent_cache_min_compile_time_secs" in updates
        assert jax.config.jax_compilation_cache_dir == env_dir
    finally:
        real_update("jax_compilation_cache_dir", None)
