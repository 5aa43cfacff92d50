"""Persistent XLA compile cache: one place decides where it lives.

Every process pays full XLA compilation of the same programs (same shapes
— the pad-bucket discipline exists precisely so shapes repeat). jax ships
a persistent compilation cache keyed on the HLO *and the cache path*, so
the directory must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax already read it at import; this
  module uses it and sets NO directory in code, so whoever launched the
  process (a chip machine that keeps a cache between calls) owns the
  placement.
- otherwise ``<checkout>/.jax_cache`` (gitignored) — fixed, never derived
  from a temp name, pid, time or checkpoint root.
- the ``compile_cache_dir`` flag only turns the cache off (``off``; the
  test suite runs that way so tests never write into the checkout).

:func:`enable` applies that rule, drops the size/time thresholds so every
program is cached (the pass boundary's small eager scatters included), and
registers a ``jax.monitoring`` listener ONCE per process; hit/miss/request
counters surface as ``compile_cache.*`` stats and through :func:`stats`,
which ``chip_smoke.py`` and ``benchmark/run.py`` embed in their JSON so a cold run
(hits == 0) and a warm run (hits > 0, shorter warm-up) are distinguishable.

**What the cache must not serve** (jax 0.9.0, found on the chip and on the CPU,
PR 38): an executable read back from the cache has lost every argument and
result layout that was not the device's default. It takes a buffer laid out
as the program asked for one in the default layout (the TPU refuses the size,
the CPU computes on the wrong elements) and labels its results likewise. So a
program that carries such a layout is built under :func:`bypassed`, and a
process that holds an array in one (a pass table in the layout its loop
carries it in, ``train/table_format.py``) calls :func:`suspend`: from then on
every eager reader of that array would otherwise write, and a later process
read, an entry of that kind.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import Dict, Optional

from paddlebox_tpu import config
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_GET, STAT_SET

logger = logging.getLogger(__name__)

_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def _check_flag(v: str) -> None:
    if v not in ("auto", "off"):
        raise ValueError(
            f"compile_cache_dir={v!r}: 'auto' or 'off' (place the cache "
            f"with the {_ENV} environment variable)"
        )


config.define_flag(
    "compile_cache_dir",
    "auto",
    f"persistent XLA compile cache: 'auto' = ${_ENV} when set, else the "
    "fixed <checkout>/.jax_cache; 'off' disables",
    validator=_check_flag,
)

_lock = threading.Lock()
_state = {"dir": None, "listener": False}  # guarded-by: _lock


def _listener(event: str, **kwargs) -> None:
    # jax.monitoring event -> our stat, one literal per branch
    if event == "/jax/compilation_cache/cache_hits":
        STAT_ADD("compile_cache.hits")
    elif event == "/jax/compilation_cache/cache_misses":
        STAT_ADD("compile_cache.misses")
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        STAT_ADD("compile_cache.requests")


def enable() -> Optional[str]:
    """Turn the persistent cache on where the policy says; returns the
    directory, or None when the flag says ``off``. Idempotent."""
    import jax
    from jax._src import compilation_cache

    if str(config.get_flag("compile_cache_dir")) == "off":
        return None
    cache_dir = os.environ.get(_ENV)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax LATCHES cache-unused at the first compile that ran without a
    # cache dir (is_cache_used checks once per task); an entrypoint that
    # compiled anything before calling enable() would silently get no
    # caching at all. reset_cache() clears the latch so the next compile
    # re-evaluates against the directory now configured.
    compilation_cache.reset_cache()
    with _lock:
        _state["dir"] = cache_dir
        if not _state["listener"]:
            jax.monitoring.register_event_listener(_listener)
            _state["listener"] = True
    return cache_dir


def enabled_dir() -> Optional[str]:
    with _lock:
        return _state["dir"]


def disable() -> None:
    """Undo :func:`enable`. The cache is process-global state — tests that
    enable it use this to keep the setting from leaking into every later
    test. A directory placed by the environment is not ours to unset."""
    import jax
    from jax._src import compilation_cache

    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", None)
    with _lock:
        _state["dir"] = None
    jax.config.update("jax_enable_compilation_cache", True)  # a suspension ends here
    compilation_cache.reset_cache()


def _use_cache(on: bool) -> None:
    import jax
    from jax._src import compilation_cache

    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()  # jax latches its answer at the first compile


def suspend(reason: str) -> None:
    """No program of this process is read from or written to the cache from
    now on (see the module's note on layouts). :func:`disable` lifts it."""
    import jax

    if jax.config.jax_enable_compilation_cache:
        _use_cache(False)
        STAT_SET("compile_cache.suspended", 1)
        logger.info("persistent compile cache off for this process: %s", reason)


@contextlib.contextmanager
def bypassed():
    """What compiles inside is neither read from nor written to the cache.
    Process-wide while it lasts: a compile on another thread misses too."""
    import jax

    was_on = bool(jax.config.jax_enable_compilation_cache)
    if was_on:
        _use_cache(False)
    try:
        yield
    finally:
        if was_on:
            _use_cache(True)


def stats() -> Dict:
    """Counters + entry census for artifact embedding (smoke and bench
    JSON). ``hits``/``misses``/``requests`` are process-lifetime."""
    d = enabled_dir()
    entries = 0
    if d is not None and os.path.isdir(d):
        entries = sum(1 for n in os.listdir(d) if n.endswith("-cache"))
    return {
        "enabled": d is not None,
        "dir": d,
        "hits": int(STAT_GET("compile_cache.hits")),
        "misses": int(STAT_GET("compile_cache.misses")),
        "requests": int(STAT_GET("compile_cache.requests")),
        "entries": entries,
    }
