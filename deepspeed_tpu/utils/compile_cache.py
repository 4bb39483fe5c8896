"""Persistent compilation cache wiring — fast resume's second half.

A preempted-and-relaunched worker pays restore time AND a full recompile of
every step program; the restore side is pipelined (checkpoint.py "parallel
streaming restore"), and this module removes the recompile: the engine
enables jax's persistent compilation cache (``jax_compilation_cache_dir``)
at build time — before any step function traces — so a restarted process
deserializes the prior attempt's executables instead of re-running XLA.

Wiring (docs/resilience.md "Time to resume"), first match wins:

* env ``JAX_COMPILATION_CACHE_DIR`` — jax's own variable.  Where the
  machine sets it, that directory IS the cache: jax reads it at import,
  :func:`enable` sets no other directory in code, and the two sources
  below yield to it with one log line.  The hit/miss listener and the
  size/time floors still install;
* config ``compile_cache: {dir, min_entry_size_bytes}`` (or the
  bare-string shorthand ``"compile_cache": "/path"``) — the engine calls
  :func:`enable_from_config` in ``__init__``;
* env ``DSTPU_COMPILE_CACHE_DIR`` — the fallback when the config carries
  no ``dir`` (and how the launcher hands the directory to relaunched
  workers: :func:`enable` exports it, ``launcher.launch`` re-exports it
  into every spawned/restarted process, and the ``dst`` fan-out allowlist
  already forwards ``DSTPU_*`` and ``JAX_*`` to remote hosts);
* observability — cache hits/misses count into
  ``resilience.COUNTERS.compile_cache_hits`` / ``compile_cache_misses``
  via ``jax.monitoring``, exported as ``Train/Resilience/*`` scalars, so
  "did the restart actually skip compilation?" is a counter, not a guess.

The cache key covers the program, compile options, and backend identity,
so a stale directory can only miss, never corrupt; entries smaller than
``min_entry_size_bytes`` are not written (tiny programs recompile faster
than they deserialize).
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger(__name__)

#: env spelling of the cache directory — exported by :func:`enable` so
#: launcher-relaunched workers (``--max_restarts``) land in the same cache
ENV_DIR = "DSTPU_COMPILE_CACHE_DIR"
#: jax's own spelling; when set it outranks every other source
JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
#: where ``chip_smoke.py`` and ``bench.py`` keep the cache when the machine
#: names none: one fixed, git-ignored directory in the checkout (the path
#: is part of jax's cache key, so a temp name, pid or timestamp never hits)
CHECKOUT_DIR_NAME = ".jax_cache"


def checkout_dir(root: str) -> str:
    """The fixed in-checkout cache directory under ``root``."""
    return os.path.join(os.path.abspath(root), CHECKOUT_DIR_NAME)

_listener_installed = False
_enabled_dir: Optional[str] = None


def _reset_jax_cache() -> None:
    """Drop jax's memoized cache object so a config change takes effect.

    jax initializes the persistent cache AT MOST ONCE per process
    (``_initialize_cache`` latches ``_cache_initialized`` even when no dir
    is configured), so any compile that ran before :func:`enable` — or
    after :func:`disable` — would freeze the old state forever without
    this reset."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def _install_hit_listener() -> None:
    """Count persistent-cache hits/misses into the resilience counters
    (idempotent; the listener is process-wide).

    jax emits no miss event — only ``cache_hits`` and, first, a
    ``compile_requests_use_cache`` per cached-path compile — so a request
    is counted as a miss up front and reclassified when the hit event
    lands (misses = requests - hits once the compile returns)."""
    global _listener_installed
    if _listener_installed:
        return
    from jax import monitoring

    from deepspeed_tpu.resilience.counters import COUNTERS

    def _on_event(event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            COUNTERS.compile_cache_misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            COUNTERS.compile_cache_hits += 1
            COUNTERS.compile_cache_misses -= 1

    monitoring.register_event_listener(_on_event)
    _listener_installed = True


def enable(cache_dir: str, min_entry_size_bytes: int = 0) -> str:
    """Point jax's persistent compilation cache at ``cache_dir``.

    Must run before the programs it should serve compile (the engine calls
    it during ``__init__``; every step function traces lazily after).
    Exports :data:`ENV_DIR` so child/relaunched processes inherit the same
    directory.  Where :data:`JAX_ENV_DIR` is set, that directory is
    enabled instead and ``jax_compilation_cache_dir`` is left as jax read
    it.  Returns the enabled directory."""
    global _enabled_dir
    import jax

    cache_dir = os.path.abspath(os.path.expanduser(cache_dir))
    jax_dir = os.environ.get(JAX_ENV_DIR)
    if jax_dir:
        jax_dir = os.path.abspath(os.path.expanduser(jax_dir))
        if cache_dir != jax_dir:
            logger.info("compile_cache: %s=%s outranks %s", JAX_ENV_DIR,
                        jax_dir, cache_dir)
        cache_dir = jax_dir
    os.makedirs(cache_dir, exist_ok=True)
    if _enabled_dir is not None and _enabled_dir != cache_dir:
        logger.warning(
            "compile_cache: re-pointing the persistent compilation cache "
            "from %s to %s (process-wide setting)", _enabled_dir, cache_dir)
    if not jax_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      int(min_entry_size_bytes))
    # jax's default only caches programs that took >= 1 s to compile; the
    # resume path wants EVERY step program back (min_entry_size_bytes is
    # the configured size floor instead)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # jax latches its cache object on the FIRST compile of the process —
    # without a reset, enabling after any prior jit (or re-pointing the
    # dir) is a silent no-op
    _reset_jax_cache()
    os.environ[ENV_DIR] = cache_dir
    _install_hit_listener()
    _enabled_dir = cache_dir
    logger.info("compile_cache: persistent compilation cache at %s "
                "(min entry %d bytes)", cache_dir, int(min_entry_size_bytes))
    return cache_dir


def disable() -> None:
    """Turn the persistent cache off again (tests; the hit listener stays
    registered but sees no further cache events).  A directory the
    machine set through :data:`JAX_ENV_DIR` is not this module's to
    unset."""
    global _enabled_dir
    import jax

    if not os.environ.get(JAX_ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache()
    os.environ.pop(ENV_DIR, None)
    _enabled_dir = None


def enabled_dir() -> Optional[str]:
    return _enabled_dir


def resolve_dir(config) -> Optional[str]:
    """The directory an engine build asks :func:`enable` for: the config's
    ``compile_cache.dir`` if set, else the :data:`ENV_DIR` environment
    fallback (how a relaunched worker whose config was an in-process dict
    still lands in the same cache), else :data:`JAX_ENV_DIR` — so the
    listener and floors install on a machine that names only that.
    :func:`enable` applies :data:`JAX_ENV_DIR`'s precedence."""
    return (getattr(config, "compile_cache_dir", None)
            or os.environ.get(ENV_DIR) or os.environ.get(JAX_ENV_DIR)
            or None)


def enable_from_config(config) -> Optional[str]:
    """Engine-build hook: enable the cache when configured (no-op
    otherwise).  Returns the enabled directory or None."""
    cache_dir = resolve_dir(config)
    if cache_dir is None:
        return None
    return enable(cache_dir,
                  int(getattr(config, "compile_cache_min_entry_size_bytes",
                              0)))
