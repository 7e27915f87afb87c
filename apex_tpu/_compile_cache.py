"""The persistent XLA compilation cache, placed from OUTSIDE the
program — one function (:func:`enable_compile_cache`) that every entry
point calls (``chip_smoke.py``, ``bench.py``, ``__graft_entry__.py``,
``tests/conftest.py``) — with hit/miss observability.

Placement rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and no code
  here sets another directory — whoever runs the program (a chip
  runner that keeps a cache between calls, CI) owns the location;
- otherwise ``<checkout>/.jit_cache`` (git-ignored). The path is part
  of nothing's identity but must be STABLE: a directory built from a
  temporary name, a pid or the time is a cache that never hits.

Enabling also installs ``jax.monitoring`` listeners for the persistent
cache's hit/miss events, so :func:`cache_stats` (and the
``compile_cache/hits`` / ``compile_cache/misses`` telemetry counters)
answer "is the cache actually warm?" — a cache that silently misses
every compile (key drift across jax versions, an evicted dir) costs the
full compile time while looking enabled.
"""

import os
import threading

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_STATS_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0}
_LISTENER_INSTALLED = False


def _on_cache_event(event, **kwargs):
    if event == _HIT_EVENT:
        key = "hits"
    elif event == _MISS_EVENT:
        key = "misses"
    else:
        return
    with _STATS_LOCK:
        _STATS[key] += 1
    from apex_tpu.telemetry.registry import get_registry

    reg = get_registry()
    if reg.enabled:
        reg.counter(f"compile_cache/{key}").inc()


def install_cache_counters() -> None:
    """Register the (one, idempotent) monitoring listener feeding
    :func:`cache_stats`. jax offers no per-listener removal, so this
    registers once per process; the listener is a counter bump."""
    global _LISTENER_INSTALLED
    with _STATS_LOCK:
        if _LISTENER_INSTALLED:
            return
        _LISTENER_INSTALLED = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_cache_event)


def cache_stats() -> dict:
    """``{"hits", "misses"}`` persistent-cache lookups observed since
    :func:`install_cache_counters` ran (0/0 before — counting starts
    when the cache is enabled)."""
    with _STATS_LOCK:
        return dict(_STATS)


def default_cache_dir() -> str:
    """``<checkout>/.jit_cache`` — next to the ``apex_tpu`` package."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(here, ".jit_cache")


def enable_compile_cache(min_compile_secs: float = 0.5) -> str:
    """Turn the persistent cache on and return the directory in use.
    Call before the first compilation. Where ``JAX_COMPILATION_CACHE_DIR``
    is set the directory is jax's own reading of it and nothing is set
    here; otherwise it is :func:`default_cache_dir`."""
    import jax

    if not os.environ.get(ENV_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    # jax leaves an instruction's metadata (op_name: module names,
    # named scopes, kernel names) out of the cache key, so a program
    # that differs from a cached one only in its scopes would get the
    # old executable back, old op_names and all, and
    # telemetry.scopes.scope_table would read yesterday's scopes. With
    # the metadata in the key an executable's scopes are those of the
    # code that asked for it; the price is one cold compile after a
    # traced source line moves. The metadata also holds each
    # operation's source location, by default with up to ten frames of
    # its call stack, those of whoever called the jitted function among
    # them: keep the innermost frame only, or the same step lowered from
    # two call sites has two keys. (The frames are only shortened:
    # jax_include_full_tracebacks_in_locations=False would also cut
    # every op_name down to its primitive.)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    # jax decides "is the cache used?" once per process; if anything
    # compiled before the directory was known that decision is a
    # permanent False. reset_cache() drops it so the next compile
    # re-reads the config.
    from jax._src import compilation_cache as _jax_cc

    _jax_cc.reset_cache()
    install_cache_counters()
    return jax.config.jax_compilation_cache_dir
