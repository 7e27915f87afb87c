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

Enabling also installs the program's one ``jax.monitoring`` listener
(``telemetry.compile_watch.install_monitoring``; jax 0.9's compile-phase
spans and the persistent cache's events behind one callback), so the
record of compile phases starts before the entry point's first jit, and
:func:`cache_stats` (with the ``compile_cache/hits`` /
``compile_cache/misses`` telemetry counters) answers "is the cache
actually warm?" — a cache that silently misses every compile (key drift
across jax versions, an evicted dir) costs the full compile time while
looking enabled.
"""

import os

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"


def cache_stats() -> dict:
    """``{"hits", "misses"}`` persistent-cache lookups observed since
    :func:`enable_compile_cache` ran (0/0 before — counting starts when
    the cache is enabled): a view of ``compile_watch``'s record, whose
    ``cache_totals()`` also has the seconds a load took and saved."""
    from apex_tpu.telemetry import compile_watch

    totals = compile_watch.cache_totals()
    return {"hits": totals["hits"], "misses": totals["misses"]}


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
    from apex_tpu.telemetry import compile_watch

    compile_watch.install_monitoring()
    return jax.config.jax_compilation_cache_dir
