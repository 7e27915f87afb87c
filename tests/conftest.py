"""Test configuration: force an 8-device virtual CPU platform.

Mirrors the reference test strategy (SURVEY.md §4): the reference spawns
one process per GPU via MultiProcessTestCase; here multi-device tests use a
virtual 8-device CPU mesh (SPMD shard_map) — chips stand in for processes.
Must set XLA flags before jax initializes.

This conftest is THE one place that mints the virtual device mesh: tests
take the ``dp_mesh`` fixture (a factory: ``dp_mesh()`` / ``dp_mesh(4)``)
and mark multi-device classes ``@pytest.mark.multi_device`` (auto-skip
when the mesh could not be built — e.g. jax initialized before this file
ran under an exotic launcher) instead of hand-rolling XLA_FLAGS or their
own module-level mesh helpers.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

# XLA:CPU compile time dominates this suite (hundreds of tiny jitted
# programs; runtime is microseconds each), and the tier-1 wall-clock
# budget is finite on the 1-core driver host: skip the backend
# optimization passes — measured ~20% off suite wall-clock with
# identical results. APEX_TPU_TEST_FULL_OPT=1 restores full
# optimization (e.g. when hunting a suspected miscompile).
if os.environ.get("APEX_TPU_TEST_FULL_OPT") != "1":
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"

# Tests always run on the virtual CPU mesh; chip_smoke.py is the run on
# the chip.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# The persistent compilation cache, placed by the one rule every entry
# point shares (apex_tpu/_compile_cache.py).
from apex_tpu._compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache(min_compile_secs=0.0)
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(1234)


@pytest.fixture
def mesh8():
    """2x2x2 (pp, dp, tp) mesh over the 8 virtual devices."""
    from jax.sharding import Mesh

    devices = np.asarray(jax.devices()[:8]).reshape(2, 2, 2)
    return Mesh(devices, ("pp", "dp", "tp"))


@pytest.fixture
def dp_mesh():
    """Factory for a 1-axis data-parallel mesh over the virtual devices:
    ``dp_mesh()`` -> 8-way 'dp' mesh, ``dp_mesh(4)`` -> 4-way. Skips the
    test when the host exposes fewer devices than asked (the
    xla_force_host_platform_device_count route is ignored once a real
    accelerator plugin registered first)."""
    from jax.sharding import Mesh

    def make(n=8, axis_name="dp"):
        devices = jax.devices()
        if len(devices) < n:
            pytest.skip(f"needs {n} devices, have {len(devices)}")
        return Mesh(np.asarray(devices[:n]), (axis_name,))

    return make


_LAST_TEST_MODULE = [None]


def pytest_runtest_setup(item):
    """Drop jax's live jit/trace caches at FILE boundaries.

    Accumulated cache state makes later tests pay a superlinear
    dispatch/tracing tax: by mid-suite, identical tests run 3x their
    fresh-process time (a 20-test probe slice: 124 s accumulated vs
    68 s with per-file clearing; the full tier-1 run regressed past
    the 870 s budget on the 1-core driver host from this alone — and
    it is NOT the garbage collector; gc.freeze() changes nothing).
    Cross-file executable reuse is essentially nil (each file builds
    its own tiny models), so clearing at module edges costs nothing
    while keeping within-file no-recompile assertions intact.
    APEX_TPU_TEST_KEEP_CACHES=1 restores the old behavior (e.g. when
    profiling cache reuse itself)."""
    if os.environ.get("APEX_TPU_TEST_KEEP_CACHES") == "1":
        return
    mod = getattr(item, "module", None)
    name = getattr(mod, "__name__", None)
    if _LAST_TEST_MODULE[0] is not None \
            and _LAST_TEST_MODULE[0] != name:
        jax.clear_caches()
    _LAST_TEST_MODULE[0] = name


def pytest_collection_modifyitems(config, items):
    """``@pytest.mark.multi_device``: skip when the virtual 8-device CPU
    mesh is unavailable rather than failing on mesh construction."""
    if len(jax.devices()) >= 8:
        return
    skip = pytest.mark.skip(reason="virtual 8-device CPU mesh unavailable")
    for item in items:
        if "multi_device" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def assert_clean_hlo():
    """The static-lint CI primitive (apex_tpu.analysis,
    docs/analysis.md) as a fixture, next to ``assert_no_recompiles``:
    ``assert_clean_hlo(step, *args, rules=...)`` raises HloLintError
    naming every hot-path-invariant violation in the lowered step."""
    from apex_tpu.analysis import assert_clean_hlo as _ach

    return _ach


@pytest.fixture(autouse=True)
def _reset_parallel_state():
    yield
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
