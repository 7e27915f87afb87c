"""Compile watch (ISSUE 5 tentpole): trace/compile accounting, recompile
signature diffs, assert_no_recompiles as a CI primitive, and the
recompile-stability regression pins on the 8-device DDP step and the
ZeRO optimizer step."""

import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _compile_cache, resilience
from apex_tpu.telemetry.compile_watch import (
    CompileWatcher,
    RecompileError,
    abstract_signature,
    assert_no_recompiles,
    diff_signatures,
)
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry


# -- signatures -------------------------------------------------------------

class TestSignatures:
    def test_array_descriptor_names_shape_and_dtype(self):
        sig = abstract_signature((jnp.ones((4, 8), jnp.bfloat16),))
        assert sig == {"args/0": "bfloat16[4, 8]"}

    def test_pytree_paths(self):
        sig = abstract_signature(({"layer0": {"w": jnp.ones((2, 2))}},),
                                 {"flag": True})
        assert "args/0/layer0/w" in sig
        assert sig["kwargs/flag"] == "py:bool=True"

    def test_python_scalars_carry_values(self):
        sig = abstract_signature((3, 2.5, "mode"))
        assert sig["args/0"] == "py:int=3"
        assert sig["args/1"] == "py:float=2.5"
        assert sig["args/2"] == "py:str='mode'"

    def test_diff_names_changed_argument(self):
        old = abstract_signature((jnp.ones((4, 8)),))
        new = abstract_signature((jnp.ones((4, 16)),))
        changes = diff_signatures(old, new)
        assert changes == [{"arg": "args/0", "old": "float32[4, 8]",
                            "new": "float32[4, 16]"}]

    def test_diff_reports_added_and_removed(self):
        old = abstract_signature((jnp.ones((2,)),))
        new = abstract_signature((jnp.ones((2,)), jnp.ones((3,))))
        changes = diff_signatures(old, new)
        assert changes == [{"arg": "args/1", "old": None,
                            "new": "float32[3]"}]

    def test_dtype_change_detected(self):
        changes = diff_signatures(
            abstract_signature((jnp.ones((2,), jnp.float32),)),
            abstract_signature((jnp.ones((2,), jnp.bfloat16),)))
        assert changes[0]["old"] == "float32[2]"
        assert changes[0]["new"] == "bfloat16[2]"


# -- the watcher ------------------------------------------------------------

class TestWatcher:
    def test_disabled_watch_returns_fn_unchanged(self):
        f = jax.jit(lambda x: x + 1)
        assert CompileWatcher(enabled=False).watch(f) is f

    def test_counts_first_compile_and_cache_hits(self):
        w = CompileWatcher(enabled=True)
        g = w.watch(jax.jit(lambda x: x * 2), "g")
        x = jnp.ones((8,))
        g(x)
        assert w.compile_count("g") == 1
        g(x)
        g(x)
        assert w.compile_count("g") == 1
        assert w.recompile_count() == 0

    def test_recompile_diffs_signature(self):
        w = CompileWatcher(enabled=True)
        g = w.watch(jax.jit(lambda x: x * 2), "g")
        g(jnp.ones((8,)))
        g(jnp.ones((16,)))
        assert w.compile_count("g") == 2
        assert w.recompile_count() == 1
        assert w.last_changes()["g"] == [
            {"arg": "args/0", "old": "float32[8]", "new": "float32[16]"}]

    def test_compile_event_lands_in_jsonl(self, tmp_path):
        reg = MetricsRegistry(jsonl_dir=str(tmp_path))
        with use_registry(reg):
            w = CompileWatcher(enabled=True)
            g = w.watch(jax.jit(lambda x: x * 3), "stepfn")
            g(jnp.ones((4, 4)))
            g(jnp.ones((4, 2)))
        events = []
        for path in glob.glob(str(tmp_path / "*.jsonl")):
            with open(path) as f:
                events.extend(json.loads(l) for l in f if l.strip())
        compiles = [e for e in events
                    if e["kind"] == "compile" and e["name"] == "stepfn"]
        assert len(compiles) == 2
        first, second = compiles
        assert first["changed"] is None and not first["recompile"]
        assert second["recompile"]
        assert second["changed"] == [
            {"arg": "args/0", "old": "float32[4, 4]",
             "new": "float32[4, 2]"}]
        # the process-wide counters rode along
        assert reg.counter_value("compile/count/stepfn") == 2
        assert reg.counter_value("compile/count") >= 2

    def test_watched_fn_delegates_aot_api(self):
        w = CompileWatcher(enabled=True)
        f = jax.jit(lambda x: x + 1)
        g = w.watch(f, "f")
        x = jnp.ones((4,))
        assert g.lower(x).as_text() == f.lower(x).as_text()

    def test_watching_keeps_hlo_byte_identical(self):
        # the PR 4 contract: observation stays out of the graph
        def f(x):
            return jnp.tanh(x @ x)

        plain = jax.jit(f)
        watched = CompileWatcher(enabled=True).watch(jax.jit(f), "f")
        x = jnp.ones((16, 16))
        watched(x)  # watching a real call must not perturb lowering
        assert watched.lower(x).as_text() == plain.lower(x).as_text()

    def test_context_manager_emits_summary(self, tmp_path):
        reg = MetricsRegistry(jsonl_dir=str(tmp_path))
        with use_registry(reg):
            with CompileWatcher() as w:
                g = w.watch(jax.jit(lambda x: x - 1), "h")
                g(jnp.ones((4,)))
        events = []
        for path in glob.glob(str(tmp_path / "*.jsonl")):
            with open(path) as f:
                events.extend(json.loads(l) for l in f if l.strip())
        summaries = [e for e in events if e["kind"] == "compile"
                     and e["name"] == "watch_summary"]
        assert summaries and summaries[-1]["backend_compiles"] >= 1
        assert summaries[-1]["watched"]["h"]["compiles"] == 1


# -- assert_no_recompiles ---------------------------------------------------

class TestAssertNoRecompiles:
    def test_clean_block_passes(self):
        f = jax.jit(lambda x: x * 2)
        x = jnp.ones((8,))
        f(x)  # warm
        with assert_no_recompiles():
            for _ in range(3):
                f(x)

    def test_compile_inside_block_raises(self):
        f = jax.jit(lambda x: x * 2 + 1)
        x8, x4 = jnp.ones((8,)), jnp.ones((4,))
        f(x8)
        with pytest.raises(RecompileError, match="compile"):
            with assert_no_recompiles():
                f(x4)

    def test_error_names_changed_arg_of_watched_fn(self):
        w = CompileWatcher(enabled=True)
        g = w.watch(jax.jit(lambda x: x / 2), "shaky")
        big, small = jnp.ones((32,)), jnp.ones((8,))
        g(big)
        with pytest.raises(RecompileError, match=r"shaky.*args/0"):
            with assert_no_recompiles(w):
                g(small)

    def test_allow_tolerates_known_compiles(self):
        f = jax.jit(lambda x: x + 2)
        x16, x12 = jnp.ones((16,)), jnp.ones((12,))
        f(x16)
        with assert_no_recompiles(allow=1):
            f(x12)


# -- recompile-stability regression pins (ISSUE 5 satellite) ----------------

@pytest.mark.multi_device
class TestRecompileStability:
    """Any future PR that introduces a per-step retrace (e.g. a Python
    scalar leaking into the traced signature) must fail HERE, loudly."""

    def _ddp_step(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from apex_tpu.parallel import DistributedDataParallel

        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.randn(32, 32), jnp.float32),
                  "b": jnp.zeros((32,), jnp.float32)}
        x = jnp.asarray(rng.randn(16, 32), jnp.float32)
        y = jnp.asarray(rng.randn(16, 32), jnp.float32)
        ddp = DistributedDataParallel(axis_name="dp", compress="int8")
        residual = ddp.init_residual(params)
        gstate = resilience.init_guard_state()
        params, residual, gstate = jax.device_put(
            (params, residual, gstate), NamedSharding(mesh, P()))

        def loss_fn(p, xb, yb):
            return jnp.mean((jnp.tanh(xb @ p["w"] + p["b"]) - yb) ** 2)

        def step_fn(p, res, gst, xb, yb):
            loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
            flag = resilience.nonfinite_flag(grads)
            synced, new_res = ddp.sync(grads, res)

            def commit(g, st):
                prev_p, _ = st
                new_p = jax.tree_util.tree_map(
                    lambda w_, g_: w_ - 0.05 * g_, prev_p, g)
                return (new_p, new_res)

            (p, res), gst = resilience.guarded_update(
                synced, commit, (p, res), gst, axis_name="dp", flag=flag)
            return p, res, gst, loss

        sharded = jax.shard_map(step_fn, mesh=mesh,
                                in_specs=(P(), P(), P(), P("dp"),
                                          P("dp")),
                                out_specs=(P(), P(), P(), P()),
                                check_vma=False)

        @jax.jit
        def train_step(p, res, gst):
            return sharded(p, res, gst, x, y)

        return train_step, (params, residual, gstate)

    def test_ddp_train_step_is_shape_stable(self, dp_mesh):
        mesh = dp_mesh()
        train_step, state = self._ddp_step(mesh)
        out = train_step(*state)      # compile
        out = train_step(*out[:3])    # settle output shardings
        with assert_no_recompiles():
            for _ in range(5):
                out = train_step(*out[:3])
        assert bool(jnp.isfinite(out[3]))
        assert int(train_step._cache_size()) == 1

    def test_zero_optimizer_step_is_shape_stable(self, dp_mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        mesh = dp_mesh()
        rng = np.random.RandomState(1)
        params = {"w": jnp.asarray(rng.randn(16, 16), jnp.float32),
                  "b": jnp.zeros((16,), jnp.float32)}
        params = jax.device_put(params, NamedSharding(mesh, P()))
        opt = DistributedFusedAdam(lr=1e-3)
        x = jnp.asarray(rng.randn(8, 16), jnp.float32)
        y = jnp.asarray(rng.randn(8, 16), jnp.float32)

        def loss_fn(p, xb, yb):
            return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

        def step_fn(p, state, xb, yb):
            loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
            new_p, new_state = opt.step(grads, state, p)
            return new_p, new_state, loss

        sharded = jax.shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P(), P("dp"), P("dp")),
            out_specs=(P(), P(), P()), check_vma=False)

        @jax.jit
        def opt_step(p, state):
            return sharded(p, state, x, y)

        @jax.jit
        def opt_init(p):
            return jax.shard_map(opt.init, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(), check_vma=False)(p)

        state = opt_init(params)
        out = opt_step(params, state)   # compile
        out = opt_step(*out[:2])        # settle output shardings
        with assert_no_recompiles():
            for _ in range(5):
                out = opt_step(*out[:2])
        assert bool(jnp.isfinite(out[2]))
        assert int(opt_step._cache_size()) == 1


@pytest.mark.multi_device
class TestE2ECompileWatch:
    """ISSUE 5 acceptance: a jitted 8-device DDP step fed a changed
    input shape triggers exactly one recompile whose `compile` event
    names the changed argument (path, old -> new shape); the same
    harness passes assert_no_recompiles() over >= 5 steady-state
    steps."""

    def test_shape_change_names_the_argument(self, dp_mesh, tmp_path):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from apex_tpu.parallel import DistributedDataParallel

        mesh = dp_mesh()
        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.randn(16, 16), jnp.float32),
                  "b": jnp.zeros((16,), jnp.float32)}
        ddp = DistributedDataParallel(axis_name="dp", compress="int8")
        residual = ddp.init_residual(params)
        gstate = resilience.init_guard_state()
        params, residual, gstate = jax.device_put(
            (params, residual, gstate), NamedSharding(mesh, P()))

        def loss_fn(p, xb, yb):
            return jnp.mean((jnp.tanh(xb @ p["w"] + p["b"]) - yb) ** 2)

        def step_fn(p, res, gst, xb, yb):
            loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
            flag = resilience.nonfinite_flag(grads)
            synced, new_res = ddp.sync(grads, res)

            def commit(g, st):
                prev_p, _ = st
                new_p = jax.tree_util.tree_map(
                    lambda w_, g_: w_ - 0.05 * g_, prev_p, g)
                return (new_p, new_res)

            (p, res), gst = resilience.guarded_update(
                synced, commit, (p, res), gst, axis_name="dp",
                flag=flag)
            return p, res, gst, loss

        train_step = jax.jit(jax.shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P(), P(), P("dp"), P("dp")),
            out_specs=(P(), P(), P(), P()), check_vma=False))

        x = jnp.asarray(rng.randn(32, 16), jnp.float32)
        y = jnp.asarray(rng.randn(32, 16), jnp.float32)
        reg = MetricsRegistry(jsonl_dir=str(tmp_path))
        with use_registry(reg):
            w = CompileWatcher(enabled=True)
            step = w.watch(train_step, "ddp_step")
            out = step(params, residual, gstate, x, y)  # the one compile
            assert w.compile_count("ddp_step") == 1
            # >= 5 steady-state steps: no retrace, loudly enforced
            with assert_no_recompiles(w):
                for _ in range(5):
                    out = step(*out[:3], x, y)
            assert int(train_step._cache_size()) == 1
            # a changed batch shape: exactly ONE recompile
            x2 = jnp.asarray(rng.randn(16, 16), jnp.float32)
            y2 = jnp.asarray(rng.randn(16, 16), jnp.float32)
            out = step(*out[:3], x2, y2)
            out = step(*out[:3], x2, y2)  # cached again — still one
        assert w.compile_count("ddp_step") == 2
        assert w.recompile_count() == 1
        changed = {c["arg"]: c for c in w.last_changes()["ddp_step"]}
        assert changed["args/3"]["old"] == "float32[32, 16]"
        assert changed["args/3"]["new"] == "float32[16, 16]"
        # the emitted compile event carries the same attribution
        events = []
        for path in glob.glob(str(tmp_path / "*.jsonl")):
            with open(path) as f:
                events.extend(json.loads(l) for l in f if l.strip())
        recompiles = [e for e in events if e["kind"] == "compile"
                      and e["name"] == "ddp_step" and e.get("changed")]
        assert len(recompiles) == 1
        args = {c["arg"] for c in recompiles[0]["changed"]}
        assert {"args/3", "args/4"} == args
        assert bool(jnp.isfinite(out[3]))


# -- persistent-cache hit/miss counters (_compile_cache satellite) ----------

class TestCompileCacheCounters:
    @pytest.fixture
    def restore_cache_config(self):
        before_dir = jax.config.jax_compilation_cache_dir
        before_min = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_compilation_cache_dir", before_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before_min)
        # drop the cache object pointing at the (temporary) test dir so
        # the rest of the suite goes back to the session's cache
        from jax._src import compilation_cache as jax_cc

        jax_cc.reset_cache()

    @staticmethod
    def _enable_at(monkeypatch, path):
        """The outside-placement rule: with JAX_COMPILATION_CACHE_DIR
        set the code sets no directory, so the test (standing in for
        jax's import-time read of the variable) puts it in the config."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))
        jax.config.update("jax_compilation_cache_dir", str(path))
        assert _compile_cache.enable_compile_cache(
            min_compile_secs=0.0) == str(path)

    def test_hits_and_misses_counted(self, monkeypatch, tmp_path,
                                     restore_cache_config):
        self._enable_at(monkeypatch, tmp_path / "cache")
        before = _compile_cache.cache_stats()
        x = jnp.ones((64,))

        # two distinct pjit instances of the same program, from the same
        # source line (the line is part of the key): the first populates
        # the persistent cache, the second must hit it
        def program():
            return jax.jit(lambda v: v * 7 + 3)

        program()(x)
        mid = _compile_cache.cache_stats()
        assert mid["misses"] > before["misses"]
        program()(x)
        after = _compile_cache.cache_stats()
        assert after["hits"] > mid["hits"]

    def test_a_scope_is_part_of_the_key(self, monkeypatch, tmp_path,
                                        restore_cache_config):
        """A program that differs from a cached one only in a named
        scope is compiled again, so its executable's ``op_name``s are its
        own and not those of the code that filled the cache."""
        self._enable_at(monkeypatch, tmp_path / "cache3")

        def program(scope):
            def f(v):
                with jax.named_scope(scope):
                    return v * 5 + 2
            return jax.jit(f)

        x = jnp.ones((32,))
        program("yesterday").lower(x).compile()
        mid = _compile_cache.cache_stats()
        text = program("today").lower(x).compile().as_text()
        after = _compile_cache.cache_stats()
        assert after["misses"] > mid["misses"]
        assert "today" in text and "yesterday" not in text
        # ... and who calls the program is not: one frame a location
        again = (lambda: program("today").lower(x).compile())()
        assert _compile_cache.cache_stats()["hits"] > after["hits"]
        assert "today" in again.as_text()

    def test_registry_counters_ride_along(self, monkeypatch, tmp_path,
                                          restore_cache_config):
        self._enable_at(monkeypatch, tmp_path / "cache2")
        with use_registry(MetricsRegistry(enabled=True)) as reg:
            x = jnp.ones((48,))
            for _ in range(2):      # one source line: one cache key
                jax.jit(lambda v: v * 9 - 1)(x)
            assert reg.counter_value("compile_cache/misses") >= 1
            assert reg.counter_value("compile_cache/hits") >= 1
