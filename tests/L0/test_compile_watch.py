"""Compile watch (ISSUE 5 tentpole): trace/compile accounting, recompile
signature diffs, assert_no_recompiles as a CI primitive, and the
recompile-stability regression pins on the 8-device DDP step and the
ZeRO optimizer step. Since ISSUE 37 also the always-on record of compile
phases behind them (``TestPhaseRecord``)."""

import glob
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _compile_cache, resilience
from apex_tpu.telemetry import compile_watch
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

@pytest.fixture
def restore_cache_config():
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


class TestCompileCacheCounters:
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


# -- the record of compile phases (ISSUE 37 tentpole) -----------------------

def _nested():
    """Fresh jits each call (same source lines: one persistent-cache
    key), an inner one traced inside its caller."""
    @jax.jit
    def phase_inner(x):
        return jnp.tanh(x) * 3

    @jax.jit
    def phase_outer(x):
        return phase_inner(x) + phase_inner(x * 2)

    return phase_outer


def _new_records(before):
    return compile_watch.phase_records()[before:]


# the process's own record, with the package's import in it
_PROCESS_LOG = compile_watch._LOG


class TestPhaseRecord:
    @pytest.fixture(autouse=True)
    def record_of_its_own(self, monkeypatch):
        """The listener writes to whatever ``_LOG`` names: a worker that
        ran other files first may have filled the process's. And every
        phase kept, however short (the test of folding sets its own)."""
        compile_watch.install_monitoring()
        monkeypatch.setattr(compile_watch, "FOLD_BELOW", 0.0)
        log = compile_watch._PhaseLog()
        log.installed = True
        log.wall_offset = _PROCESS_LOG.wall_offset
        monkeypatch.setattr(compile_watch, "_LOG", log)

    def test_jax_fires_the_events_the_record_is_fed_by(self):
        """The probe of jax 0.9's names: each phase's time span carries
        ``fun_name`` and lies on ``time.time()``."""
        import jax.monitoring

        seen = []

        def listener(event, start, end, **meta):
            seen.append((event, start, end, meta))

        jax.monitoring.register_event_time_span_listener(listener)
        try:
            wall0 = time.time()
            _nested()(jnp.ones((3, 5)))
            wall1 = time.time()
        finally:
            jax.monitoring.unregister_event_time_span_listener(listener)
        assert {e for e, *_ in seen} == set(compile_watch._PHASE_OF_EVENT)
        for _, start, end, meta in seen:
            assert wall0 <= start <= end <= wall1
            assert "phase_" in meta["fun_name"] or meta["fun_name"]

    def test_records_carry_phase_name_and_perf_counter_times(self):
        before = len(compile_watch.phase_records())
        t0 = time.perf_counter()
        _nested()(jnp.ones((4, 6)))
        t1 = time.perf_counter()
        new = _new_records(before)
        assert {r.phase for r in new} == {"trace", "lower", "compile"}
        by = {(r.phase, r.fun_name) for r in new}
        assert ("trace", "phase_outer") in by
        assert ("trace", "phase_inner") in by
        assert ("lower", "jit(phase_outer)") in by
        assert ("compile", "jit(phase_outer)") in by
        # one offset from time.time(): to within a wall-clock slew
        for r in new:
            assert t0 - 0.05 <= r.start <= r.end <= t1 + 0.05
            assert r.thread == threading.get_ident()
        compiles = [r for r in new if r.phase == "compile"]
        assert all(r.cache_hit in (True, False) for r in compiles)
        assert all(r.cache_hit is None for r in new if r.phase != "compile")

    def test_a_nested_jit_is_a_child_inside_its_parent(self):
        before = len(compile_watch.phase_records())
        _nested()(jnp.ones((5, 7)))
        new = _new_records(before)
        (outer,) = [r for r in new if r.fun_name == "phase_outer"]
        inner = [r for r in new if r.fun_name == "phase_inner"]
        assert len(inner) == 2
        for r in inner:
            assert outer.start <= r.start <= r.end <= outer.end
            # a child ends first, so it is recorded first
            assert new.index(r) < new.index(outer)
        traces = [r for r in new if r.phase == "trace"
                  and outer.start <= r.start and r.end <= outer.end]
        total = sum(r.end - r.start for r in traces)
        union = outer.end - outer.start
        assert union < total
        # self time: the parent's duration less what its children cover
        own = compile_watch._self_seconds(new)
        direct = [r for r in traces if r is not outer
                  and not any(p is not r and p is not outer
                              and p.start <= r.start and r.end <= p.end
                              for p in traces)]
        assert own[new.index(outer)] == pytest.approx(
            union - sum(r.end - r.start for r in direct))
        assert sum(own[new.index(r)] for r in traces) == pytest.approx(union)

    def test_a_short_phase_inside_its_own_kind_is_folded(self,
                                                         monkeypatch):
        """The jnp helpers inside a trace, by the thousand in a real
        step: counted in the totals, not kept, and the union of the kept
        records is the same without them."""
        _nested()(jnp.ones((5, 21)))
        everything = compile_watch.phase_records()
        assert compile_watch.record_stats()["folded"] == 0

        def union(records):
            spans, total, edge = sorted(
                (r.start, r.end) for r in records
                if r.phase == "trace"), 0.0, float("-inf")
            for start, end in spans:
                total += max(end, edge) - max(start, edge)
                edge = max(end, edge)
            return total

        monkeypatch.setattr(compile_watch, "FOLD_BELOW", 3600.0)
        before = len(compile_watch.phase_records())
        traces = compile_watch._LOG.totals["trace"][0]
        _nested()(jnp.ones((5, 22)))
        new = _new_records(before)
        # every trace inside phase_outer's is folded into it, whatever it
        # took; a trace at the top (jnp.ones' own) is kept
        assert [r.fun_name for r in new if r.phase == "trace"
                and "phase_" in r.fun_name] == ["phase_outer"]
        folded = compile_watch.record_stats()["folded"]
        assert folded >= 4      # phase_inner twice, with its helpers
        assert compile_watch._LOG.totals["trace"][0] == traces + folded \
            + sum(r.phase == "trace" for r in new)
        kept_outer, = [r for r in new if r.fun_name == "phase_outer"]
        inside = [r for r in everything if r.phase == "trace"
                  and r.fun_name in ("phase_outer", "phase_inner", "tanh")]
        (whole,) = [r for r in inside if r.fun_name == "phase_outer"]
        assert union(inside) == pytest.approx(whole.end - whole.start)
        assert union([kept_outer]) == kept_outer.end - kept_outer.start

    def test_steady_state_adds_no_record(self):
        f = _nested()
        x = jnp.ones((6, 8))
        f(x)
        before = len(compile_watch.phase_records())
        stats = compile_watch.record_stats()
        for _ in range(5):
            f(x)
        assert len(compile_watch.phase_records()) == before
        # nothing folded, nothing dropped, the listener never called
        assert compile_watch.record_stats() == stats

    def test_the_bound_holds_and_the_drop_is_counted(self, monkeypatch):
        _nested()(jnp.ones((2, 9)))
        kept = len(compile_watch.phase_records())
        monkeypatch.setattr(compile_watch, "MAX_RECORDS", kept)
        dropped = compile_watch.record_stats()["dropped"]
        count, seconds = compile_watch.backend_compiles()
        with use_registry(MetricsRegistry(enabled=True)) as reg:
            _nested()(jnp.ones((2, 10)))
            assert reg.counter_value("compile/records_dropped") >= 3
            # past the bound the totals and their counters still grow
            assert reg.counter_value("compile/count") >= 1
        assert len(compile_watch.phase_records()) == kept
        assert compile_watch.record_stats()["dropped"] >= dropped + 3
        after = compile_watch.backend_compiles()
        assert after[0] > count and after[1] > seconds

    def test_views_read_the_one_record(self):
        """``backend_compiles()`` and ``cache_stats()`` as before: a count
        with its seconds, hits and misses; both from the record."""
        before = len(compile_watch.phase_records())
        c0, s0 = compile_watch.backend_compiles()
        stats0 = _compile_cache.cache_stats()
        _nested()(jnp.ones((7, 11)))
        c1, s1 = compile_watch.backend_compiles()
        stats1 = _compile_cache.cache_stats()
        compiles = [r for r in _new_records(before)
                    if r.phase == "compile"]
        assert c1 - c0 == len(compiles) >= 1
        assert s1 - s0 == pytest.approx(
            sum(r.end - r.start for r in compiles))
        assert set(stats1) == {"hits", "misses"}
        assert (stats1["hits"] - stats0["hits"]
                == sum(r.cache_hit is True for r in compiles))
        assert (stats1["misses"] - stats0["misses"]
                == sum(r.cache_hit is False for r in compiles))
        totals = compile_watch.cache_totals()
        assert set(totals) == {"hits", "misses", "retrieval_seconds",
                               "saved_seconds"}
        assert totals["hits"] == stats1["hits"]

    def test_a_cache_hit_marks_its_compile(self, monkeypatch, tmp_path,
                                           restore_cache_config):
        """The cache's event comes before the end of the compile it is
        inside: the same program twice, a miss and then a hit, and the
        load's seconds in the totals."""
        TestCompileCacheCounters._enable_at(monkeypatch,
                                            tmp_path / "phase_cache")
        before = len(compile_watch.phase_records())
        totals0 = compile_watch.cache_totals()
        x = jnp.ones((9, 13))
        _nested()(x)
        _nested()(x)
        first, second = [r for r in _new_records(before)
                         if r.fun_name == "jit(phase_outer)"
                         and r.phase == "compile"]
        assert first.cache_hit is False and second.cache_hit is True
        assert compile_watch.cache_totals()["retrieval_seconds"] > \
            totals0["retrieval_seconds"]

    def test_registry_gets_spans_and_counters(self, tmp_path):
        reg = MetricsRegistry(jsonl_dir=str(tmp_path))
        with use_registry(reg):
            _nested()(jnp.ones((8, 12)))
            assert reg.counter_value("compile/traces") >= 3
            assert reg.counter_value("compile/lowerings") >= 1
            assert reg.counter_value("compile/count") >= 1
            assert reg.counter_value("compile/seconds") > 0
        events = []
        for path in glob.glob(str(tmp_path / "*.jsonl")):
            with open(path) as f:
                events.extend(json.loads(l) for l in f if l.strip())
        spans = [e for e in events if e["kind"] == "span"]
        names = {e["name"] for e in spans}
        assert {"compile/trace", "compile/lower", "compile/backend"} <= names
        outer = [e for e in spans if e["name"] == "compile/trace"
                 and e["fun_name"] == "phase_outer"]
        assert len(outer) == 1 and outer[0]["duration_s"] > 0
        backend = [e for e in spans if e["name"] == "compile/backend"]
        assert all(e["cache_hit"] in (True, False) for e in backend)

    def test_the_package_import_is_a_record(self):
        (rec,) = [r for r in _PROCESS_LOG.records if r.phase == "import"]
        assert rec is _PROCESS_LOG.records[0]
        assert rec.fun_name == "apex_tpu" and rec.start < rec.end
        assert _PROCESS_LOG.jax_preloaded in (True, False)
        start = compile_watch.process_start_perf()
        assert start is not None and start <= rec.start
        assert 0 < time.perf_counter() - start < 24 * 3600
        # the same reading twice: the clock ticks of /proc are coarse,
        # the answer is not
        assert compile_watch.process_start_perf() == pytest.approx(
            start, abs=1e-3)

    def test_phase_table_rows(self):
        before = len(compile_watch.phase_records())
        mark = time.perf_counter()
        _nested()(jnp.ones((3, 14)))
        rows = compile_watch.phase_table()
        assert [r["self_s"] for r in rows] == sorted(
            (r["self_s"] for r in rows), reverse=True)
        by = {(r["fun_name"], r["phase"]): r for r in rows}
        inner = by[("phase_inner", "trace")]
        assert inner["calls"] >= 2 and inner["self_s"] <= inner["total_s"]
        outer = by[("jit(phase_outer)", "compile")]
        assert outer["cache_hits"] + outer["cache_misses"] >= 1
        assert set(rows[0]) == {"fun_name", "phase", "calls", "self_s",
                                "total_s", "cache_hits", "cache_misses"}
        # until: what had ended by then, and nothing of this call
        early = compile_watch.phase_table(until=mark)
        assert sum(r["calls"] for r in early) == len(
            compile_watch.phase_records(until=mark)) <= before

    def test_watching_the_phases_leaves_the_lowered_text_alone(self):
        """The listener is host-side: a step lowered under it, with the
        registry on and off, has the text of one lowered before."""
        def f(x):
            return jnp.tanh(x @ x) + 1

        x = jnp.ones((16, 16))
        plain = jax.jit(f).lower(x).as_text()
        with use_registry(MetricsRegistry(enabled=True)):
            watched = jax.jit(f)
            watched(x)
            assert watched.lower(x).as_text() == plain
        assert jax.jit(f).lower(x).as_text() == plain
