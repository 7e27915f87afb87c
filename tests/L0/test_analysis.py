"""apex_tpu.analysis — the static HLO/jaxpr lint pass (ISSUE 9).

Three layers of evidence:

- **Seeded violations**: each rule catches a deliberately bad program
  and names the offending op/argument path in the structured finding
  (the acceptance's per-rule requirement).
- **Clean hot paths**: the real DDP fp32/int8, ZeRO, guarded, and
  serving decode steps (``analysis.targets`` — built through the same
  machinery the benches use) lint clean with every rule running.
- **Integration**: the CompileWatcher lints on compile under
  ``APEX_TPU_HLO_LINT=1`` and emits ``lint`` JSONL events; bench
  staging carries ``lint_violations``; the donation-repro ladder is
  retired into the double-donation regression here.

Everything is trace-only except the watcher integration (one tiny
compile) and the serving target (AOT ladder of 2 executables).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import analysis
from apex_tpu.analysis import (
    Finding,
    HloLintError,
    LintConfig,
    LintReport,
    RULES,
    assert_clean_hlo,
    lint_fn,
    lint_lowered,
)
from apex_tpu.analysis.targets import TARGETS


def _rules_fired(report):
    return sorted({f.rule for f in report.findings})


# ---------------------------------------------------------------------------
# seeded violations — every rule must catch its bad program and name
# the offending op/argument path
# ---------------------------------------------------------------------------

class TestSeededViolations:
    def test_no_host_callback(self):
        def poisoned(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y * 2

        report = lint_fn(poisoned, jnp.ones((4,)))
        assert _rules_fired(report) == ["no-host-callback"]
        f = report.findings[0]
        assert "custom_call @" in f.where
        assert "callback" in f.message

    def test_no_host_callback_substring_cannot_false_positive(self):
        """The precision the substring grep lacked: 'callback' inside
        a plain op constant/name must not fire the rule."""
        from apex_tpu.analysis.lint import LintContext, run_rules

        text = ('module @jit_f {\n'
                '  func.func public @main(%arg0: tensor<4xf32>) -> '
                '(tensor<4xf32>) {\n'
                '    // callback mentioned in a comment only\n'
                '    return %arg0 : tensor<4xf32>\n  }\n}\n')
        report = run_rules(LintContext(hlo_text=text),
                           rules="no-host-callback")
        assert report.ok

    @staticmethod
    def _custom_call_module(target):
        return ('module @jit_f {\n'
                '  func.func public @main(%arg0: tensor<8x128xf32>) -> '
                '(tensor<8x128xf32>) {\n'
                f'    %0 = stablehlo.custom_call @{target}(%arg0) : '
                '(tensor<8x128xf32>) -> tensor<8x128xf32>\n'
                '    return %0 : tensor<8x128xf32>\n  }\n}\n')

    def test_pallas_targets_allowlisted(self):
        """ISSUE 14 satellite: a compiled pallas_call lowers to a
        custom_call (tpu_custom_call / mosaic_cpu / ...) that runs
        on-device — kernel-backed hot paths must lint clean."""
        from apex_tpu.analysis.lint import LintContext, run_rules
        from apex_tpu.analysis.rules import PALLAS_CUSTOM_CALL_TARGETS

        for target in sorted(PALLAS_CUSTOM_CALL_TARGETS):
            report = run_rules(
                LintContext(hlo_text=self._custom_call_module(target)),
                rules="no-host-callback")
            assert report.ok, f"{target} false-positived: " \
                f"{[str(f) for f in report.findings]}"

    def test_pallas_allowlist_env_extendable(self, monkeypatch):
        """A marker-matching target (hypothetical new Pallas runtime
        name containing 'callback') trips by default and is waivable
        via APEX_TPU_HLO_LINT_PALLAS_TARGETS without a code change."""
        from apex_tpu.analysis.lint import LintContext, run_rules

        text = self._custom_call_module("my_pallas_kernel_callback")
        report = run_rules(LintContext(hlo_text=text),
                           rules="no-host-callback")
        assert not report.ok
        monkeypatch.setenv("APEX_TPU_HLO_LINT_PALLAS_TARGETS",
                           "other_target, my_pallas_kernel_callback")
        report = run_rules(LintContext(hlo_text=text),
                           rules="no-host-callback")
        assert report.ok

    def test_real_callback_trips_despite_allowlist(self, monkeypatch):
        """The seeded proof the allowlist cannot hide a REAL host
        callback: a jax.pure_callback program still trips the rule
        even with extra pallas targets allowlisted."""
        monkeypatch.setenv("APEX_TPU_HLO_LINT_PALLAS_TARGETS",
                           "tpu_custom_call,mosaic_cpu")

        def poisoned(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y * 2

        report = lint_fn(poisoned, jnp.ones((4,)))
        assert _rules_fired(report) == ["no-host-callback"]
        assert "custom_call @" in report.findings[0].where

    def test_no_f64(self):
        from jax import enable_x64

        with enable_x64():
            report = lint_fn(lambda x: x.astype(jnp.float64) * 2.0,
                             jnp.ones((4,), jnp.float32))
        assert "no-f64" in _rules_fired(report)
        assert "line" in report.findings[0].where

    def test_unexpected_upcast(self):
        def upcast_matmul(a, b):
            return a.astype(jnp.float32) @ b.astype(jnp.float32).T

        report = lint_fn(upcast_matmul, jnp.ones((8, 8), jnp.bfloat16),
                         jnp.ones((8, 8), jnp.bfloat16))
        assert _rules_fired(report) == ["unexpected-upcast"]
        assert "dot_general" in report.findings[0].message

    def test_bf16_matmul_and_f32_accumulate_are_clean(self):
        report = lint_fn(lambda a, b: a @ b,
                         jnp.ones((8, 8), jnp.bfloat16),
                         jnp.ones((8, 8), jnp.bfloat16))
        assert report.ok
        # accumulating in f32 via preferred_element_type is the GOOD
        # spelling and must not fire
        report = lint_fn(
            lambda a, b: jax.lax.dot(a, b,
                                     preferred_element_type=jnp.float32),
            jnp.ones((8, 8), jnp.bfloat16),
            jnp.ones((8, 8), jnp.bfloat16))
        assert report.ok

    def test_donation_coverage(self):
        def step(w, x):
            return w - 0.01 * (x.T @ (x @ w)), jnp.sum(w)

        cfg = LintConfig(donate_min_bytes=1024)
        w = jnp.ones((64, 64))
        report = lint_fn(step, w, jnp.ones((4, 64)), config=cfg)
        assert _rules_fired(report) == ["donation-coverage"]
        assert report.findings[0].where == "args/0"
        # donated -> clean
        report = lint_fn(jax.jit(step, donate_argnums=(0,)), w,
                         jnp.ones((4, 64)), config=cfg)
        assert report.ok
        # below the size threshold -> clean (not carry-state worth 2x)
        report = lint_fn(step, jnp.ones((4, 4)), jnp.ones((2, 4)),
                         config=cfg)
        assert report.ok

    def test_double_donation(self):
        shared = jnp.ones((8,))
        params = {"scale": shared}
        masters = {"master": shared.astype(jnp.float32)}  # no-op alias

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(p, m):
            return (jax.tree_util.tree_map(lambda t: t * 2, p),
                    jax.tree_util.tree_map(lambda t: t * 3, m))

        report = lint_fn(step, params, masters)
        assert _rules_fired(report) == ["double-donation"]
        f = report.findings[0]
        assert "args/0/scale" in f.extra["paths"]
        assert "args/1/master" in f.extra["paths"]

    def test_trace_constant_capture(self):
        baked = jnp.arange(4096, dtype=jnp.float32)
        report = lint_fn(lambda x: x + baked, jnp.ones((4096,)),
                         config=LintConfig(const_min_bytes=1024))
        assert _rules_fired(report) == ["trace-constant-capture"]
        assert "const[" in report.findings[0].where
        # passing the array as an argument is the fix
        report = lint_fn(lambda x, c: x + c, jnp.ones((4096,)), baked,
                         config=LintConfig(const_min_bytes=1024))
        assert report.ok

    @pytest.mark.multi_device
    def test_collective_consistency_cond_divergence(self, dp_mesh):
        mesh = dp_mesh(8)
        allreduce = jax.shard_map(
            lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
            in_specs=P("dp"), out_specs=P(), check_vma=False)

        def diverging(x, pred):
            return jax.lax.cond(
                pred,
                lambda v: jnp.broadcast_to(allreduce(v), v.shape),
                lambda v: v, x)

        report = lint_fn(diverging, jnp.ones((8, 4)), jnp.asarray(True))
        assert "collective-consistency" in _rules_fired(report)
        assert "cond branches" in report.findings[0].message

    @pytest.mark.multi_device
    def test_collective_consistency_while_loop(self, dp_mesh):
        mesh = dp_mesh(8)

        def body(x):
            def cond(c):
                return c[1].sum() < 10.0

            def step(c):
                i, v = c
                return i + 1, jax.lax.psum(v, "dp") * 0.5

            return jax.lax.while_loop(
                cond, step, (jnp.zeros((), jnp.int32), x))[1]

        sm = jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp"), check_vma=False)
        report = lint_fn(sm, jnp.ones((8, 4)))
        assert "collective-consistency" in _rules_fired(report)
        assert "while" in report.findings[0].message

    @pytest.mark.multi_device
    def test_overlap_serialization_chained_collectives(self, dp_mesh):
        """Bucket 2's psum artificially data-dependent on bucket 1's
        result — the serialized chain the overlapped step must never
        emit (ISSUE 10 satellite)."""
        mesh = dp_mesh(8)

        def chained(a, b):
            s1 = jax.lax.psum(a, "dp")
            s2 = jax.lax.psum(b + 0.0 * s1[0], "dp")
            return s1, s2

        sm = jax.shard_map(chained, mesh=mesh, in_specs=(P(), P()),
                           out_specs=(P(), P()), check_vma=False)
        big = jnp.ones((1 << 18,), jnp.float32)  # 1 MiB payloads
        report = lint_fn(jax.jit(sm), big, big,
                         rules="overlap-serialization")
        assert _rules_fired(report) == ["overlap-serialization"]
        f = report.findings[0]
        assert "depends on the result" in f.message
        assert f.extra["upstream"] == 1

    @pytest.mark.multi_device
    def test_overlap_serialization_independent_buckets_clean(
            self, dp_mesh):
        mesh = dp_mesh(8)

        def indep(a, b):
            return jax.lax.psum(a, "dp"), jax.lax.psum(b, "dp")

        sm = jax.shard_map(indep, mesh=mesh, in_specs=(P(), P()),
                           out_specs=(P(), P()), check_vma=False)
        big = jnp.ones((1 << 18,), jnp.float32)
        report = lint_fn(jax.jit(sm), big, big,
                         rules="overlap-serialization")
        assert report.ok, report.render()

    @pytest.mark.multi_device
    def test_overlap_serialization_threshold_gates_small_chains(
            self, dp_mesh):
        """The scalar guard-flag psum / per-block scale pmax pattern:
        small collectives neither taint nor trip; dropping
        ``overlap_min_bytes`` below them flips the verdict."""
        mesh = dp_mesh(8)

        def chained(a, b):
            s1 = jax.lax.psum(a, "dp")
            return s1, jax.lax.psum(b + 0.0 * s1[0], "dp")

        sm = jax.shard_map(chained, mesh=mesh, in_specs=(P(), P()),
                           out_specs=(P(), P()), check_vma=False)
        small = jnp.ones((64,), jnp.float32)
        assert lint_fn(jax.jit(sm), small, small,
                       rules="overlap-serialization").ok
        report = lint_fn(jax.jit(sm), small, small,
                         rules="overlap-serialization",
                         config=LintConfig(overlap_min_bytes=16))
        assert _rules_fired(report) == ["overlap-serialization"]

    @pytest.mark.multi_device
    def test_replication_blowup_output(self, dp_mesh):
        mesh = dp_mesh(8)

        @functools.partial(jax.jit,
                           out_shardings=NamedSharding(mesh, P()))
        def f(x):
            return x @ x.T

        xin = jax.device_put(jnp.ones((64, 64)),
                             NamedSharding(mesh, P("dp", None)))
        report = lint_fn(
            f, xin, config=LintConfig(replicated_min_bytes=1024))
        assert "replication-blowup" in _rules_fired(report)
        assert report.findings[0].where == "result[0]"

    @pytest.mark.multi_device
    def test_replication_blowup_constraint(self, dp_mesh):
        mesh = dp_mesh(8)

        def f(x):
            h = x @ x.T
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, P()))
            return jnp.sum(h)

        xin = jax.device_put(jnp.ones((64, 64)),
                             NamedSharding(mesh, P("dp", None)))
        report = lint_fn(
            f, xin, config=LintConfig(replicated_min_bytes=1024))
        assert "replication-blowup" in _rules_fired(report)

    @pytest.mark.multi_device
    def test_sharded_outputs_do_not_fire_replication(self, dp_mesh):
        mesh = dp_mesh(8)

        @functools.partial(
            jax.jit, out_shardings=NamedSharding(mesh, P("dp", None)))
        def f(x):
            return x * 2

        xin = jax.device_put(jnp.ones((64, 64)),
                             NamedSharding(mesh, P("dp", None)))
        report = lint_fn(
            f, xin, config=LintConfig(replicated_min_bytes=1024))
        assert report.ok


# ---------------------------------------------------------------------------
# the SPMD communication rules (ISSUE 13): seeded violations + the
# collective dataflow graph machinery
# ---------------------------------------------------------------------------

def _seeded_module(body, num_partitions=8):
    return ('module @m attributes {mhlo.num_partitions = '
            f'{num_partitions} : i32}} {{\n'
            '  func.func public @main(%arg0: tensor<256xf32>) -> '
            '(tensor<256xf32>) {\n'
            f'{body}'
            '    return %0 : tensor<256xf32>\n  }\n}\n')


def _all_reduce_line(groups, shape="2x128"):
    rows = len(groups)
    cols = len(groups[0]) if groups else 0
    payload = ", ".join("[" + ", ".join(str(d) for d in g) + "]"
                        for g in groups)
    return (f'    %0 = "stablehlo.all_reduce"(%arg0) <{{channel_handle '
            f'= #stablehlo.channel_handle<handle = 1, type = 1>, '
            f'replica_groups = dense<[{payload}]> : '
            f'tensor<{rows}x{cols}xi64>, use_global_device_ids}}> ({{\n'
            f'    ^bb0(%a: tensor<f32>, %b: tensor<f32>):\n'
            f'      stablehlo.return %a : tensor<f32>\n'
            f'    }}) : (tensor<{shape}xf32>) -> tensor<{shape}xf32>\n')


class TestShardingRules:
    def test_implicit_reshard_seeded(self):
        """A collective_permute in the HLO the source jaxpr never
        authored — the GSPMD silent-reshard shape, named by operand
        and wire bytes."""
        from apex_tpu.analysis.lint import LintContext, run_rules

        traced = jax.jit(lambda x: x * 2).trace(jnp.ones((256,)))
        text = _seeded_module(
            '    %0 = "stablehlo.collective_permute"(%arg0) '
            '<{channel_handle = #stablehlo.channel_handle<handle = 1, '
            'type = 1>, source_target_pairs = dense<[[0, 1], [1, 0]]> '
            ': tensor<2x2xi64>}> : (tensor<256xf32>) -> '
            'tensor<256xf32>\n')
        report = run_rules(
            LintContext(hlo_text=text, closed_jaxpr=traced.jaxpr),
            rules="implicit-reshard")
        assert _rules_fired(report) == ["implicit-reshard"]
        f = report.findings[0]
        assert "collective_permute" in f.where
        assert "%arg0" in f.message
        assert f.extra["nbytes"] == 256 * 4  # each device ships it once

    @pytest.mark.slow  # one XLA SPMD-partitioner compile (~50s on the
    # 8-way virtual CPU mesh); the text-seeded test above keeps the
    # rule under tier-1
    @pytest.mark.multi_device
    def test_implicit_reshard_fires_on_real_gspmd_program(self, dp_mesh):
        """The real thing: mismatched in/out shardings force the SPMD
        partitioner to insert a resharding collective that is only
        visible post-compile — audit_spmd catches it, and the same
        post-optimization dialect (iota replica_groups, hyphenated op
        names) parses into the collective graph."""
        from apex_tpu.analysis import sharding

        mesh = dp_mesh(8)
        resharded = functools.partial(
            jax.jit, in_shardings=NamedSharding(mesh, P("dp", None)),
            out_shardings=NamedSharding(mesh, P(None, "dp")))(
                lambda v: v * 2)
        report = sharding.audit_spmd(resharded, jnp.ones((8, 8)),
                                     name="gspmd_reshard")
        fired = _rules_fired(report)
        assert fired == ["implicit-reshard"], report.render()
        assert report.findings[0].extra["nbytes"] > 0
        assert "no corresponding collective" in report.findings[0].message
        # the post-opt dialect parses into the same graph shape (reuse
        # the compile audit_spmd already paid for)
        compiled = resharded.trace(jnp.ones((8, 8))).lower().compile()
        graph = sharding.collective_graph(compiled.as_text())
        kinds = {op.kind for op in graph.ops}
        assert kinds & {"all_to_all", "collective_permute",
                        "all_gather"}
        for op in graph.ops:
            if op.replica_groups is not None:
                assert {d for g in op.replica_groups
                        for d in g} <= set(range(8))

    @pytest.mark.multi_device
    def test_implicit_reshard_clean_when_authored(self, dp_mesh):
        """An authored ppermute matches its lowered collective_permute
        1:1 — no finding."""
        mesh = dp_mesh(8)
        sm = jax.shard_map(
            lambda v: jax.lax.ppermute(
                v, "dp", [(i, (i + 1) % 8) for i in range(8)]),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False)
        report = lint_fn(jax.jit(sm), jnp.ones((8, 4)),
                         rules="implicit-reshard")
        assert report.ok, report.render()

    def test_replica_group_consistency_coverage(self):
        """Groups covering only half the device set: the other half
        executes the op with no group to join — the deadlock shape."""
        from apex_tpu.analysis.lint import LintContext, run_rules

        text = _seeded_module(_all_reduce_line([[0, 1], [2, 3]]))
        report = run_rules(LintContext(hlo_text=text),
                           rules="replica-group-consistency")
        assert _rules_fired(report) == ["replica-group-consistency"]
        f = report.findings[0]
        assert "all_reduce" in f.where
        assert f.extra["missing"] == [4, 5, 6, 7]

    def test_replica_group_consistency_overlap_and_sizes(self):
        from apex_tpu.analysis.lint import LintContext, run_rules

        # device 1 in two groups — not a partition
        text = _seeded_module(
            _all_reduce_line([[0, 1], [1, 2], [3, 4], [5, 6], [7, 0]]),
            num_partitions=8)
        report = run_rules(LintContext(hlo_text=text),
                           rules="replica-group-consistency")
        assert any("more than one group" in f.message
                   for f in report.findings)
        # a clean partition of the full set is quiet
        text = _seeded_module(
            _all_reduce_line([[0, 1, 2, 3], [4, 5, 6, 7]]))
        report = run_rules(LintContext(hlo_text=text),
                           rules="replica-group-consistency")
        assert report.ok, report.render()

    @pytest.mark.multi_device
    def test_comm_budget(self, dp_mesh):
        """Static program wire bytes vs a declared budget; budget 0 =
        no budget declared, the rule runs and is clean."""
        mesh = dp_mesh(8)
        sm = jax.shard_map(lambda a: jax.lax.psum(a, "dp"), mesh=mesh,
                           in_specs=P(), out_specs=P(), check_vma=False)
        big = jnp.ones((1 << 18,), jnp.float32)  # 1 MiB payload
        report = lint_fn(jax.jit(sm), big, rules="comm-budget",
                         config=LintConfig(comm_budget_bytes=1024))
        assert _rules_fired(report) == ["comm-budget"]
        f = report.findings[0]
        assert "all_reduce" in f.where
        assert f.extra["nbytes"] > 1024
        assert f.extra["budget_bytes"] == 1024
        # generous budget -> clean; no budget -> runs and is clean
        assert lint_fn(jax.jit(sm), big, rules="comm-budget",
                       config=LintConfig(
                           comm_budget_bytes=1 << 30)).ok
        report = lint_fn(jax.jit(sm), big, rules="comm-budget")
        assert report.ok and report.rules_run == ("comm-budget",)

    @pytest.mark.multi_device
    def test_sharding_propagation_loss(self, dp_mesh):
        """A large intermediate pinned replicated BETWEEN two sharded
        values — named with both sharded endpoints; the same tensor
        with no sharded consumer stays quiet under this rule."""
        mesh = dp_mesh(8)

        def lossy(x):
            h = x @ x.T
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, P()))
            return jax.lax.with_sharding_constraint(
                h * 2, NamedSharding(mesh, P("dp", None)))

        xin = jax.device_put(jnp.ones((64, 64)),
                             NamedSharding(mesh, P("dp", None)))
        cfg = LintConfig(replicated_min_bytes=1024)
        report = lint_fn(lossy, xin,
                         rules="sharding-propagation-loss", config=cfg)
        assert _rules_fired(report) == ["sharding-propagation-loss"]
        f = report.findings[0]
        assert "line" in f.where
        assert f.extra["nbytes"] == 64 * 64 * 4
        assert "upstream" in f.message and "downstream" in f.message

        def sink(x):
            h = jax.lax.with_sharding_constraint(
                x @ x.T, NamedSharding(mesh, P()))
            return jnp.sum(h)  # no sharded consumer downstream

        report = lint_fn(sink, xin,
                         rules="sharding-propagation-loss", config=cfg)
        assert report.ok, report.render()


@pytest.mark.multi_device
class TestCollectiveGraph:
    """analysis.sharding — the parser + ring model the four rules and
    the bench's static_comm_bytes_per_step stand on."""

    def _measured(self, jitted, args):
        from apex_tpu.telemetry.registry import (MetricsRegistry,
                                                 use_registry)

        reg = MetricsRegistry(enabled=True)
        reg.enable()
        with use_registry(reg):
            lowered = jitted.lower(*args)
        return lowered, reg.counter_value("comm/bytes")

    def test_static_matches_measured_fp32_exact(self, dp_mesh):
        """The ddp_fp32 step: the parsed graph's ring bytes equal the
        trace-measured record_collective total EXACTLY."""
        from apex_tpu.analysis import sharding
        from apex_tpu.analysis.targets import TARGETS

        fn, args, kwargs = TARGETS["ddp_fp32"]()
        lowered, measured = self._measured(fn, args)
        static = sharding.static_comm_bytes(lowered.as_text())
        assert measured > 0
        assert static == int(round(measured))

    def test_static_matches_measured_int8_band(self, dp_mesh):
        """The tiny ddp_compressed (int8 + EF) step: the emulated-int8
        payload is recognized through the convert(i8->i32) feeding the
        psum, so static lands within the documented 25% band of the
        semantic measured bytes (exact under today's emulation)."""
        from apex_tpu.analysis import sharding
        from apex_tpu.analysis.targets import TARGETS

        fn, args, kwargs = TARGETS["ddp_int8"]()
        lowered, measured = self._measured(fn, args)
        graph = sharding.collective_graph(lowered.as_text())
        static = graph.total_wire_bytes
        assert measured > 0
        assert abs(static - measured) / measured <= 0.25
        assert any(op.emulated and op.wire_dtype == "i8"
                   for op in graph.ops)

    def test_graph_structure_tp_dp(self, dp_mesh):
        """The 2-D mesh target carries two collective families with
        DIFFERENT partitions of the same 8 devices — the graph sees
        both, with axes attached from the jaxpr."""
        from apex_tpu.analysis import build_context, sharding
        from apex_tpu.analysis.targets import TARGETS

        fn, args, kwargs = TARGETS["tp_dp"]()
        ctx = build_context(fn, *args, name="tp_dp", **kwargs)
        rows = sharding.comm_table(ctx)
        partitions = {tuple(tuple(g) for g in r["replica_groups"])
                      for r in rows if r["replica_groups"]}
        assert len(partitions) == 2  # TP groups and DP groups coexist
        axes = {a for r in rows for a in (r["axes"] or ())}
        assert axes == {"data", "model"}
        assert any(r["emulated"] for r in rows)  # int8 scoped to data
        dp_rows = [r for r in rows if r["axes"] == ["data"]]
        assert all(len(g) == 2 for r in dp_rows
                   for g in r["replica_groups"])

    def test_graph_edges_and_device_set(self, dp_mesh):
        """The scale pmax feeds the quantized psum — a dataflow edge
        in the collective graph — and the device set is the mesh."""
        from apex_tpu.analysis import sharding
        from apex_tpu.parallel import compression

        mesh = dp_mesh(8)
        sm = jax.shard_map(
            lambda g: compression.psum_compressed(g, "dp"), mesh=mesh,
            in_specs=P(), out_specs=(P(), P()), check_vma=False)
        lowered = jax.jit(sm).lower(jnp.ones((1000,), jnp.float32))
        graph = sharding.collective_graph(lowered.as_text())
        assert len(graph.ops) == 2  # scale pmax + payload psum
        assert (0, 1) in graph.edges
        assert graph.device_set() == set(range(8))

    def test_postopt_hlo_dialect_parses_text(self):
        """The post-partitioning dialect parses without a compile:
        hyphenated op names, iota replica_groups (with and without a
        transpose), and brace groups all land in the graph."""
        from apex_tpu.analysis import sharding

        text = (
            "HloModule jit_f\n"
            "ENTRY %main {\n"
            "  %p0 = f32[4,2]{1,0} parameter(0)\n"
            "  %all-gather = f32[8,2]{1,0} all-gather(f32[4,2]{1,0} "
            "%p0), channel_id=1, replica_groups=[4,2]<=[2,4]T(1,0), "
            "dimensions={0}, use_global_device_ids=true\n"
            "  %all-to-all.1 = f32[8,2]{1,0} all-to-all(f32[8,2]{1,0} "
            "%all-gather), channel_id=2, "
            "replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}\n"
            "  %collective-permute.2 = f32[8,2]{1,0} collective-permute("
            "f32[8,2]{1,0} %all-to-all.1), channel_id=3, "
            "source_target_pairs={{0,1},{1,0}}\n"
            "}\n")
        graph = sharding.collective_graph(text)
        assert [op.kind for op in graph.ops] == [
            "all_gather", "all_to_all", "collective_permute"]
        ag, a2a, cp = graph.ops
        # iota [4,2]<=[2,4]T(1,0): arange(8).reshape(2,4).T -> 4 groups
        assert ag.replica_groups == ((0, 4), (1, 5), (2, 6), (3, 7))
        assert a2a.replica_groups == ((0, 1, 2, 3), (4, 5, 6, 7))
        assert cp.source_target_pairs == ((0, 1), (1, 0))
        assert ag.channel_id == 1 and cp.channel_id == 3
        # dataflow edges follow the def-use chain
        assert (0, 1) in graph.edges and (1, 2) in graph.edges
        # ring model at each op's own group size
        assert ag.wire_bytes == (2 - 1) * 4 * 2 * 4  # (g-1)*shard
        assert a2a.wire_bytes == int(3 / 4 * 8 * 2 * 4)
        assert cp.wire_bytes == 8 * 2 * 4


class TestBenchCommGate:
    """bench.py closes the loop: static stamped next to measured, and
    a disagreement beyond the band fails the bench."""

    def test_bench_stages_static_comm(self, monkeypatch):
        import bench

        step = jax.jit(lambda x: (x * 2, jnp.sum(x)))
        bench._measure_step_cost(step, (jnp.ones((8,)),))
        # no collectives in the step: static is an honest zero
        assert bench._PENDING_MEASURED.get(
            "static_comm_bytes_per_step") == 0
        bench._PENDING_MEASURED.clear()

    def test_bench_static_comm_null_when_disabled(self, monkeypatch):
        import bench

        monkeypatch.setenv("APEX_TPU_STATIC_COMM", "0")
        step = jax.jit(lambda x: (x * 2, jnp.sum(x)))
        bench._measure_step_cost(step, (jnp.ones((8,)),))
        assert bench._PENDING_MEASURED.get(
            "static_comm_bytes_per_step") is None
        bench._PENDING_MEASURED.clear()

    def test_emit_carries_static_comm(self, capsys):
        import bench

        bench._PENDING_MEASURED["static_comm_bytes_per_step"] = 1820
        bench._emit("static_comm_probe_metric", 1.0, "x/sec", 1e9, 1,
                    1.0)
        line = json.loads(capsys.readouterr().out.strip())
        assert line["static_comm_bytes_per_step"] == 1820
        bench._PENDING_MEASURED.clear()

    @pytest.mark.multi_device
    def test_gate_fails_bench_on_disagreement(self, dp_mesh,
                                              monkeypatch):
        """A lying static model (simulated by monkeypatching the
        parser) must crash the measurement, not emit an untrusted
        number; APEX_TPU_COMM_GATE=0 restores the old behavior."""
        import bench
        from apex_tpu.analysis import sharding
        from apex_tpu.analysis.targets import TARGETS

        step, args, _ = TARGETS["ddp_fp32"]()  # instrumented psum
        monkeypatch.setattr(sharding, "static_comm_bytes",
                            lambda text: 1)
        with pytest.raises(RuntimeError,
                           match="comm-bytes disagreement"):
            bench._measure_step_cost(step, args)
        bench._PENDING_MEASURED.clear()
        monkeypatch.setenv("APEX_TPU_COMM_GATE", "0")
        bench._measure_step_cost(step, args)
        assert bench._PENDING_MEASURED[
            "static_comm_bytes_per_step"] == 1
        bench._PENDING_MEASURED.clear()

    @pytest.mark.multi_device
    def test_gate_agrees_on_real_int8_step(self, dp_mesh):
        """The in-bench gate passes on the real compressed step (the
        acceptance's ddp_compressed contract at test size)."""
        import bench
        from apex_tpu.analysis.targets import TARGETS

        fn, args, kwargs = TARGETS["ddp_int8"]()
        bench._measure_step_cost(fn, args)
        staged = dict(bench._PENDING_MEASURED)
        bench._PENDING_MEASURED.clear()
        static = staged["static_comm_bytes_per_step"]
        measured = staged["measured_comm_bytes_per_step"]
        assert static is not None and measured > 0
        assert abs(static - measured) / measured <= 0.25


# ---------------------------------------------------------------------------
# clean pass over the real hot paths — the acceptance's other half
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
class TestCleanHotPaths:
    @pytest.mark.parametrize("name", [n for n in TARGETS
                                      if n != "serve_decode"])
    def test_training_steps_lint_clean(self, name):
        fn, args, kwargs = TARGETS[name]()
        report = assert_clean_hlo(fn, *args, name=name, **kwargs)
        # every rule ran — nothing silently skipped on the full context
        assert not report.rules_skipped
        assert set(report.rules_run) == set(RULES)

    def test_serve_decode_lints_clean(self):
        fn, args, kwargs = TARGETS["serve_decode"]()
        report = assert_clean_hlo(fn, *args, name="serve_decode",
                                  **kwargs)
        assert not report.rules_skipped


# ---------------------------------------------------------------------------
# the donation-repro retirement: the double-donate contract in
# optimizers._base / fp16_optimizer / amp_optimizer, enforced
# ---------------------------------------------------------------------------

class TestDonationContractRegression:
    def _amp_style_step(self, params, masters):
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(p, m):
            new_m = jax.tree_util.tree_map(
                lambda t: t - 0.1 * t, m)
            new_p = jax.tree_util.tree_map(
                lambda t: t.astype(jnp.float32), new_m)
            return new_p, new_m

        return step

    def test_astype_masters_trip_double_donation(self):
        """The exact round-2/3 bug shape: fp32 masters built with a
        no-op astype alias the already-fp32 (norm) params; donating
        both would die in Execute() — the rule catches it at trace
        time instead."""
        params = {"conv": jnp.ones((8, 8), jnp.float32),
                  "norm_scale": jnp.ones((8,), jnp.float32)}
        aliased = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), params)  # no-op = alias
        step = self._amp_style_step(params, aliased)
        report = lint_fn(step, params, aliased)
        assert "double-donation" in _rules_fired(report)

    def test_master_copy_tree_masters_are_clean(self):
        """master_copy_tree (the fix) forces distinct buffers — the
        same donated step lints clean."""
        from apex_tpu.optimizers._base import master_copy_tree

        params = {"conv": jnp.ones((8, 8), jnp.float32),
                  "norm_scale": jnp.ones((8,), jnp.float32)}
        masters = master_copy_tree(params)
        step = self._amp_style_step(params, masters)
        assert_clean_hlo(step, params, masters,
                         rules="double-donation")

    def test_amp_optimizer_masters_are_alias_free(self):
        """The real amp O2 init path: AMPOptimizer's fp32 masters must
        not alias params (the contract the comments in amp_optimizer
        used to merely describe)."""
        from apex_tpu.amp.amp_optimizer import AmpOptimizer
        from apex_tpu.amp.scaler import LossScaler
        from apex_tpu.optimizers import FusedAdam

        params = {"dense": jnp.ones((16, 16), jnp.float32),
                  "scale": jnp.ones((16,), jnp.float32)}
        opt = AmpOptimizer(FusedAdam(lr=1e-3), LossScaler(128.0),
                           master_weights=True)
        state = opt.init(params)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step(p, s):
            new_p, new_s = opt.step(
                jax.tree_util.tree_map(jnp.ones_like, p), s, p)
            return new_p, new_s

        assert_clean_hlo(train_step, params, state,
                         rules="double-donation")


# ---------------------------------------------------------------------------
# report / selection machinery
# ---------------------------------------------------------------------------

class TestLintMachinery:
    def test_unknown_rule_raises(self):
        with pytest.raises(ValueError, match="unknown lint rule"):
            lint_fn(lambda x: x, jnp.ones(()), rules="no-such-rule")

    def test_waive_excludes_rule(self):
        baked = jnp.arange(2048, dtype=jnp.float32)
        report = lint_fn(lambda x: x + baked, jnp.ones((2048,)),
                         waive="trace-constant-capture",
                         config=LintConfig(const_min_bytes=64))
        assert report.ok
        assert "trace-constant-capture" not in report.rules_run

    def test_assert_clean_hlo_raises_with_rule_and_where(self):
        def poisoned(x):
            return jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        with pytest.raises(HloLintError) as exc:
            assert_clean_hlo(poisoned, jnp.ones((4,)))
        msg = str(exc.value)
        assert "no-host-callback" in msg
        assert "custom_call @" in msg

    def test_lint_lowered_skips_jaxpr_rules_visibly(self):
        lowered = jax.jit(lambda x: x * 2).lower(jnp.ones((4,)))
        report = lint_lowered(lowered)
        assert report.ok
        assert "unexpected-upcast" in report.rules_skipped
        assert "collective-consistency" in report.rules_skipped
        # text-capable rules still ran
        assert "no-host-callback" in report.rules_run
        assert "trace-constant-capture" in report.rules_run

    def test_lint_lowered_const_fallback_uses_text(self):
        baked = jnp.arange(4096, dtype=jnp.float32)
        lowered = jax.jit(lambda x: x + baked).lower(jnp.ones((4096,)))
        report = lint_lowered(
            lowered, config=LintConfig(const_min_bytes=1024))
        assert _rules_fired(report) == ["trace-constant-capture"]

    def test_report_shapes(self):
        report = lint_fn(lambda x: x, jnp.ones(()))
        d = report.to_dict()
        assert d["violations"] == 0
        assert set(d["rules_run"]) == set(RULES)
        assert "0 violation(s)" in report.render()
        counts = report.counts()
        assert all(v == 0 for v in counts.values())

    def test_finding_to_dict(self):
        f = Finding("r", "msg", where="w", extra={"nbytes": 3})
        assert f.to_dict() == {"rule": "r", "severity": "error",
                               "message": "msg", "where": "w",
                               "nbytes": 3}

    def test_report_to_registry_emits_events(self, tmp_path):
        from apex_tpu.telemetry.registry import (MetricsRegistry,
                                                 use_registry)

        reg = MetricsRegistry(enabled=True)
        reg.enable(jsonl_dir=str(tmp_path))
        report = LintReport("prog", [Finding("no-f64", "bad")],
                            ("no-f64",), ())
        with use_registry(reg):
            analysis.report_to_registry(report, registry=reg)
        assert reg.counter_value("lint/violations") == 1
        events = [json.loads(line) for p in tmp_path.glob("*.jsonl")
                  for line in open(p) if line.strip()]
        lint_events = [e for e in events if e["kind"] == "lint"]
        assert any(e.get("rule") == "no-f64" for e in lint_events)
        summary = [e for e in lint_events if e.get("summary")]
        assert summary and summary[-1]["violations"] == 1


# ---------------------------------------------------------------------------
# CompileWatcher + bench integration
# ---------------------------------------------------------------------------

class TestWatcherIntegration:
    def test_watcher_lints_on_compile(self, tmp_path, monkeypatch):
        from apex_tpu.telemetry import CompileWatcher
        from apex_tpu.telemetry.registry import (MetricsRegistry,
                                                 use_registry)

        reg = MetricsRegistry(enabled=True)
        reg.enable(jsonl_dir=str(tmp_path))
        watcher = CompileWatcher(enabled=True, lint=True,
                                 registry=reg)

        baked = jnp.arange(1024, dtype=jnp.float32)
        monkeypatch.setenv("APEX_TPU_HLO_LINT_CONST_BYTES", "512")

        @jax.jit
        def step(x):
            return x + baked

        with use_registry(reg):
            watched = watcher.watch(step, "bad_step")
            watched(jnp.ones((1024,)))  # compiles -> lints
        assert "bad_step" in watcher.lint_reports
        assert watcher.lint_violation_count() >= 1
        events = [json.loads(line) for p in tmp_path.glob("*.jsonl")
                  for line in open(p) if line.strip()]
        lint_events = [e for e in events if e["kind"] == "lint"]
        assert any(e.get("rule") == "trace-constant-capture"
                   for e in lint_events)

    def test_watcher_lint_off_by_default(self):
        from apex_tpu.telemetry import CompileWatcher

        watcher = CompileWatcher(enabled=True, lint=False)
        watched = watcher.watch(jax.jit(lambda x: x * 3), "clean")
        watched(jnp.ones((4,)))
        assert watcher.lint_reports == {}

    def test_record_aot_lints_lowered(self, monkeypatch):
        from apex_tpu.telemetry import CompileWatcher

        monkeypatch.setenv("APEX_TPU_HLO_LINT_CONST_BYTES", "512")
        watcher = CompileWatcher(enabled=True, lint=True)
        baked = jnp.arange(1024, dtype=jnp.float32)
        lowered = jax.jit(lambda x: x + baked).lower(jnp.ones((1024,)))
        watcher.record_aot("aot_prog", (jnp.ones((1024,)),),
                           seconds=0.1, lowered=lowered)
        assert watcher.lint_violation_count() >= 1

    def test_bench_stages_lint_violations(self, monkeypatch):
        import bench

        monkeypatch.setenv("APEX_TPU_HLO_LINT", "1")
        step = jax.jit(lambda x: (x * 2, jnp.sum(x)))
        bench._measure_step_cost(step, (jnp.ones((8,)),))
        assert bench._PENDING_MEASURED.get("lint_violations") == 0
        bench._PENDING_MEASURED.clear()

    def test_bench_lint_null_when_unset(self, monkeypatch):
        import bench

        monkeypatch.delenv("APEX_TPU_HLO_LINT", raising=False)
        step = jax.jit(lambda x: (x * 2, jnp.sum(x)))
        bench._measure_step_cost(step, (jnp.ones((8,)),))
        assert bench._PENDING_MEASURED.get("lint_violations") is None
        bench._PENDING_MEASURED.clear()

    def test_emit_carries_lint_violations(self, capsys):
        import bench

        bench._PENDING_MEASURED["lint_violations"] = 2
        bench._emit("lint_probe_metric", 1.0, "x/sec", 1e9, 1, 1.0)
        line = json.loads(capsys.readouterr().out.strip())
        assert line["lint_violations"] == 2
        bench._PENDING_MEASURED.clear()


# ---------------------------------------------------------------------------
# tools: CLI table + telemetry_report lint kind
# ---------------------------------------------------------------------------

class TestTools:
    def test_hlo_lint_run_and_table(self):
        """The CLI machinery on a subset (the full table incl. the
        serving engine is exercised by the CLI itself and the clean-
        pass tests above)."""
        import tools.hlo_lint as hlo_lint

        reports = hlo_lint.run_lint(configs=["ddp_fp32"])
        assert list(reports) == ["ddp_fp32"]
        assert reports["ddp_fp32"].ok
        table = hlo_lint.render_table(reports)
        assert "ddp_fp32" in table
        assert "no-host-callback" in table

    def test_hlo_lint_unknown_config(self):
        import tools.hlo_lint as hlo_lint

        with pytest.raises(SystemExit, match="unknown config"):
            hlo_lint.run_lint(configs=["nope"])

    @pytest.mark.multi_device
    def test_hlo_lint_comm_table(self):
        """--comm: one trace serves both the rule report and the
        collective table; the int8 emulation is called out."""
        import tools.hlo_lint as hlo_lint

        reports, tables = hlo_lint.run_lint(configs=["ddp_int8"],
                                            comm=True)
        assert reports["ddp_int8"].ok
        rows = tables["ddp_int8"]
        assert rows and all(r["op"] == "all_reduce" for r in rows)
        assert any(r["emulated"] for r in rows)
        assert all(r["wire_bytes"] > 0 for r in rows)
        text = hlo_lint.render_comm_table(tables)
        assert "ddp_int8" in text
        assert "emulated int8" in text
        assert "axes=dp" in text

    def test_telemetry_report_renders_sharding_rules(self):
        """The lint kind is rule-name generic: the four new rules'
        findings roll up exactly like the PR-9 rules'."""
        from tools.telemetry_report import aggregate

        events = [
            ("r0", {"kind": "lint", "name": "step",
                    "rule": "implicit-reshard", "severity": "error",
                    "message": "inserted", "where": "all_to_all@line 9",
                    "nbytes": 4096}),
            ("r0", {"kind": "lint", "name": "step",
                    "rule": "comm-budget", "severity": "error",
                    "message": "over", "where": "all_reduce@line 3"}),
            ("r0", {"kind": "lint", "name": "step", "summary": True,
                    "violations": 2, "clean": False,
                    "rules_run": ["implicit-reshard", "comm-budget"],
                    "rules_skipped": []}),
        ]
        rep = aggregate(events)
        assert rep["lint"]["violations"] == 2
        assert rep["lint"]["by_rule"] == {"implicit-reshard": 1,
                                          "comm-budget": 1}
        assert rep["unknown_kinds"] == {}

    def test_telemetry_report_lint_kind(self):
        from tools.telemetry_report import aggregate

        events = [
            ("r0", {"kind": "lint", "name": "step",
                    "rule": "no-f64", "severity": "error",
                    "message": "bad", "where": "line 3"}),
            ("r0", {"kind": "lint", "name": "step", "summary": True,
                    "violations": 1, "clean": False,
                    "rules_run": ["no-f64"], "rules_skipped": []}),
            ("r0", {"kind": "lint", "name": "other", "summary": True,
                    "violations": 0, "clean": True,
                    "rules_run": ["no-f64"], "rules_skipped": []}),
        ]
        rep = aggregate(events)
        assert rep["lint"]["violations"] == 1
        assert rep["lint"]["by_rule"] == {"no-f64": 1}
        assert rep["lint"]["programs"]["step"]["clean"] is False
        assert rep["lint"]["programs"]["other"]["clean"] is True
        # and the kind is known — not counted as unknown
        assert rep["unknown_kinds"] == {}
