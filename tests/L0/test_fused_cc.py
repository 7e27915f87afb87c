"""apex_tpu.kernels.fused_cc — fused computation-collective kernels
(ISSUE 16).

Covers the tentpole acceptance on the CPU container, interpret-mode
only (nothing compiles a Pallas binary):

- family (a): ``matmul_reduce_from`` / ``matmul_reduce_scatter`` /
  ``all_gather_matmul`` match their compute-then-collective oracles on
  the 4-way model mesh — forward to fp32 tolerance, gradients
  BIT-exact against the real ``copy_to``/``reduce_from`` custom-vjp
  composition mesh2d differentiates, and measured trace-time wire
  bytes identical (T tile psums == one psum; g-1 ring permutes == one
  scatter/gather).
- family (b): the verify-window flash kernel against the einsum
  oracle across starts/window/softcap, the int8-KV fused verify
  against materialize-then-attend including a ragged quantization
  tail, the ``use_window`` gate ladder, the ``ServeConfig.fused_verify``
  scope knob, and the transformer_lm multi-token-chunk wiring (fused
  chunk logits == einsum chunk logits through the real model gate).
- family (c): one-kernel quantize+pack / unpack+dequant bit-exact
  against quant4's two-step path (including the ragged odd-lane tail,
  both jnp and interpret — satellite 3), and the fused
  ``_all_gather_int4`` ring bit-identical to the unfused path.
- static auditor: ``wire_bytes_for``'s ``n_pairs`` contract incl. the
  group_size=1 degenerate (satellite 2); fused custom_call targets
  priced EXACTLY like their unfused collective in both HLO dialects;
  unknown targets stay unpriced; lowered fused programs' static wire
  bytes equal to their unfused equivalents'.
- telemetry/tooling satellites: the flat
  ``kernels/dispatch/<name>_<path>`` counter and its
  telemetry_report fold; the bench_trend band + per-family timing
  gate; the bench_schema round-21 fused_cc contract.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.analysis import sharding as asharding
from apex_tpu.kernels import fused_cc, quant4
from apex_tpu.kernels.registry import get_kernel_registry
from apex_tpu.parallel import compression, mesh2d
from apex_tpu.testing import shard_map
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region as _copy_to,
    reduce_from_tensor_model_parallel_region as _reduce_from,
)

_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
for _p in (_ROOT, os.path.join(_ROOT, "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

KREG = get_kernel_registry()
AX = "model"


@pytest.fixture
def interpret():
    KREG.force_interpret(True)
    try:
        yield
    finally:
        KREG.force_interpret(False)


# ---------------------------------------------------------------------------
# family (a): matmul <-> collective
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
class TestMatmulCollectiveFusion:
    G, M, K, N = 4, 8, 16, 32

    def _data(self, rng):
        x = jnp.asarray(rng.randn(self.M, self.K).astype(np.float32))
        w = jnp.asarray(
            rng.randn(self.G * self.K, self.N).astype(np.float32))
        return x, w

    def test_matmul_reduce_from_matches_composition(
            self, rng, dp_mesh, interpret):
        mesh = dp_mesh(self.G, axis_name=AX)
        x, w = self._data(rng)

        def fused(xs, ws):
            return fused_cc.matmul_reduce_from(xs, ws, AX)

        def oracle(xs, ws):
            return _reduce_from(xs @ ws, AX)

        specs = dict(mesh=mesh, in_specs=(P(), P(AX)), out_specs=P())
        got = shard_map(fused, **specs)(x, w)
        want = shard_map(oracle, **specs)(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_matmul_reduce_from_grads_bit_exact(
            self, rng, dp_mesh, interpret):
        """The acceptance gradient contract: the fused op's custom vjp
        composed with ``copy_to`` must be BIT-identical to the
        ``copy_to``/matmul/``reduce_from`` chain mesh2d
        differentiates (psum forward, identity backward — NOT raw
        ``lax.psum``, whose transpose is not identity)."""
        mesh = dp_mesh(self.G, axis_name=AX)
        x, w = self._data(rng)

        def grads(loss):
            def body(xs, ws):
                return jax.grad(loss, argnums=(0, 1))(xs, ws)
            return shard_map(body, mesh=mesh, in_specs=(P(), P(AX)),
                             out_specs=(P(), P(AX)))(x, w)

        def loss_f(xs, ws):
            return fused_cc.matmul_reduce_from(
                _copy_to(xs, AX), ws, AX).sum()

        def loss_o(xs, ws):
            return _reduce_from(_copy_to(xs, AX) @ ws, AX).sum()

        dx_f, dw_f = grads(loss_f)
        dx_o, dw_o = grads(loss_o)
        np.testing.assert_array_equal(np.asarray(dx_f),
                                      np.asarray(dx_o))
        np.testing.assert_array_equal(np.asarray(dw_f),
                                      np.asarray(dw_o))

    def test_matmul_reduce_scatter_matches_oracle(
            self, rng, dp_mesh, interpret, monkeypatch):
        mesh = dp_mesh(self.G, axis_name=AX)
        x, w = self._data(rng)
        specs = dict(mesh=mesh, in_specs=(P(), P(AX)),
                     out_specs=P(AX))

        def run():
            def body(xs, ws):
                return fused_cc.matmul_reduce_scatter(xs, ws, AX)
            return np.asarray(shard_map(body, **specs)(x, w))

        got = run()
        KREG.force_interpret(False, ["fused_cc"])
        want = run()
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_all_gather_matmul_matches_oracle(
            self, rng, dp_mesh, interpret, monkeypatch):
        mesh = dp_mesh(self.G, axis_name=AX)
        xfull = jnp.asarray(
            rng.randn(self.G * self.M, self.K).astype(np.float32))
        w = jnp.asarray(rng.randn(self.K, self.N).astype(np.float32))
        specs = dict(mesh=mesh, in_specs=(P(AX), P()), out_specs=P())

        def run():
            def body(xs, ws):
                return fused_cc.all_gather_matmul(xs, ws, AX)
            return np.asarray(shard_map(body, **specs)(xfull, w))

        got = run()
        KREG.force_interpret(False, ["fused_cc"])
        want = run()
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("family", ["reduce_from", "scatter",
                                        "gather"])
    def test_measured_wire_bytes_identical(
            self, rng, dp_mesh, interpret, monkeypatch, family):
        """Trace-time comm accounting parity: the fused decomposition
        records exactly the wire bytes of the unfused collective — T
        psums of payload/T, or g-1 full-priced permutes of
        payload/g."""
        from apex_tpu.telemetry.registry import (
            MetricsRegistry,
            use_registry,
        )

        mesh = dp_mesh(self.G, axis_name=AX)
        x, w = self._data(rng)
        xg = jnp.asarray(
            rng.randn(self.G * self.M, self.K).astype(np.float32))
        wg = jnp.asarray(rng.randn(self.K, self.N).astype(np.float32))

        def leg():
            reg = MetricsRegistry(enabled=True)
            with use_registry(reg):
                if family == "reduce_from":
                    shard_map(
                        lambda a, b: fused_cc.matmul_reduce_from(
                            a, b, AX),
                        mesh=mesh, in_specs=(P(), P(AX)),
                        out_specs=P())(x, w)
                elif family == "scatter":
                    shard_map(
                        lambda a, b: fused_cc.matmul_reduce_scatter(
                            a, b, AX),
                        mesh=mesh, in_specs=(P(), P(AX)),
                        out_specs=P(AX))(x, w)
                else:
                    shard_map(
                        lambda a, b: fused_cc.all_gather_matmul(
                            a, b, AX),
                        mesh=mesh, in_specs=(P(AX), P()),
                        out_specs=P())(xg, wg)
            return reg.snapshot()["counters"].get("comm/bytes", 0.0)

        fused_bytes = leg()
        KREG.force_interpret(False, ["fused_cc"])
        unfused_bytes = leg()
        assert fused_bytes == unfused_bytes > 0


# ---------------------------------------------------------------------------
# family (b): verify-window flash attention
# ---------------------------------------------------------------------------

class TestVerifyWindow:
    @pytest.mark.parametrize("window,softcap", [(None, None), (7, None),
                                                (None, 30.0),
                                                (6, 25.0)])
    def test_window_attention_parity(self, rng, interpret, window,
                                     softcap):
        w, b, g, rep, d, T = 4, 2, 2, 2, 16, 64
        qg = jnp.asarray(
            rng.randn(w, b, g, rep, d).astype(np.float32))
        kt = jnp.asarray(rng.randn(T, b, g, d).astype(np.float32))
        vt = jnp.asarray(rng.randn(T, b, g, d).astype(np.float32))
        for start in (0, 1, 37, T - w):
            want = fused_cc.window_attention_reference(
                qg, kt, vt, start, 0.25, window=window, softcap=softcap)
            got = fused_cc.window_attention(
                qg, kt, vt, start, 0.25, window=window, softcap=softcap,
                block_t=32)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       rtol=2e-5, atol=2e-5)

    def test_window_attention_query_blocks(self, rng, interpret,
                                           monkeypatch):
        """A window whose rows overflow the scratch budget splits into
        8-row-aligned query blocks, each with its own causal bound and
        tile clamp — a prefill-sized window must match the oracle
        across block edges, and a window no block fits declines."""
        monkeypatch.setattr(fused_cc, "_WINDOW_ROW_BUDGET", 32)
        w, b, g, rep, d, T = 16, 1, 2, 2, 16, 64
        assert fused_cc._q_block(w, g, rep) == 8
        qg = jnp.asarray(rng.randn(w, b, g, rep, d).astype(np.float32))
        kt = jnp.asarray(rng.randn(T, b, g, d).astype(np.float32))
        vt = jnp.asarray(rng.randn(T, b, g, d).astype(np.float32))
        for start in (0, 21, T - w):
            want = fused_cc.window_attention_reference(
                qg, kt, vt, start, 0.25, window=9)
            got = fused_cc.window_attention(
                qg, kt, vt, start, 0.25, window=9, block_t=16)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
        # 3 rows x 2 reps never align to 8 and overflow the budget
        monkeypatch.setattr(fused_cc, "_WINDOW_ROW_BUDGET", 8)
        assert not fused_cc.use_window(T, q_shape=(3, b, g, rep, d))

    @pytest.mark.parametrize("d", [64, 40])
    def test_spec_verify_parity_including_ragged_tail(self, rng,
                                                      interpret, d):
        """int8-KV fused verify vs materialize-then-attend. d=40 makes
        g*d = 160 lanes against one 256-lane quantization block — the
        ragged-tail layout the serving cache actually stores."""
        T, w, g, rep = 64, 3, 4, 2
        feat = g * d
        q = jnp.asarray(rng.randn(w, g, rep, d).astype(np.float32))
        kq, ks = compression.quantize_rows_blockwise(
            jnp.asarray(rng.randn(T, feat).astype(np.float32)))
        vq, vs = compression.quantize_rows_blockwise(
            jnp.asarray(rng.randn(T, feat).astype(np.float32)))
        for start in (0, 13, T - w):
            want = fused_cc.spec_verify_reference(
                q, kq, ks, vq, vs, start, 0.25)
            got = fused_cc.spec_verify_attention(
                q, kq, ks, vq, vs, start, 0.25, block_t=32)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       rtol=2e-5, atol=2e-5)

    def test_use_window_gate_ladder(self):
        # gate off on CPU (no interpret forcing): oracle
        assert not fused_cc.use_window(64)
        KREG.force_interpret(True, ["fused_cc"])
        try:
            assert fused_cc.use_window(64)
            # no block divides a 1000-long buffer: kernel declines
            assert not fused_cc.use_window(1000)
            with fused_cc.verify_scope(False):
                assert not fused_cc.use_window(64)
            assert fused_cc.use_window(64)
        finally:
            KREG.force_interpret(False, ["fused_cc"])

    def test_serve_config_fused_verify_knob(self):
        from apex_tpu.serving.engine import ServeConfig

        assert ServeConfig().fused_verify is True
        assert ServeConfig(fused_verify=False).fused_verify is False


class TestModelWindowWiring:
    def test_multi_token_chunk_matches_einsum(self, monkeypatch):
        """transformer_lm wiring: a 3-token continuation chunk over an
        initialized cache takes the window kernel when the gate is
        live and must reproduce the chunked-einsum logits (the same
        integration gate discipline as the s==1 gqa_decode path)."""
        from apex_tpu.models import GPTModel, TransformerConfig
        from apex_tpu.models import generation as gen
        from apex_tpu.transformer import parallel_state

        parallel_state.destroy_model_parallel()
        cfg = TransformerConfig(
            hidden_size=48, num_layers=2, num_attention_heads=4,
            vocab_size=96, max_position_embeddings=32,
            compute_dtype=jnp.float32, use_flash_attention=False,
            normalization="rmsnorm", position_embedding_type="rope",
            activation="swiglu", num_query_groups=2)
        model = GPTModel(cfg, decode=True)
        rng = np.random.RandomState(5)
        prompt = jnp.asarray(rng.randint(0, 96, size=(2, 6)))
        chunk = jnp.asarray(rng.randint(0, 96, size=(2, 3)))
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]

        def run():
            cache = gen.init_cache(model, 2)
            cache, _ = gen.prefill(model, params, cache, prompt,
                                   jnp.arange(6)[None, :])
            _, logits = gen.prefill(model, params, cache, chunk,
                                    (6 + jnp.arange(3))[None, :],
                                    full_logits=True)
            return np.asarray(logits)

        KREG.force_interpret(True, ["fused_cc"])
        try:
            fused_logits = run()
        finally:
            KREG.force_interpret(False, ["fused_cc"])
        einsum_logits = run()
        np.testing.assert_allclose(fused_logits, einsum_logits,
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# family (c): quantize-into-ring int4
# ---------------------------------------------------------------------------

class TestQuantizeIntoRing:
    def _scaled(self, rng, nb, lanes):
        x2d = jnp.asarray(rng.randn(nb, lanes).astype(np.float32))
        absmax = jnp.maximum(
            jnp.max(jnp.abs(x2d), axis=-1, keepdims=True), 1e-12)
        sq, gmax = quant4.int4_block_scales(absmax)
        return x2d, quant4.effective_scales(sq, gmax)

    @pytest.mark.parametrize("lanes", [256, 13])
    def test_quantize_pack_bit_exact(self, rng, interpret, lanes):
        x2d, scales = self._scaled(rng, 8, lanes)
        got = np.asarray(fused_cc.quantize_pack_int4(x2d, scales))
        want = np.asarray(quant4._pack_jnp(
            quant4._quantize_jnp(quant4._pad_even_lanes(x2d), scales)))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("lanes", [256, 13])
    def test_unpack_dequantize_bit_exact(self, rng, interpret, lanes):
        x2d, scales = self._scaled(rng, 8, lanes)
        packed = quant4._pack_jnp(quant4._quantize_jnp(
            quant4._pad_even_lanes(x2d), scales))
        got = np.asarray(fused_cc.unpack_dequantize_int4(
            packed, scales, n=lanes))
        want = np.asarray(quant4._dequantize_jnp(
            quant4._unpack_jnp(packed, n=lanes), scales))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("path", ["jnp", "interpret"])
    def test_quant4_ragged_tail_roundtrip_bit_identical(self, rng,
                                                        path):
        """Satellite 3: a last block whose lane count is NOT a pack
        width multiple must round-trip pack->unpack bit-identically in
        both the jnp and interpret paths (one zero lane padded, then
        truncated back via ``n=``)."""
        q = jnp.asarray(
            rng.randint(-7, 8, size=(5, 13)).astype(np.int8))
        if path == "interpret":
            KREG.force_interpret(True, ["quant4"])
        try:
            rt = quant4.unpack_int4(quant4.pack_int4(q), n=13)
        finally:
            KREG.force_interpret(False, ["quant4"])
        np.testing.assert_array_equal(np.asarray(rt), np.asarray(q))

    @pytest.mark.multi_device
    def test_all_gather_int4_fused_matches_unfused(
            self, rng, dp_mesh, interpret, monkeypatch):
        """The ring itself: quantize-into-send / dequant-out-of-receive
        must be bit-identical to quant4's two-step path around the
        same gather."""
        g = 4
        mesh = dp_mesh(g, axis_name=AX)
        full = jnp.asarray(rng.randn(g * 512).astype(np.float32))

        def run():
            def body(sh):
                return compression._all_gather_int4(sh, AX)
            return np.asarray(shard_map(
                body, mesh=mesh, in_specs=(P(AX),),
                out_specs=P())(full))

        got = run()
        KREG.force_interpret(False, ["fused_cc"])
        want = run()
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# mesh2d integration: the fused= knob end to end
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
class TestMesh2dFusedStep:
    def test_fused_train_step_matches_unfused(self, interpret):
        """build_train_step(fused=True) on the 2x2 mesh: same loss and
        same post-step params as the unfused composition (identical
        collectives and custom-vjp gradients; only the GEMM runs
        through the kernel)."""
        mesh = mesh2d.mesh_2d(2)
        sp = mesh2d.gpt2_init(hidden=32, layers=2, heads=4, vocab=64,
                              max_seq=8)
        outs = {}
        for fused in (False, True):
            step, state = mesh2d.build_train_step(
                mesh, sp, hidden=32, heads=4, mode="baseline",
                fused=fused)
            tokens, labels = mesh2d.make_batch(
                mesh, batch_per_replica=2, seq=8, vocab=64)
            outs[fused] = step(*state, tokens, labels)
        np.testing.assert_allclose(float(outs[True][2]),
                                   float(outs[False][2]), rtol=2e-5)
        for pf, pu in zip(jax.tree_util.tree_leaves(outs[True][0]),
                          jax.tree_util.tree_leaves(outs[False][0])):
            np.testing.assert_allclose(np.asarray(pf), np.asarray(pu),
                                       rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# static auditor: n_pairs + fused custom_call pricing
# ---------------------------------------------------------------------------

class TestWireBytesForNPairs:
    """Satellite 2: the previously-untested ``n_pairs`` parameter."""

    def test_permute_prices_full_payload_when_pairs_exist(self):
        assert asharding.wire_bytes_for(
            "collective_permute", 1024, 4, n_pairs=3) == 1024.0

    def test_permute_without_real_pairs_is_free(self):
        # self-loop-only permutes (n_pairs=0) move nothing
        assert asharding.wire_bytes_for(
            "collective_permute", 1024, 4) == 0.0

    def test_permute_ignores_group_size_degenerate(self):
        # a permute's price keys on pairs, not group size: even the
        # group_size=1 degenerate ships the payload once per pair
        assert asharding.wire_bytes_for(
            "collective_permute", 512, 1, n_pairs=1) == 512.0

    def test_group_size_one_degenerate_is_free(self):
        for kind in ("all_reduce", "all_gather", "reduce_scatter",
                     "all_to_all"):
            assert asharding.wire_bytes_for(kind, 4096, 1) == 0.0

    def test_ring_model_factors(self):
        assert asharding.wire_bytes_for("all_reduce", 1024, 4) \
            == 2.0 * 3 / 4 * 1024
        assert asharding.wire_bytes_for("all_gather", 100, 8) == 700.0
        assert asharding.wire_bytes_for("reduce_scatter", 800, 8) \
            == 700.0


class TestFusedCustomCallPricing:
    def test_target_tables_agree(self):
        assert asharding.FUSED_CC_TARGETS \
            == fused_cc.FUSED_CC_CUSTOM_CALL_TARGETS

    def test_stablehlo_custom_call_priced_like_unfused(self):
        text = (
            'module @jit_f attributes {mhlo.num_partitions = 4 : i32} '
            '{\n'
            '  func.func public @main(%arg0: tensor<8x16xf32>, '
            '%arg1: tensor<16x32xf32>) -> tensor<8x32xf32> {\n'
            '    %0 = stablehlo.custom_call '
            '@apex_fused_cc_matmul_all_reduce(%arg0, %arg1) '
            '{apex_payload_bytes = "1024", apex_group_size = "4"} : '
            '(tensor<8x16xf32>, tensor<16x32xf32>) -> '
            'tensor<8x32xf32>\n'
            '    return %0 : tensor<8x32xf32>\n'
            '  }\n'
            '}\n')
        g = asharding.collective_graph(text)
        assert len(g.ops) == 1
        op = g.ops[0]
        assert op.kind == "all_reduce"
        assert op.custom_target == "apex_fused_cc_matmul_all_reduce"
        assert op.group_size == 4
        assert op.payload_bytes == 1024
        assert op.wire_bytes == int(round(
            asharding.wire_bytes_for("all_reduce", 1024, 4)))
        assert g.total_wire_bytes == 1536

    def test_hlo_custom_call_priced_like_unfused(self):
        text = (
            "HloModule jit_g\n"
            "ENTRY %main (p0: u8[4,128]) -> f32[4,1024] {\n"
            "  %p0 = u8[4,128] parameter(0)\n"
            "  %cc = f32[4,1024] custom-call(u8[4,128] %p0), "
            "custom_call_target=\"apex_fused_cc_quant4_all_gather\", "
            "frontend_attributes={apex_payload_bytes=\"512\","
            "apex_group_size=\"8\"}\n"
            "  ROOT %r = f32[4,1024] copy(f32[4,1024] %cc)\n"
            "}\n")
        g = asharding.collective_graph(text)
        assert len(g.ops) == 1
        op = g.ops[0]
        assert op.kind == "all_gather"
        assert op.group_size == 8
        assert op.wire_bytes == int(round(
            asharding.wire_bytes_for("all_gather", 512, 8)))

    def test_unknown_custom_call_stays_unpriced(self):
        text = (
            'module @jit_h {\n'
            '  func.func public @main(%arg0: tensor<8xf32>) -> '
            'tensor<8xf32> {\n'
            '    %0 = stablehlo.custom_call @some_vendor_op(%arg0) : '
            '(tensor<8xf32>) -> tensor<8xf32>\n'
            '    return %0 : tensor<8xf32>\n'
            '  }\n'
            '}\n')
        assert asharding.collective_graph(text).ops == []

    def test_custom_target_lands_in_report_row(self):
        text = (
            'module @jit_f {\n'
            '  func.func public @main(%arg0: tensor<8xf32>) -> '
            'tensor<8xf32> {\n'
            '    %0 = stablehlo.custom_call '
            '@apex_fused_cc_all_gather_matmul(%arg0) '
            '{apex_payload_bytes = "32", apex_group_size = "2"} : '
            '(tensor<8xf32>) -> tensor<8xf32>\n'
            '    return %0 : tensor<8xf32>\n'
            '  }\n'
            '}\n')
        rows = asharding.collective_graph(text).to_rows()
        assert rows[0]["custom_target"] \
            == "apex_fused_cc_all_gather_matmul"


@pytest.mark.multi_device
class TestStaticParityLowered:
    """EXACT fused-vs-unfused agreement of the auditor over real
    lowered programs (the acceptance gate the bench also enforces)."""

    @pytest.mark.parametrize("family", ["reduce_from", "scatter",
                                        "gather", "int4_ring"])
    def test_static_comm_bytes_equal(self, rng, dp_mesh, interpret,
                                     monkeypatch, family):
        g = 4
        mesh = dp_mesh(g, axis_name=AX)
        x = jnp.asarray(rng.randn(8, 16).astype(np.float32))
        w = jnp.asarray(rng.randn(g * 16, 32).astype(np.float32))
        wg = jnp.asarray(rng.randn(16, 32).astype(np.float32))
        xg = jnp.asarray(rng.randn(g * 8, 16).astype(np.float32))
        flat = jnp.asarray(rng.randn(g * 512).astype(np.float32))

        def lowered():
            if family == "reduce_from":
                fn = shard_map(
                    lambda a, b: fused_cc.matmul_reduce_from(a, b, AX),
                    mesh=mesh, in_specs=(P(), P(AX)), out_specs=P())
                args = (x, w)
            elif family == "scatter":
                fn = shard_map(
                    lambda a, b: fused_cc.matmul_reduce_scatter(
                        a, b, AX),
                    mesh=mesh, in_specs=(P(), P(AX)), out_specs=P(AX))
                args = (x, w)
            elif family == "gather":
                fn = shard_map(
                    lambda a, b: fused_cc.all_gather_matmul(a, b, AX),
                    mesh=mesh, in_specs=(P(AX), P()), out_specs=P())
                args = (xg, wg)
            else:
                fn = shard_map(
                    lambda a: compression._all_gather_int4(a, AX),
                    mesh=mesh, in_specs=(P(AX),), out_specs=P())
                args = (flat,)
            return jax.jit(fn).lower(*args).as_text()

        fused_bytes = asharding.static_comm_bytes(lowered())
        KREG.force_interpret(False, ["fused_cc"])
        unfused_bytes = asharding.static_comm_bytes(lowered())
        assert fused_bytes == unfused_bytes > 0


# ---------------------------------------------------------------------------
# telemetry + tooling satellites
# ---------------------------------------------------------------------------

class TestDispatchCounterTelemetry:
    def test_flat_dispatch_counter_and_report_fold(self):
        """Satellite 1: every dispatch bumps the flat
        ``kernels/dispatch/<name>_<path>`` counter, and
        telemetry_report folds the counters into the kernels table
        even with no dispatch events in the stream."""
        from apex_tpu.telemetry.registry import (
            MetricsRegistry,
            use_registry,
        )

        reg = MetricsRegistry(enabled=True)
        with use_registry(reg):
            KREG.dispatch("fused_cc", "interpret")
            KREG.dispatch("fused_cc", "interpret")
            KREG.dispatch("fused_cc", "oracle")
        snap = reg.snapshot()["counters"]
        assert snap["kernels/dispatch/fused_cc_interpret"] == 2
        assert snap["kernels/dispatch/fused_cc_oracle"] == 1

        import telemetry_report

        rep = telemetry_report.aggregate(
            [(0, {"kind": "summary", "counters": snap})])
        k = rep["kernels"]["fused_cc"]
        assert k["interpret"] == 2 and k["oracle"] == 1
        assert k["pallas"] == 0


class TestBenchTooling:
    def test_trend_band_and_timing_field_gate(self):
        import bench_trend

        assert bench_trend.band_for("fused_cc_speedup_geomean") == 0.40
        prev = {"n": 1, "parsed": {
            "metric": "fused_cc_speedup_geomean", "value": 1.0,
            "backend": "cpu-mesh", "fused_cc_verify_fused_ms": 1.0}}
        cur = {"n": 2, "parsed": {
            "metric": "fused_cc_speedup_geomean", "value": 1.0,
            "backend": "cpu-mesh", "fused_cc_verify_fused_ms": 1.6}}
        regs = bench_trend.compare_pair(prev, cur, 0.40)
        assert [r["field"] for r in regs] \
            == ["fused_cc_verify_fused_ms"]

    def test_schema_round21_contract(self):
        import bench_schema_check as bsc

        base = {"metric": "fused_cc_speedup_geomean", "value": 1.0,
                "unit": "x", "vs_baseline": 1.0, "tflops_per_sec": 0.0,
                "mfu": 0.0, "backend": "cpu-mesh",
                "measured_comm_bytes_per_step": None,
                "model_flops_per_step_xla": None,
                "comm_bytes_per_step": 100, "compile_count": None,
                "lint_violations": None,
                "static_comm_bytes_per_step": None,
                "peak_hbm_bytes": None, "hbm_headroom_pct": None,
                "live_buffer_bytes": None}
        full = dict(base)
        for f in bsc.FUSED_CC_REQUIRED_FIELDS:
            full[f] = 1.0
        assert bsc.check_metric_line(full, round_n=21, errors=[]) == []
        missing = bsc.check_metric_line(base, round_n=21, errors=[])
        assert any("fused_cc line missing" in e for e in missing)
        early = bsc.check_metric_line(full, round_n=20, errors=[])
        assert any("only defined from round 21" in e for e in early)

    def test_bench_specs_carry_fused_cc(self):
        import bench

        assert "fused_cc" in bench.BENCH_SPECS
