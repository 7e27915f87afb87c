"""FusedLayerNorm/FusedRMSNorm numerics + gradients vs references.

Mirrors reference tests/L0/run_fused_layer_norm/test_fused_layer_norm.py
(vs torch.nn.LayerNorm / manual RMS across shapes and dtypes).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from apex_tpu.normalization import (
    FusedLayerNorm,
    FusedRMSNorm,
    MixedFusedLayerNorm,
    MixedFusedRMSNorm,
    fused_layer_norm,
    fused_layer_norm_affine,
    fused_rms_norm,
    fused_rms_norm_affine,
    manual_rms_norm,
)

SHAPES = [(3, 16), (2, 5, 32), (4, 128)]


class TestLayerNormNumerics:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_vs_torch(self, rng, shape):
        x = rng.randn(*shape).astype(np.float32)
        h = shape[-1]
        w = rng.randn(h).astype(np.float32)
        b = rng.randn(h).astype(np.float32)
        ours = fused_layer_norm_affine(jnp.asarray(x), jnp.asarray(w),
                                       jnp.asarray(b), h, eps=1e-5)
        theirs = torch.nn.functional.layer_norm(
            torch.tensor(x), (h,), torch.tensor(w), torch.tensor(b), 1e-5)
        np.testing.assert_allclose(np.asarray(ours), theirs.numpy(),
                                   atol=1e-5)

    @pytest.mark.parametrize("shape", SHAPES)
    def test_no_affine(self, rng, shape):
        x = rng.randn(*shape).astype(np.float32)
        h = shape[-1]
        ours = fused_layer_norm(jnp.asarray(x), h, eps=1e-5)
        theirs = torch.nn.functional.layer_norm(torch.tensor(x), (h,))
        np.testing.assert_allclose(np.asarray(ours), theirs.numpy(), atol=1e-5)

    def test_gradients_vs_torch(self, rng):
        x = rng.randn(4, 32).astype(np.float32)
        w = rng.randn(32).astype(np.float32)
        b = rng.randn(32).astype(np.float32)

        def f(x_, w_, b_):
            return jnp.sum(fused_layer_norm_affine(x_, w_, b_, 32) ** 2)

        dx, dw, db = jax.grad(f, argnums=(0, 1, 2))(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))

        tx = torch.tensor(x, requires_grad=True)
        tw = torch.tensor(w, requires_grad=True)
        tb = torch.tensor(b, requires_grad=True)
        out = torch.nn.functional.layer_norm(tx, (32,), tw, tb, 1e-5)
        (out ** 2).sum().backward()
        np.testing.assert_allclose(np.asarray(dx), tx.grad.numpy(),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(dw), tw.grad.numpy(),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(db), tb.grad.numpy(),
                                   rtol=1e-4, atol=1e-4)

    def test_multi_dim_normalized_shape(self, rng):
        x = rng.randn(2, 3, 4, 5).astype(np.float32)
        w = rng.randn(4, 5).astype(np.float32)
        b = rng.randn(4, 5).astype(np.float32)
        ours = fused_layer_norm_affine(jnp.asarray(x), jnp.asarray(w),
                                       jnp.asarray(b), (4, 5), eps=1e-5)
        theirs = torch.nn.functional.layer_norm(
            torch.tensor(x), (4, 5), torch.tensor(w), torch.tensor(b), 1e-5)
        np.testing.assert_allclose(np.asarray(ours), theirs.numpy(), atol=1e-5)


class TestRMSNormNumerics:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_vs_manual(self, rng, shape):
        x = rng.randn(*shape).astype(np.float32)
        h = shape[-1]
        w = rng.randn(h).astype(np.float32)
        ours = fused_rms_norm_affine(jnp.asarray(x), jnp.asarray(w), h, eps=1e-5)
        ref = manual_rms_norm(jnp.asarray(x), (h,), jnp.asarray(w), 1e-5)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=1e-5)

    def test_gradients(self, rng):
        x = rng.randn(4, 32).astype(np.float32)
        w = rng.randn(32).astype(np.float32)

        def f_fused(x_, w_):
            return jnp.sum(fused_rms_norm_affine(x_, w_, 32, eps=1e-5) ** 3)

        def f_ref(x_, w_):
            return jnp.sum(manual_rms_norm(x_, (32,), w_, 1e-5) ** 3)

        g1 = jax.grad(f_fused, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
        g2 = jax.grad(f_ref, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_no_affine(self, rng):
        x = rng.randn(4, 16).astype(np.float32)
        ours = fused_rms_norm(jnp.asarray(x), 16, eps=1e-5)
        ref = manual_rms_norm(jnp.asarray(x), (16,), None, 1e-5)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=1e-5)


class TestModules:
    def test_fused_layer_norm_module(self, rng):
        m = FusedLayerNorm(normalized_shape=32)
        x = jnp.asarray(rng.randn(4, 32).astype(np.float32))
        params = m.init(jax.random.PRNGKey(0), x)
        y = m.apply(params, x)
        assert y.shape == x.shape

    def test_mixed_dtype_output_follows_params(self, rng):
        m = MixedFusedLayerNorm(normalized_shape=32, param_dtype=jnp.float32)
        x = jnp.asarray(rng.randn(4, 32).astype(np.float32)).astype(jnp.bfloat16)
        params = m.init(jax.random.PRNGKey(0), x)
        y = m.apply(params, x)
        assert y.dtype == jnp.float32  # follows param dtype

        r = MixedFusedRMSNorm(normalized_shape=32, param_dtype=jnp.bfloat16)
        params = r.init(jax.random.PRNGKey(0), x)
        y = r.apply(params, x)
        assert y.dtype == jnp.bfloat16

    def test_rms_module(self, rng):
        m = FusedRMSNorm(normalized_shape=16, elementwise_affine=False)
        x = jnp.asarray(rng.randn(4, 16).astype(np.float32))
        params = m.init(jax.random.PRNGKey(0), x)
        y = m.apply(params, x)
        ref = manual_rms_norm(x, (16,), None, 1e-5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_bf16_input(self, rng):
        x = jnp.asarray(rng.randn(8, 64).astype(np.float32)).astype(jnp.bfloat16)
        y = fused_layer_norm(x, 64)
        assert y.dtype == jnp.bfloat16
