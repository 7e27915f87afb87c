"""Contrib tier tests: flash attention (Pallas interpret mode), xentropy,
clip_grad, focal loss, index_mul_2d.

Mirrors reference apex/contrib/test/ per-extension numerics tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

import apex_tpu.contrib.fmha as fmha_mod
from apex_tpu.contrib.clip_grad import clip_grad_norm_
from apex_tpu.contrib.fmha import _attention_reference, flash_attention
from apex_tpu.contrib.focal_loss import focal_loss
from apex_tpu.contrib.index_mul_2d import index_mul_2d
from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss


class TestFlashAttention:
    @pytest.fixture(autouse=True)
    def _interpret_pallas(self, monkeypatch):
        """Run the Pallas kernel in interpreter mode on CPU so the TPU code
        path is exercised by the CPU test suite."""
        monkeypatch.setattr(fmha_mod.GATE, "interpret", True)

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, rng, causal):
        b, n, s, d = 1, 2, 128, 64
        q = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        out = flash_attention(q, k, v, causal, None, 64, 64)
        ref = _attention_reference(q, k, v, 1.0 / np.sqrt(d), causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_gradients_flow(self, rng):
        b, n, s, d = 1, 1, 128, 64
        q = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))

        def f(q_, k_, v_):
            return jnp.sum(flash_attention(q_, k_, v_, True, None, 64, 64))

        def f_ref(q_, k_, v_):
            return jnp.sum(_attention_reference(q_, k_, v_, 1.0 / np.sqrt(d),
                                                True))

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("window", [1, 37, 64, 100, 256])
    def test_sliding_window_matches_reference(self, rng, window):
        """Windowed flash (block-skip band) vs the reference band mask:
        windows below/at/above the block size and spanning several
        blocks, forward and all three gradients."""
        b, n, s, d = 1, 2, 256, 64
        q = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))

        out = flash_attention(q, k, v, True, None, 64, 64, window)
        ref = _attention_reference(q, k, v, 1.0 / np.sqrt(d), True,
                                   window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

        def f(q_, k_, v_):
            return jnp.sum(
                flash_attention(q_, k_, v_, True, None, 64, 64,
                                window) ** 2)

        def f_ref(q_, k_, v_):
            return jnp.sum(_attention_reference(
                q_, k_, v_, 1.0 / np.sqrt(d), True, window) ** 2)

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    def test_alibi_bias_matches_reference(self, rng):
        """In-kernel alibi bias (key-position form) vs the reference
        band-free einsum path, forward and all three gradients; slopes
        cotangent is zero by construction."""
        b, n, s, d = 1, 3, 128, 64
        q = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        slopes = jnp.asarray([0.5, 0.25, 0.0625], jnp.float32)

        out = flash_attention(q, k, v, True, None, 64, 64, None, slopes)
        ref = _attention_reference(q, k, v, 1.0 / np.sqrt(d), True,
                                   None, slopes)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

        def f(q_, k_, v_):
            return jnp.sum(flash_attention(
                q_, k_, v_, True, None, 64, 64, None, slopes) ** 2)

        def f_ref(q_, k_, v_):
            return jnp.sum(_attention_reference(
                q_, k_, v_, 1.0 / np.sqrt(d), True, None, slopes) ** 2)

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    def test_window_requires_causal(self):
        q = jnp.zeros((1, 1, 128, 64), jnp.float32)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, q, q, False, None, 64, 64, 37)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("bq,bk", [(64, 64), (64, 128), (128, 64)])
    def test_streamed_backward_multiblock(self, rng, causal, bq, bk):
        """The Pallas dq/dkv kernels stream multiple blocks here (s=256)
        including unequal block_q/block_k — covers accumulator
        init/finish and both causal clamp derivations."""
        b, n, s, d = 1, 2, 256, 64
        q = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, n, s, d).astype(np.float32))

        def f(q_, k_, v_):
            return jnp.sum(
                flash_attention(q_, k_, v_, causal, None, bq, bk) ** 2)

        def f_ref(q_, k_, v_):
            return jnp.sum(_attention_reference(
                q_, k_, v_, 1.0 / np.sqrt(d), causal) ** 2)

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)


class TestXentropy:
    def test_matches_torch(self, rng):
        logits = rng.randn(6, 11).astype(np.float32)
        labels = rng.randint(1, 11, size=(6,))
        ours = softmax_cross_entropy_loss(
            jnp.asarray(logits), jnp.asarray(labels), padding_idx=None)
        theirs = torch.nn.functional.cross_entropy(
            torch.tensor(logits), torch.tensor(labels), reduction="none")
        np.testing.assert_allclose(np.asarray(ours), theirs.numpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_label_smoothing_matches_torch(self, rng):
        logits = rng.randn(6, 11).astype(np.float32)
        labels = rng.randint(1, 11, size=(6,))
        ours = softmax_cross_entropy_loss(
            jnp.asarray(logits), jnp.asarray(labels), smoothing=0.1,
            padding_idx=None)
        theirs = torch.nn.functional.cross_entropy(
            torch.tensor(logits), torch.tensor(labels), reduction="none",
            label_smoothing=0.1)
        np.testing.assert_allclose(np.asarray(ours), theirs.numpy(),
                                   rtol=1e-4, atol=1e-5)

    def test_padding_idx_zeroes_loss(self, rng):
        logits = rng.randn(4, 7).astype(np.float32)
        labels = np.array([0, 1, 0, 2])
        ours = softmax_cross_entropy_loss(jnp.asarray(logits),
                                          jnp.asarray(labels), padding_idx=0)
        assert float(ours[0]) == 0.0 and float(ours[2]) == 0.0
        assert float(ours[1]) > 0.0

    def test_half_to_float_dtype(self, rng):
        logits = jnp.asarray(rng.randn(4, 7).astype(np.float32)).astype(jnp.bfloat16)
        labels = jnp.asarray(rng.randint(0, 7, size=(4,)))
        assert softmax_cross_entropy_loss(logits, labels,
                                          half_to_float=True).dtype == jnp.float32
        assert softmax_cross_entropy_loss(logits, labels,
                                          half_to_float=False).dtype == jnp.bfloat16


class TestClipGrad:
    def test_matches_torch_clip(self, rng):
        grads = {"a": jnp.asarray(rng.randn(5, 3).astype(np.float32)),
                 "b": jnp.asarray(rng.randn(7).astype(np.float32))}
        clipped, norm = clip_grad_norm_(grads, max_norm=1.0)
        tgrads = [torch.tensor(np.asarray(grads["a"]), requires_grad=True),
                  torch.tensor(np.asarray(grads["b"]), requires_grad=True)]
        for t in tgrads:
            t.grad = t.detach().clone()
        tnorm = torch.nn.utils.clip_grad_norm_(tgrads, 1.0)
        np.testing.assert_allclose(float(norm), float(tnorm), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   tgrads[0].grad.numpy(), rtol=1e-4,
                                   atol=1e-6)

    def test_no_clip_below_max(self, rng):
        grads = {"a": jnp.asarray((rng.randn(4) * 0.01).astype(np.float32))}
        clipped, _ = clip_grad_norm_(grads, max_norm=100.0)
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   np.asarray(grads["a"]), rtol=1e-6)


class TestFocalLoss:
    def test_reduces_easy_example_weight(self, rng):
        logits = jnp.asarray([[5.0, -5.0], [0.1, -0.1]])
        targets = jnp.asarray([0, 0])
        fl = focal_loss(logits, targets, jnp.asarray(2.0), 2, gamma=2.0)
        # focal loss is finite and positive
        assert np.isfinite(float(fl)) and float(fl) > 0

    def test_ignore_labels(self):
        logits = jnp.zeros((2, 3))
        targets = jnp.asarray([-2, -2])  # ignored
        fl = focal_loss(logits, targets, jnp.asarray(1.0), 3)
        assert float(fl) == 0.0


class TestIndexMul2d:
    def test_matches_reference(self, rng):
        in1 = jnp.asarray(rng.randn(10, 4).astype(np.float32))
        in2 = jnp.asarray(rng.randn(6, 4).astype(np.float32))
        idx = jnp.asarray(rng.randint(0, 10, size=(6,)))
        out = index_mul_2d(in1, in2, idx)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(in1)[np.asarray(idx)] * np.asarray(in2),
            rtol=1e-6)
