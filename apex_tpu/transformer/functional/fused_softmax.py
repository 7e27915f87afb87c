"""Fused scale + mask + softmax.

Parity: reference apex/transformer/functional/fused_softmax.py —
``FusedScaleMaskSoftmax`` (164-274) dispatching between
``scaled_upper_triang_masked_softmax_cuda`` (causal),
``scaled_masked_softmax_cuda``, ``scaled_softmax_cuda`` and a torch
fallback, with kernel-availability heuristics (222-246: 16 < sk <= 16384,
divisibility by 4 / batch-per-block), plus ``GenericFusedScaleMaskSoftmax``
(276).

TPU design: scale+mask+softmax is a pure VPU chain that XLA fuses into one
loop; the pure-jnp forms below are both the default lowering and the
parity oracle for the hand-written Pallas kernels in
:mod:`apex_tpu.kernels.softmax` (fused fwd + one-pass custom-VJP bwd,
causal mask derived in-kernel). Dispatch rides the kernel registry's
``softmax`` gate (:mod:`apex_tpu.kernels.registry`): gate off — the
default everywhere but TPU — reproduces today's jnp path bit-identically
*including autodiff gradients*; gate on routes through the kernels. The
availability heuristic is kept (``is_kernel_available``) for API parity
and returns True under the same shape conditions so callers exercising
the reference's dispatch logic behave identically. Numerics:
subtract-max in fp32, optionally compute in bf16 input dtype
(``attn_mask_type`` semantics preserved).
"""

import jax.numpy as jnp

from apex_tpu.kernels import softmax as _kernels
from apex_tpu.transformer.enums import AttnMaskType


def scaled_upper_triang_masked_softmax(x, scale):
    """Causal-masked scaled softmax over [b, sq, sk] or [b, np, sq, sk]
    (reference scaled_upper_triang_masked_softmax_cuda)."""
    if _kernels.usable(scale, fits=x.ndim == 3):
        return _kernels.scaled_upper_triang_masked_softmax(x, float(scale))
    xf = x.astype(jnp.float32) * scale
    sq, sk = x.shape[-2], x.shape[-1]
    causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
    xf = jnp.where(causal, xf, -10000.0)
    xf = xf - jnp.max(xf, axis=-1, keepdims=True)
    e = jnp.exp(xf)
    e = jnp.where(causal, e, 0.0)
    return (e / jnp.sum(e, axis=-1, keepdims=True)).astype(x.dtype)


def scaled_masked_softmax(x, mask, scale):
    """Arbitrary-mask scaled softmax; mask is 1/True where masked OUT
    (reference scaled_masked_softmax_cuda)."""
    if mask is None:
        return scaled_softmax(x, scale)
    if _kernels.usable(scale):
        maskf = jnp.broadcast_to(mask.astype(bool), x.shape) \
            .astype(jnp.float32)
        return _kernels.scaled_masked_softmax(x, maskf, float(scale))
    xf = x.astype(jnp.float32) * scale
    xf = jnp.where(mask.astype(bool), -10000.0, xf)
    xf = xf - jnp.max(xf, axis=-1, keepdims=True)
    e = jnp.exp(xf)
    e = jnp.where(mask.astype(bool), 0.0, e)
    return (e / jnp.sum(e, axis=-1, keepdims=True)).astype(x.dtype)


def scaled_softmax(x, scale):
    """No-mask scaled softmax (reference scaled_softmax_cuda)."""
    if _kernels.usable(scale):
        return _kernels.scaled_softmax(x, float(scale))
    xf = x.astype(jnp.float32) * scale
    xf = xf - jnp.max(xf, axis=-1, keepdims=True)
    e = jnp.exp(xf)
    return (e / jnp.sum(e, axis=-1, keepdims=True)).astype(x.dtype)


class FusedScaleMaskSoftmax:
    """Dispatching softmax front-end (reference fused_softmax.py:164-274).

    Args mirror the reference: input_in_fp16/bf16, attn_mask_type,
    scaled_masked_softmax_fusion, mask_func, softmax_in_fp32, scale.
    """

    def __init__(self, input_in_fp16, input_in_bf16, attn_mask_type,
                 scaled_masked_softmax_fusion, mask_func, softmax_in_fp32,
                 scale):
        self.input_in_fp16 = input_in_fp16
        self.input_in_bf16 = input_in_bf16
        assert not (input_in_fp16 and input_in_bf16), (
            "both fp16 and bf16 flags cannot be active at the same time.")
        self.input_in_float16 = input_in_fp16 or input_in_bf16
        self.attn_mask_type = attn_mask_type
        self.scaled_masked_softmax_fusion = scaled_masked_softmax_fusion
        self.mask_func = mask_func
        self.softmax_in_fp32 = softmax_in_fp32
        self.scale = scale
        assert self.scale is None or softmax_in_fp32, (
            "softmax should be in fp32 when scaled")

    def __call__(self, input, mask):
        assert input.ndim == 4  # [b, np, sq, sk]
        if self.is_kernel_available(mask, *input.shape):
            return self.forward_fused_softmax(input, mask)
        return self.forward_torch_softmax(input, mask)

    def is_kernel_available(self, mask, b, np_, sq, sk):
        """Same availability heuristic as the reference
        (fused_softmax.py:222-246); on TPU the fused path is always
        numerically available, but the predicate is preserved so dispatch
        behavior matches."""
        attn_batches = b * np_
        if (self.scaled_masked_softmax_fusion
                and self.input_in_float16
                and 16 < sk <= 16384
                and sq % 4 == 0
                and sk % 4 == 0
                and attn_batches % 4 == 0):
            if 0 <= sk <= 16384:
                batch_per_block = self.get_batch_per_block(sq, sk, b, np_)
                if self.attn_mask_type == AttnMaskType.causal:
                    if attn_batches % batch_per_block == 0:
                        return True
                else:
                    if sq % batch_per_block == 0:
                        return True
        return False

    def forward_fused_softmax(self, input, mask):
        scale = self.scale if self.scale is not None else 1.0
        if self.attn_mask_type == AttnMaskType.causal:
            b, np_, sq, sk = input.shape
            assert sq == sk, "causal mask is only for self attention"
            out = scaled_upper_triang_masked_softmax(
                input.reshape(-1, sq, sk), scale)
            return out.reshape(b, np_, sq, sk)
        if mask is not None:
            return scaled_masked_softmax(input, mask, scale)
        return scaled_softmax(input, scale)

    def forward_torch_softmax(self, input, mask):
        """Unfused fallback (reference fused_softmax.py:248-268)."""
        orig_dtype = input.dtype
        if self.input_in_float16 and self.softmax_in_fp32:
            input = input.astype(jnp.float32)
        if self.scale is not None:
            input = input * self.scale
        mask_output = self.mask_func(input, mask) if mask is not None else input
        probs = jnp.exp(mask_output - jnp.max(mask_output, axis=-1, keepdims=True))
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        if self.input_in_float16 and self.softmax_in_fp32:
            probs = probs.astype(orig_dtype)
        return probs

    @staticmethod
    def get_batch_per_block(sq, sk, b, np_):
        """Mirror of scaled_masked_softmax_cuda.get_batch_per_block
        (reference fused_softmax.py:271-274): pow2 batching heuristic."""
        pow2 = 1 << (sk - 1).bit_length()
        warp_size = pow2 if pow2 < 32 else 32
        batches_per_warp = 2 if pow2 <= 128 else 1
        warps_per_block = 4 * 32 // warp_size
        return warps_per_block * batches_per_warp


class GenericFusedScaleMaskSoftmax(FusedScaleMaskSoftmax):
    """Shape-generic variant (reference fused_softmax.py:276): no shape
    heuristics, always fused."""

    def __init__(self, input_in_fp16, input_in_bf16, mask_func,
                 softmax_in_fp32, scale):
        super().__init__(input_in_fp16, input_in_bf16, AttnMaskType.padding,
                         True, mask_func, softmax_in_fp32, scale)

    def is_kernel_available(self, mask, b, np_, sq, sk):
        return True
