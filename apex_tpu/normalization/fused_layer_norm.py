"""FusedLayerNorm / FusedRMSNorm modules and functional entry points.

Parity: reference apex/normalization/fused_layer_norm.py —
``FusedLayerNorm`` (204), ``FusedRMSNorm`` (300), ``MixedFusedLayerNorm``
(398), ``MixedFusedRMSNorm`` (420), functional wrappers
``fused_layer_norm[_affine]`` / ``fused_rms_norm[_affine]`` (168-201) and
``manual_rms_norm`` (16-29).

TPU design: modules are flax.linen Modules; the math lives in
:mod:`apex_tpu.ops.layer_norm`: the jnp forward and backward under a
``custom_vjp``, which XLA fuses into their neighbours (no hand-written
kernel).
"Mixed" variants compute in fp32 but return the *parameter* dtype, matching
the reference's mixed-dtype kernels (layer_norm_cuda.cpp
``forward_affine_mixed_dtypes``).
"""

from typing import Sequence, Union

import flax.linen as nn
import jax.numpy as jnp

from apex_tpu.ops import layer_norm as _ln_ops

Shape = Union[int, Sequence[int]]


def _norm_shape(normalized_shape: Shape):
    if isinstance(normalized_shape, int):
        return (normalized_shape,)
    return tuple(normalized_shape)


# -- functional API (reference fused_layer_norm.py:168-201) -----------------

def fused_layer_norm_affine(input, weight, bias, normalized_shape, eps=1e-6):
    return _ln_ops.layer_norm(input, normalized_shape, weight, bias, eps)


def fused_layer_norm(input, normalized_shape, eps=1e-6):
    return _ln_ops.layer_norm(input, normalized_shape, None, None, eps)


def fused_rms_norm_affine(input, weight, normalized_shape, eps=1e-6):
    return _ln_ops.rms_norm(input, normalized_shape, weight, eps)


def fused_rms_norm(input, normalized_shape, eps=1e-6):
    return _ln_ops.rms_norm(input, normalized_shape, None, eps)


def mixed_dtype_fused_layer_norm_affine(input, weight, bias, normalized_shape, eps=1e-6):
    return _ln_ops.layer_norm(input, normalized_shape, weight, bias, eps,
                              out_dtype=weight.dtype)


def mixed_dtype_fused_rms_norm_affine(input, weight, normalized_shape, eps=1e-6):
    return _ln_ops.rms_norm(input, normalized_shape, weight, eps,
                            out_dtype=weight.dtype)


def manual_rms_norm(input, normalized_shape, weight, eps):
    """Pure-jnp RMSNorm reference (reference fused_layer_norm.py:16-29)."""
    dims = tuple(range(-len(_norm_shape(normalized_shape)), 0))
    variance = jnp.mean(jnp.square(input.astype(jnp.float32)), axis=dims, keepdims=True)
    out = input * jnp.reciprocal(jnp.sqrt(variance + eps))
    if weight is None:
        return out.astype(input.dtype)
    if weight.dtype in [jnp.float16, jnp.bfloat16]:
        out = out.astype(weight.dtype)
    return (weight * out).astype(weight.dtype)


# -- module API -------------------------------------------------------------

class FusedLayerNorm(nn.Module):
    """LayerNorm module (reference FusedLayerNorm, fused_layer_norm.py:204).

    Usage: ``FusedLayerNorm(normalized_shape=h)`` then ``.apply({'params': p}, x)``.
    """

    normalized_shape: Shape
    eps: float = 1e-5
    elementwise_affine: bool = True
    param_dtype: jnp.dtype = jnp.float32
    memory_efficient: bool = False  # accepted for parity; recompute is jax.checkpoint's job

    @nn.compact
    def __call__(self, x):
        shape = _norm_shape(self.normalized_shape)
        if self.elementwise_affine:
            weight = self.param("weight", nn.initializers.ones, shape, self.param_dtype)
            bias = self.param("bias", nn.initializers.zeros, shape, self.param_dtype)
            return _ln_ops.layer_norm(x, shape, weight, bias, self.eps)
        return _ln_ops.layer_norm(x, shape, None, None, self.eps)


class FusedRMSNorm(nn.Module):
    """RMSNorm module (reference FusedRMSNorm, fused_layer_norm.py:300)."""

    normalized_shape: Shape
    eps: float = 1e-5
    elementwise_affine: bool = True
    param_dtype: jnp.dtype = jnp.float32
    memory_efficient: bool = False

    @nn.compact
    def __call__(self, x):
        shape = _norm_shape(self.normalized_shape)
        if self.elementwise_affine:
            weight = self.param("weight", nn.initializers.ones, shape, self.param_dtype)
            return _ln_ops.rms_norm(x, shape, weight, self.eps)
        return _ln_ops.rms_norm(x, shape, None, self.eps)


class MixedFusedLayerNorm(FusedLayerNorm):
    """LayerNorm whose output dtype follows the parameter dtype
    (reference MixedFusedLayerNorm, fused_layer_norm.py:398)."""

    @nn.compact
    def __call__(self, x):
        shape = _norm_shape(self.normalized_shape)
        weight = self.param("weight", nn.initializers.ones, shape, self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, shape, self.param_dtype)
        return mixed_dtype_fused_layer_norm_affine(x, weight, bias, shape, self.eps)


class MixedFusedRMSNorm(FusedRMSNorm):
    """RMSNorm whose output dtype follows the parameter dtype
    (reference MixedFusedRMSNorm, fused_layer_norm.py:420)."""

    @nn.compact
    def __call__(self, x):
        shape = _norm_shape(self.normalized_shape)
        weight = self.param("weight", nn.initializers.ones, shape, self.param_dtype)
        return mixed_dtype_fused_rms_norm_affine(x, weight, shape, self.eps)
