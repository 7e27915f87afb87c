"""Fused LayerNorm / RMSNorm — the jnp formulation under a custom VJP.

Parity: reference csrc/layer_norm_cuda.cpp (442) + layer_norm_cuda_kernel.cu
(1,170) exporting ``forward[_affine]``, ``backward[_affine]``,
``rms_forward*``, ``rms_backward*`` — consumed by
apex/normalization/fused_layer_norm.py:32-165.

TPU design: the row statistics, the normalization and the affine are a
chain XLA fuses into its neighbours by itself, so this module is the
public entry points, the shape handling and a ``custom_vjp`` around the
jnp forward and backward (fp32 statistics; the backward recomputes them
from the stashed input instead of keeping them). There is no
hand-written kernel: the one there was lost on the chip (BERT-large,
hidden 1024: 14% of a step, the custom call being a fusion barrier with
layout copies behind it) and was taken out.
"""

import functools

import jax
import jax.numpy as jnp


def _ln_stats(x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return mean, var


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_fwd(x2d, weight, bias, eps):
    x = x2d.astype(jnp.float32)
    mean, var = _ln_stats(x)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x2d.dtype)


def _ln_bwd_dx(dy2d, x2d, weight, eps):
    dy = dy2d.astype(jnp.float32)
    x = x2d.astype(jnp.float32)
    mean, var = _ln_stats(x)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    wdy = dy * weight.astype(jnp.float32) if weight is not None else dy
    c1 = jnp.mean(wdy, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx = (wdy - c1 - xhat * c2) * rstd
    return dx.astype(x2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layer_norm_affine(x2d, weight, bias, eps, out_dtype):
    return _ln_fwd(x2d, weight, bias, eps).astype(out_dtype)


def _layer_norm_affine_fwd(x2d, weight, bias, eps, out_dtype):
    y = _ln_fwd(x2d, weight, bias, eps)
    return y.astype(out_dtype), (x2d, weight)


def _layer_norm_affine_bwd(eps, out_dtype, res, dy):
    x2d, weight = res
    dy2d = dy.astype(x2d.dtype)
    dx = _ln_bwd_dx(dy2d, x2d, weight, eps)
    if weight is not None:
        x = x2d.astype(jnp.float32)
        mean, var = _ln_stats(x)
        xhat = (x - mean) * jax.lax.rsqrt(var + eps)
        dyf = dy.astype(jnp.float32)
        dw = jnp.sum(dyf * xhat, axis=0).astype(weight.dtype)
        db = jnp.sum(dyf, axis=0).astype(weight.dtype)
    else:
        dw = None
        db = None
    return dx, dw, db


_layer_norm_affine.defvjp(_layer_norm_affine_fwd, _layer_norm_affine_bwd)


def layer_norm(x, normalized_shape, weight=None, bias=None, eps=1e-5,
               out_dtype=None):
    """Fused layer norm over the trailing ``normalized_shape`` dims.

    Entry-point parity: fused_layer_norm_cuda.forward[_affine]
    (reference apex/normalization/fused_layer_norm.py:43-77).
    """
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    normalized_shape = tuple(normalized_shape)
    h = 1
    for d in normalized_shape:
        h *= d
    orig_shape = x.shape
    x2d = x.reshape(-1, h)
    w = weight.reshape(h) if weight is not None else None
    b = bias.reshape(h) if bias is not None else None
    out_dtype = out_dtype or x.dtype
    y = _layer_norm_affine(x2d, w, b, float(eps), out_dtype)
    return y.reshape(orig_shape)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def _rms_fwd(x2d, weight, eps):
    x = x2d.astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x2d.dtype)


def _rms_bwd_dx(dy2d, x2d, weight, eps):
    dy = dy2d.astype(jnp.float32)
    x = x2d.astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    xhat = x * rstd
    wdy = dy * weight.astype(jnp.float32) if weight is not None else dy
    c = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx = (wdy - xhat * c) * rstd
    return dx.astype(x2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm_affine(x2d, weight, eps, out_dtype):
    return _rms_fwd(x2d, weight, eps).astype(out_dtype)


def _rms_norm_affine_fwd(x2d, weight, eps, out_dtype):
    y = _rms_fwd(x2d, weight, eps)
    return y.astype(out_dtype), (x2d, weight)


def _rms_norm_affine_bwd(eps, out_dtype, res, dy):
    x2d, weight = res
    dy2d = dy.astype(x2d.dtype)
    dx = _rms_bwd_dx(dy2d, x2d, weight, eps)
    if weight is not None:
        x = x2d.astype(jnp.float32)
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        xhat = x * jax.lax.rsqrt(ms + eps)
        dw = jnp.sum(dy.astype(jnp.float32) * xhat, axis=0).astype(weight.dtype)
    else:
        dw = None
    return dx, dw


_rms_norm_affine.defvjp(_rms_norm_affine_fwd, _rms_norm_affine_bwd)


def rms_norm(x, normalized_shape, weight=None, eps=1e-5, out_dtype=None):
    """Fused RMSNorm (entry-point parity: fused_layer_norm_cuda.rms_forward*,
    reference apex/normalization/fused_layer_norm.py:80-164)."""
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    normalized_shape = tuple(normalized_shape)
    h = 1
    for d in normalized_shape:
        h *= d
    orig_shape = x.shape
    x2d = x.reshape(-1, h)
    w = weight.reshape(h) if weight is not None else None
    out_dtype = out_dtype or x.dtype
    y = _rms_norm_affine(x2d, w, float(eps), out_dtype)
    return y.reshape(orig_shape)
