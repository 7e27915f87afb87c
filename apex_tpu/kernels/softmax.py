"""Fused scale + mask + softmax Pallas kernels (forward + backward).

Parity: reference csrc/megatron_fused_kernels —
``scaled_masked_softmax_cuda``, ``scaled_upper_triang_masked_softmax_cuda``
and ``scaled_softmax_cuda``, each a fused fwd kernel plus a bwd kernel
computing ``dx = scale * y * (dy - sum(dy * y))`` from the stashed
probabilities. The jnp entry points in
:mod:`apex_tpu.transformer.functional.fused_softmax` stay the oracle
(and the ``APEX_TPU_KERNELS=0`` path, bit-identical to today including
autodiff gradients); when the ``softmax`` gate is enabled they dispatch
to the ``custom_vjp`` wrappers below, whose backward runs the one-pass
fused formula instead of re-deriving the chain through exp/sum.

Kernel design: scores flatten to ``[rows, sk]`` and grid over row
blocks with the full key dim resident in VMEM. The forward mirrors the
oracle's fp32 operation order exactly (scale, mask to -10000, subtract
row max, exp, re-mask, normalize), so interpret-mode forward parity is
bit-exact; the backward's fused formula is algebraically equal to the
autodiff chain but associates differently — gradients match within
~1e-6 relative in fp32 (the documented bound; see docs/kernels.md).
The causal variant computes its upper-triangular mask *in-kernel* from
the row/key iota (no [sq, sk] mask tensor is ever materialized — the
point of the fused kernel).

Masks follow the reference convention: 1/True where masked OUT.
"""

import functools

import jax
import jax.numpy as jnp

from apex_tpu.kernels.registry import kernel_gate

GATE = kernel_gate("softmax")

_MASK_VALUE = -10000.0


def _row_block(n_rows: int, sk: int) -> int:
    budget = 4 * 1024 * 1024
    rows = max(8, budget // max(1, 4 * sk * 4))
    rows = min(rows, 512)
    rows = max(8, (rows // 8) * 8)
    return rows


def usable(scale, fits: bool = True) -> bool:
    """Does this call take the kernel (and count the call)? The kernel
    needs a static scale (it is baked in); a traced scale takes the
    oracle, as does a shape the caller says does not fit."""
    return GATE.path(
        fits=fits and isinstance(scale, (int, float))) != "oracle"


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, y_ref, *, scale):
    xf = x_ref[...].astype(jnp.float32) * scale
    xf = xf - jnp.max(xf, axis=-1, keepdims=True)
    e = jnp.exp(xf)
    y_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(y_ref.dtype)


def _masked_fwd_kernel(x_ref, m_ref, y_ref, *, scale):
    xf = x_ref[...].astype(jnp.float32) * scale
    m = m_ref[...] != 0
    xf = jnp.where(m, _MASK_VALUE, xf)
    xf = xf - jnp.max(xf, axis=-1, keepdims=True)
    e = jnp.exp(xf)
    e = jnp.where(m, 0.0, e)
    y_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(y_ref.dtype)


def _causal_fwd_kernel(x_ref, y_ref, *, scale, sq, sk, rb):
    from jax.experimental import pallas as pl

    r0 = pl.program_id(0) * rb
    rows = jax.lax.broadcasted_iota(jnp.int32, (rb, sk), 0) + r0
    i = rows % sq
    j = jax.lax.broadcasted_iota(jnp.int32, (rb, sk), 1)
    causal = j <= i + (sk - sq)
    xf = x_ref[...].astype(jnp.float32) * scale
    xf = jnp.where(causal, xf, _MASK_VALUE)
    xf = xf - jnp.max(xf, axis=-1, keepdims=True)
    e = jnp.exp(xf)
    e = jnp.where(causal, e, 0.0)
    y_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(y_ref.dtype)


def _bwd_kernel(y_ref, dy_ref, dx_ref, *, scale):
    y = y_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    t = jnp.sum(dy * y, axis=-1, keepdims=True)
    dx_ref[...] = (scale * y * (dy - t)).astype(dx_ref.dtype)


def _rowwise_call(name, kernel, x2d, *extra, out_dtype):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, sk = x2d.shape
    rb = _row_block(n, sk)
    spec = pl.BlockSpec((rb, sk), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, rb),),
        in_specs=[spec] * (1 + len(extra)),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, sk), out_dtype),
        interpret=GATE.interpret,
        name=name,
    )(x2d, *extra)


def _bwd_rows(y2d, dy2d, scale, out_dtype):
    return _rowwise_call("softmax_bwd",
                         functools.partial(_bwd_kernel, scale=scale),
                         y2d, dy2d, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrappers (consumed by transformer.functional.fused_softmax)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def scaled_softmax(x, scale):
    """No-mask scaled softmax, fused fwd+bwd (any leading dims, softmax
    over the last)."""
    y, _ = _scaled_fwd(x, scale)
    return y


def _scaled_fwd(x, scale):
    x2d = x.reshape(-1, x.shape[-1])
    y = _rowwise_call("softmax_fwd",
                      functools.partial(_fwd_kernel, scale=scale),
                      x2d, out_dtype=x.dtype)
    y = y.reshape(x.shape)
    return y, y


def _scaled_bwd(scale, y, dy):
    sk = y.shape[-1]
    dx = _bwd_rows(y.reshape(-1, sk), dy.astype(y.dtype).reshape(-1, sk),
                   scale, y.dtype)
    return (dx.reshape(y.shape),)


scaled_softmax.defvjp(_scaled_fwd, _scaled_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def scaled_masked_softmax(x, maskf, scale):
    """Arbitrary-mask scaled softmax; ``maskf`` is an f32 0/1 tensor
    already broadcast to ``x.shape`` (1 where masked OUT — the wrapper
    in fused_softmax does the cast/broadcast)."""
    y, _ = _masked_fwd(x, maskf, scale)
    return y


def _masked_fwd(x, maskf, scale):
    sk = x.shape[-1]
    y = _rowwise_call(
        "softmax_fwd", functools.partial(_masked_fwd_kernel, scale=scale),
        x.reshape(-1, sk), maskf.reshape(-1, sk), out_dtype=x.dtype)
    y = y.reshape(x.shape)
    return y, (y, maskf)


def _masked_bwd(scale, res, dy):
    y, maskf = res
    sk = y.shape[-1]
    dx = _bwd_rows(y.reshape(-1, sk), dy.astype(y.dtype).reshape(-1, sk),
                   scale, y.dtype)
    # masked positions have y == 0, so dx is already 0 there; the mask
    # itself gets a (dead) zero cotangent
    return dx.reshape(y.shape), jnp.zeros_like(maskf)


scaled_masked_softmax.defvjp(_masked_fwd, _masked_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def scaled_upper_triang_masked_softmax(x, scale):
    """Causal-masked scaled softmax over ``[b, sq, sk]`` — the mask is
    derived in-kernel from the row index, never materialized."""
    y, _ = _causal_fwd(x, scale)
    return y


def _causal_fwd(x, scale):
    b, sq, sk = x.shape
    x2d = x.reshape(b * sq, sk)
    rb = _row_block(b * sq, sk)
    y = _rowwise_call(
        "softmax_fwd",
        functools.partial(_causal_fwd_kernel, scale=scale, sq=sq, sk=sk,
                          rb=rb),
        x2d, out_dtype=x.dtype)
    y = y.reshape(x.shape)
    return y, y


def _causal_bwd(scale, y, dy):
    b, sq, sk = y.shape
    dx = _bwd_rows(y.reshape(-1, sk), dy.astype(y.dtype).reshape(-1, sk),
                   scale, y.dtype)
    return (dx.reshape(y.shape),)


scaled_upper_triang_masked_softmax.defvjp(_causal_fwd, _causal_bwd)
