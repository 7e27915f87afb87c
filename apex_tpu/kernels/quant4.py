"""int4 dual-quantization kernels: quantize/dequantize + nibble packing.

EQuARX (arXiv 2506.17615) pushes block-scaled quantized collectives to
4 bits with *dual* (two-level) quantization: per-block scales are
themselves quantized against one per-bucket fp32 scale, so the wire
carries half-byte lanes + one byte per 256-lane block + a single fp32
— ~0.53 bytes/element at block 256 vs 1.03 for int8.

Wire format (``compress="int4"`` in parallel/compression.py; spec in
docs/kernels.md):

- values: symmetric int4 in [-7, 7] (−8 excluded, same symmetric-grid
  reasoning as the int8 path's ±127), quantized against the block's
  EFFECTIVE scale below
- level-1 scales: per-block ``sq = clip(round(absmax / gmax * 255),
  1, 255)`` stored uint8 (the floor at 1 keeps all-zero blocks exact
  instead of dividing by 0)
- level-2 scale: one fp32 ``gmax = max(absmax)`` per bucket
- effective block scale: ``sq * gmax / (255 * 7)``
- packing (the genuinely-int4 gather payload): SPLIT-HALF nibbles —
  ``packed[b, j] = (q[b, j] & 0xF) | (q[b, j + B/2] & 0xF) << 4`` —
  chosen over interleaved pairs because both halves are contiguous
  128-lane slices, which the TPU lane layout handles without a
  shuffle.

Replica agreement: the collective paths pmax the fp32 block absmaxes
(exactly like int8), then EVERY replica derives ``(sq, gmax)`` from the
shared absmaxes — a deterministic function — so all replicas quantize
against the same grid and an int32-partial psum is exact.

The jnp formulations are the Pallas kernels' parity oracles (identical
operation order — interpret-mode parity is bit-exact) and the fallback
off TPU. Gate: ``quant4``.
"""

import jax
import jax.numpy as jnp

from apex_tpu.kernels.registry import kernel_gate

GATE = kernel_gate("quant4")

QMAX4 = 7.0
_SCALE_QMAX = 255.0

# int8 tiles at 32 sublanes; one grid cell covers 32 blocks (the same
# cell the int8 compression kernels use)
_ROWS = 32


def int4_block_scales(absmax):
    """Two-level scales from (shared) per-block absmaxes:
    ``(sq uint8 [nb, 1], gmax fp32 scalar)``."""
    gmax = jnp.maximum(jnp.max(absmax), 1e-12)
    sq = jnp.clip(jnp.round(absmax / gmax * _SCALE_QMAX), 1.0,
                  _SCALE_QMAX).astype(jnp.uint8)
    return sq, gmax


def effective_scales(sq, gmax):
    """The dequantization grid the wire format implies: ``[nb, 1]``
    fp32."""
    return sq.astype(jnp.float32) * (gmax / (_SCALE_QMAX * QMAX4))


# ---------------------------------------------------------------------------
# jnp oracles
# ---------------------------------------------------------------------------

def _quantize_jnp(x2d, scales):
    return jnp.clip(jnp.round(x2d / scales), -QMAX4, QMAX4) \
        .astype(jnp.int8)


def _dequantize_jnp(q2d, scales):
    return q2d.astype(jnp.float32) * scales


def _pad_even_lanes(q2d):
    """A ragged tail block (lane count not a multiple of the pack
    width) pads ONE zero lane so the split-half nibble layout stays
    well-formed; ``unpack(..., n=)`` drops it on the way back."""
    if q2d.shape[1] % 2:
        q2d = jnp.pad(q2d, ((0, 0), (0, 1)))
    return q2d


def _pack_jnp(q2d):
    q2d = _pad_even_lanes(q2d)
    h = q2d.shape[1] // 2
    lo = q2d[:, :h].astype(jnp.int32) & 0xF
    hi = q2d[:, h:].astype(jnp.int32) & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def _unpack_jnp(p2d, n=None):
    p = p2d.astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8
    hi = (((p >> 4) & 0xF) ^ 8) - 8
    out = jnp.concatenate([lo, hi], axis=1).astype(jnp.int8)
    return out if n is None else out[:, :n]


# ---------------------------------------------------------------------------
# Pallas kernels (same bodies, ref-indexed)
# ---------------------------------------------------------------------------

def _quant_kernel(x_ref, s_ref, q_ref):
    q_ref[...] = jnp.clip(jnp.round(x_ref[...] / s_ref[...]),
                          -QMAX4, QMAX4).astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _pack_kernel(q_ref, p_ref):
    h = q_ref.shape[1] // 2
    lo = q_ref[:, :h].astype(jnp.int32) & 0xF
    hi = q_ref[:, h:].astype(jnp.int32) & 0xF
    p_ref[...] = (lo | (hi << 4)).astype(jnp.uint8)


def _unpack_kernel(p_ref, q_ref):
    p = p_ref[...].astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8
    hi = (((p >> 4) & 0xF) ^ 8) - 8
    q_ref[...] = jnp.concatenate([lo, hi], axis=1).astype(jnp.int8)


def _pad_rows(x2d, rows=_ROWS):
    nb = x2d.shape[0]
    pad = (-nb) % rows
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d, nb


def _cellwise(name, kernel, out_dtype, out_cols, x2d, *extra):
    """Launch a 32-row-cell kernel over [nb, cols] operands (scales
    pad with ones so padded rows divide by 1)."""
    from jax.experimental import pallas as pl

    x2d, nb = _pad_rows(x2d)
    args = [x2d]
    in_specs = [pl.BlockSpec((_ROWS, x2d.shape[1]), lambda i: (i, 0))]
    for e in extra:
        if e.shape[1] == 1:  # scales column: pad with ones
            e = jnp.concatenate(
                [e, jnp.ones((x2d.shape[0] - nb, 1), e.dtype)])
        else:
            e, _ = _pad_rows(e)
        args.append(e)
        in_specs.append(pl.BlockSpec((_ROWS, e.shape[1]),
                                     lambda i: (i, 0)))
    out = pl.pallas_call(
        kernel,
        grid=(x2d.shape[0] // _ROWS,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((_ROWS, out_cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x2d.shape[0], out_cols),
                                       out_dtype),
        interpret=GATE.interpret,
        name=name,
    )(*args)
    return out[:nb]


# ---------------------------------------------------------------------------
# public (gated) entry points — consumed by parallel/compression.py
# ---------------------------------------------------------------------------

def quantize_int4(x2d, scales):
    """[nb, B] fp32 + effective scales -> int4-valued int8 codes."""
    if GATE.path() != "oracle":
        def k(x_ref, s_ref, q_ref):
            _quant_kernel(x_ref, s_ref, q_ref)
        return _cellwise("quant4_quantize", k, jnp.int8, x2d.shape[1], x2d,
                         scales)
    return _quantize_jnp(x2d, scales)


def dequantize_int4(q2d, scales):
    """int4 codes (or int32 psum partials) + effective scales -> fp32."""
    if GATE.path(fits=q2d.dtype == jnp.int8) != "oracle":
        def k(q_ref, s_ref, o_ref):
            _dequant_kernel(q_ref, s_ref, o_ref)
        return _cellwise("quant4_dequantize", k, jnp.float32, q2d.shape[1],
                         q2d, scales)
    return _dequantize_jnp(q2d, scales)


def pack_int4(q2d):
    """[nb, B] int4 codes -> [nb, ceil(B/2)] uint8 split-half nibbles
    (a ragged odd-B tail pads one zero lane)."""
    if GATE.path() != "oracle":
        q2d = _pad_even_lanes(q2d)

        def k(q_ref, p_ref):
            _pack_kernel(q_ref, p_ref)
        return _cellwise("quant4_pack", k, jnp.uint8, q2d.shape[1] // 2,
                         q2d)
    return _pack_jnp(q2d)


def unpack_int4(p2d, n=None):
    """[nb, B/2] uint8 nibbles -> [nb, B] int4-valued int8 codes;
    ``n`` truncates a ragged tail's pad lane back off."""
    if GATE.path() != "oracle":
        def k(p_ref, q_ref):
            _unpack_kernel(p_ref, q_ref)
        out = _cellwise("quant4_unpack", k, jnp.int8, p2d.shape[1] * 2, p2d)
        return out if n is None else out[:, :n]
    return _unpack_jnp(p2d, n)
