"""Block-quantized gradient collectives with error feedback.

Why: every collective in the DP runtime moves gradients at full width —
``_psum_with_policy`` even *upcasts* to fp32 under ``allreduce_always_fp32``
— and the ZeRO optimizers ship full-precision shards both ways through
their ``psum_scatter``/``all_gather`` round trip. EQuARX (arxiv
2506.17615) shows a block-scaled quantized AllReduce inside XLA cuts DP
grad-sync bytes ~4x with negligible accuracy loss; this module is that
comm story for the apex_tpu collectives.

Scheme (``mode="int8"``): the flat bucket is padded to whole
``block_size``-element blocks (ragged tail zero-padded); per-block absmax
scales are computed locally and the per-replica scales are combined with
``lax.pmax`` — the all-gather-the-scales-and-take-max exchange fused into
one tiny collective — so every replica quantizes against the SAME scale
grid; values are rounded to int8 in [-127, 127]; the payload is summed as
**int32 partials** (a psum of <= 2^24 int8 lanes is exact in int32, and a
production quantized allreduce — EQuARX's — ships the int8 payload on the
wire; :func:`estimate_allreduce_bytes` models those wire bytes); the sum
is dequantized with the shared scales. The local quantization error
``g_eff - q*s`` is returned as the **error-feedback residual**: callers
must add it back into the next step's gradient (EF-SGD), which is what
keeps int8 training within noise of the fp32 baseline. The residual is an
explicit pytree/array so it composes with jit and buffer donation.

``mode="bf16"`` is a passthrough-cast mode: the payload is bf16 on the
wire (2x fewer bytes, no residual needed — and exact when the gradients
are already bf16).

``mode="int4"`` pushes the same machinery to 4 bits with EQuARX-style
DUAL quantization (apex_tpu.kernels.quant4): symmetric int4 values in
[-7, 7] against per-block scales that are THEMSELVES uint8-quantized
relative to one fp32 per-bucket scale, so the modeled wire is ~0.53
bytes/element at block 256 (values 0.5 + scales 1/256 + one fp32). The
error-feedback residual machinery is shared verbatim with int8 — only
the per-step quantization error is larger (EF absorbs it; the 200-step
convergence test holds the same 2% bound).

The quantize/dequantize kernels ride the kernel registry
(:mod:`apex_tpu.kernels.registry`): gates ``quant`` (int8) and
``quant4``, the one switch ``APEX_TPU_KERNELS=0``;
:func:`force_interpret` runs them in interpreter mode for CPU tests.
Off TPU the pure-``jnp`` formulations below are both the fallback and
the kernels' parity oracles.
"""

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.kernels import quant4 as _quant4
from apex_tpu.kernels.registry import kernel_gate
from apex_tpu.telemetry import comm as _telemetry_comm

# ~256 lanes per scale: 2 TPU lane-groups wide, 0.4% scale overhead.
BLOCK_SIZE = 256

# int8 symmetric range; -128 is excluded so the grid is symmetric and
# dequantization is a pure scale multiply.
_QMAX = 127.0

# compression modes whose collectives return an error-feedback residual
RESIDUAL_MODES = ("int8", "int4")

_GATE = kernel_gate("quant")


def needs_residual(mode) -> bool:
    """Whether ``mode`` makes the compressed collectives stateful —
    returning ``(result, new_residual)`` for error feedback."""
    return mode in RESIDUAL_MODES


def force_interpret(on: bool):
    """Run the Pallas quantize/dequantize kernels (int8 AND int4) in
    interpreter mode regardless of backend (tests: exercises the kernel
    dataflow on the CPU mesh)."""
    _GATE.force_interpret(on)
    _quant4.GATE.force_interpret(on)


def num_blocks(n: int, block_size: int = BLOCK_SIZE) -> int:
    return -(-n // block_size)


def pad_to_blocks(flat, block_size: int = BLOCK_SIZE):
    """[n] -> [nblocks, block_size] fp32, ragged tail zero-padded."""
    n = flat.shape[0]
    nb = num_blocks(n, block_size)
    flat = jnp.pad(flat.astype(jnp.float32), (0, nb * block_size - n))
    return flat.reshape(nb, block_size)


def block_scales(x2d):
    """Per-block symmetric scale: absmax/127, floored so an all-zero
    block dequantizes to zeros instead of NaN."""
    absmax = jnp.max(jnp.abs(x2d), axis=-1, keepdims=True)
    return jnp.maximum(absmax, 1e-12) / _QMAX


# ---------------------------------------------------------------------------
# quantize / dequantize: pure-jnp formulation + Pallas kernel
# ---------------------------------------------------------------------------

def _quantize_jnp(x2d, scales):
    return jnp.clip(jnp.round(x2d / scales), -_QMAX, _QMAX).astype(jnp.int8)


def _dequantize_jnp(q2d, scales):
    return q2d.astype(jnp.float32) * scales


# fp32 rows tile at 8 sublanes, int8 output rows at 32 — one grid cell
# handles 32 blocks so both operand tilings are legal.
_ROWS_PER_CELL = 32


def _quant_kernel(x_ref, s_ref, q_ref):
    q_ref[...] = jnp.clip(jnp.round(x_ref[...] / s_ref[...]),
                          -_QMAX, _QMAX).astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _pad_rows(x2d, rows):
    nb = x2d.shape[0]
    pad = (-nb) % rows
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d, nb


def _quantize_pallas(x2d, scales):
    from jax.experimental import pallas as pl

    bs = x2d.shape[1]
    x2d, nb = _pad_rows(x2d, _ROWS_PER_CELL)
    # pad scales with ones: the padded rows divide by 1, not by 0
    s = jnp.concatenate(
        [scales, jnp.ones((x2d.shape[0] - nb, 1), jnp.float32)])
    q = pl.pallas_call(
        _quant_kernel,
        grid=(x2d.shape[0] // _ROWS_PER_CELL,),
        in_specs=[pl.BlockSpec((_ROWS_PER_CELL, bs), lambda i: (i, 0)),
                  pl.BlockSpec((_ROWS_PER_CELL, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS_PER_CELL, bs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.int8),
        interpret=_GATE.interpret,
        name="quant_quantize",
    )(x2d, s)
    return q[:nb]


def _dequantize_pallas(q2d, scales):
    from jax.experimental import pallas as pl

    bs = q2d.shape[1]
    q2d, nb = _pad_rows(q2d, _ROWS_PER_CELL)
    s, _ = _pad_rows(scales, _ROWS_PER_CELL)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(q2d.shape[0] // _ROWS_PER_CELL,),
        in_specs=[pl.BlockSpec((_ROWS_PER_CELL, bs), lambda i: (i, 0)),
                  pl.BlockSpec((_ROWS_PER_CELL, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS_PER_CELL, bs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(q2d.shape, jnp.float32),
        interpret=_GATE.interpret,
        name="quant_dequantize",
    )(q2d, s)
    return out[:nb]


def quantize_blockwise(flat, block_size: int = BLOCK_SIZE, scales=None):
    """[n] -> (q [nblocks, block_size] int8, scales [nblocks, 1] fp32).

    ``scales=None`` computes local per-block scales; pass shared
    (pmax-combined) scales for the collective path so every replica
    lands on the same grid."""
    x2d = pad_to_blocks(flat, block_size)
    if scales is None:
        scales = block_scales(x2d)
    if _GATE.path() != "oracle":
        return _quantize_pallas(x2d, scales), scales
    return _quantize_jnp(x2d, scales), scales


def dequantize_blockwise(q2d, scales, n=None):
    """(q [nblocks, b] int8/int32, scales [nblocks, 1]) -> [n] fp32."""
    if _GATE.path() != "oracle":
        out = _dequantize_pallas(q2d, scales)
    else:
        out = _dequantize_jnp(q2d, scales)
    out = out.reshape(-1)
    return out if n is None else out[:n]


def quantize_rows_blockwise(x, block_size: int = BLOCK_SIZE):
    """Per-row lane-blocked symmetric int8: ``[..., F]`` ->
    ``(q [..., nb, block] int8, scales [..., nb, 1] fp32)``.

    The KV-cache quantization primitive (apex_tpu.serving.kv_cache):
    every leading-dim row (a cache position) is quantized independently
    against its own per-256-lane-block absmax scales, so appending one
    position never re-quantizes — and never drifts — the rest of the
    cache. Same grid and kernels as the flat gradient path (the Pallas
    gate applies; the parity oracle is the pure-jnp formulation)."""
    lead, n = x.shape[:-1], x.shape[-1]
    nb = num_blocks(n, block_size)
    flat = jnp.pad(x.astype(jnp.float32).reshape(-1, n),
                   ((0, 0), (0, nb * block_size - n)))
    flat = flat.reshape(-1, block_size)
    scales = block_scales(flat)
    q = (_quantize_pallas(flat, scales) if _GATE.path() != "oracle"
         else _quantize_jnp(flat, scales))
    return (q.reshape(*lead, nb, block_size),
            scales.reshape(*lead, nb, 1))


def dequantize_rows_blockwise(q, scales, n=None):
    """Inverse of :func:`quantize_rows_blockwise`:
    ``(q [..., nb, block], scales [..., nb, 1])`` -> ``[..., F]`` fp32
    (``n`` truncates the zero-padded ragged tail; default keeps
    ``nb * block`` lanes)."""
    lead = q.shape[:-2]
    block_size = q.shape[-1]
    flat = q.reshape(-1, block_size)
    s = scales.reshape(-1, 1)
    out = (_dequantize_pallas(flat, s) if _GATE.path() != "oracle"
           else _dequantize_jnp(flat, s))
    out = out.reshape(*lead, q.shape[-2] * block_size)
    return out if n is None else out[..., :n]


def init_residual(grads):
    """Zero error-feedback residual pytree matching ``grads`` (fp32
    leaves — the residual accumulates sub-ulp-of-bf16 errors)."""
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)


# ---------------------------------------------------------------------------
# compressed collectives (inside shard_map / pmap regions)
# ---------------------------------------------------------------------------

def _shared_scales(x2d, axis_name):
    """Per-replica block scales combined to the replica-set max — the
    all-gather of per-replica scales collapsed into one lax.pmax (bytes:
    nblocks fp32, ~0.4% of the payload at block 256)."""
    scales = block_scales(x2d)
    _telemetry_comm.record_collective(
        "pmax", elements=scales.size, dtype=jnp.float32,
        axis_name=axis_name, mode="int8")
    return lax.pmax(scales, axis_name)


def _shared_int4_scales(x2d, axis_name):
    """The int4 scale agreement: pmax the raw fp32 block absmaxes (one
    tiny collective, same as int8), then derive the two-level
    ``(sq uint8, gmax fp32)`` pair DETERMINISTICALLY from the shared
    absmaxes — every replica lands on the identical effective grid, so
    the int32-partial psum stays exact. Returns the effective
    ``[nblocks, 1]`` fp32 scales."""
    absmax = jnp.maximum(jnp.max(jnp.abs(x2d), axis=-1, keepdims=True),
                         1e-12)
    _telemetry_comm.record_collective(
        "pmax", elements=absmax.size, dtype=jnp.float32,
        axis_name=axis_name, mode="int4")
    absmax = lax.pmax(absmax, axis_name)
    sq, gmax = _quant4.int4_block_scales(absmax)
    return _quant4.effective_scales(sq, gmax)


def _psum_int4(flat, axis_name, *, residual, block_size=BLOCK_SIZE):
    """int4 body shared by :func:`psum_compressed`: quantize on the
    shared two-level grid, sum int32 partials (semantic wire: 4-bit
    lanes — ``bits=4`` in the accounting), dequantize, return the EF
    residual in the flat domain."""
    n = flat.shape[0]
    g = flat.astype(jnp.float32)
    if residual is not None:
        g = g + residual.astype(jnp.float32)
    x2d = pad_to_blocks(g, block_size)
    scales = _shared_int4_scales(x2d, axis_name)
    q = _quant4.quantize_int4(x2d, scales)
    _telemetry_comm.record_collective(
        "psum", elements=q.size, dtype=jnp.int8, bits=4,
        axis_name=axis_name, mode="int4", emulated=True)
    total = lax.psum(q.astype(jnp.int32), axis_name)
    out = dequantize_blockwise(total, scales, n=n)
    err = (x2d - _quant4._dequantize_jnp(q, scales)).reshape(-1)[:n]
    return out, err


def psum_compressed(flat, axis_name, *, mode="int8", residual=None,
                    block_size: int = BLOCK_SIZE):
    """AllReduce-sum of a flat buffer with a compressed payload.

    Returns ``(summed flat, new_residual)``. int8: the sum is fp32 and
    ``new_residual`` is the fp32 local quantization error to feed back
    next step (``residual=None`` starts from zeros). int4 works like
    int8 at half the wire width (dual-quantized scales; see module
    docstring). bf16: payload is a bf16 cast, result is cast back to
    ``flat.dtype``, residual is passed through unchanged (None stays
    None).
    """
    if mode == "bf16":
        _telemetry_comm.record_collective(
            "psum", elements=flat.size, dtype=jnp.bfloat16,
            axis_name=axis_name, mode="bf16")
        out = lax.psum(flat.astype(jnp.bfloat16), axis_name)
        return out.astype(flat.dtype), residual
    if mode == "int4":
        return _psum_int4(flat, axis_name, residual=residual,
                          block_size=block_size)
    if mode != "int8":
        raise ValueError(f"unknown compression mode {mode!r}")
    n = flat.shape[0]
    g = flat.astype(jnp.float32)
    if residual is not None:
        g = g + residual.astype(jnp.float32)
    x2d = pad_to_blocks(g, block_size)
    scales = _shared_scales(x2d, axis_name)
    q, _ = quantize_blockwise(g, block_size, scales=scales)
    # semantic wire width: int8 lanes + the fp32 scale pmax (the psum
    # emulation ships int32 partials until XLA grows a quantized
    # collective — estimate_allreduce_bytes models the same wire format)
    _telemetry_comm.record_collective(
        "psum", elements=q.size, dtype=jnp.int8, axis_name=axis_name,
        mode="int8", emulated=True)
    total = lax.psum(q.astype(jnp.int32), axis_name)
    out = dequantize_blockwise(total, scales, n=n)
    err = (x2d - _dequantize_jnp(q, scales)).reshape(-1)[:n]
    return out, err


def psum_compressed_blocks(x2d, axis_name, *, scale_mult=None):
    """AllReduce-sum of an ALREADY block-shaped ``[nblocks, block]``
    fp32 buffer with the int8 payload — the bucket-domain primitive the
    overlapped step (parallel/overlap.py) is built on.

    The flat :func:`psum_compressed` re-marshals its error-feedback
    residual through ``flatten``/``unflatten`` every step; a step that
    keeps its residual in this 2-D block layout adds it with one
    elementwise add and skips that traffic entirely. ``x2d`` is the
    effective gradient (residual already added by the caller).

    ``scale_mult`` folds a constant post-psum multiply (e.g. the
    ``1/world`` gradient averaging) into the dequantization scales — a
    ``[nblocks, 1]`` multiply instead of a full-length pass over the
    payload. Folding changes the result by at most one fp32 rounding
    per element vs dividing afterwards; pass ``None`` for the
    bit-exact-to-:func:`psum_compressed` order of operations.

    Returns ``(summed fp32 [nblocks * block] flat, err2d)`` where
    ``err2d`` is the local quantization error in the SAME 2-D block
    layout (the next step's residual, zero pad tail included)."""
    scales = _shared_scales(x2d, axis_name)
    q = (_quantize_pallas(x2d, scales) if _GATE.path() != "oracle"
         else _quantize_jnp(x2d, scales))
    _telemetry_comm.record_collective(
        "psum", elements=q.size, dtype=jnp.int8, axis_name=axis_name,
        mode="int8", emulated=True)
    total = lax.psum(q.astype(jnp.int32), axis_name)
    out_scales = scales if scale_mult is None \
        else scales * jnp.float32(scale_mult)
    out = dequantize_blockwise(total, out_scales)
    err = x2d - _dequantize_jnp(q, scales)
    return out, err


def psum_scatter_compressed(flat, axis_name, *, mode="int8", residual=None,
                            block_size: int = BLOCK_SIZE):
    """ZeRO grad sync: reduce-scatter with a compressed payload.

    ``flat`` length must be a multiple of ``world * block_size`` (int8)
    or ``world`` (bf16) — the optimizers pad to that (``_shard_info``).
    Returns ``(local summed shard fp32 [len/world], new_residual)``;
    the residual is full-length (the error lives where the *local*
    gradient was quantized, not where the shard landed).
    """
    if mode == "bf16":
        _telemetry_comm.record_collective(
            "psum_scatter", elements=flat.size, dtype=jnp.bfloat16,
            axis_name=axis_name, mode="bf16")
        shard = lax.psum_scatter(flat.astype(jnp.bfloat16), axis_name,
                                 tiled=True)
        return shard.astype(jnp.float32), residual
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown compression mode {mode!r}")
    world = lax.axis_size(axis_name)
    g = flat.astype(jnp.float32)
    if residual is not None:
        g = g + residual.astype(jnp.float32)
    x2d = pad_to_blocks(g, block_size)
    nb = x2d.shape[0]
    if mode == "int4":
        scales = _shared_int4_scales(x2d, axis_name)
        q = _quant4.quantize_int4(x2d, scales)
        _telemetry_comm.record_collective(
            "psum_scatter", elements=q.size, dtype=jnp.int8, bits=4,
            axis_name=axis_name, mode="int4", emulated=True)
        dq = _quant4._dequantize_jnp(q, scales)
    else:
        scales = _shared_scales(x2d, axis_name)
        q = _quantize_pallas(x2d, scales) if _GATE.path() != "oracle" \
            else _quantize_jnp(x2d, scales)
        _telemetry_comm.record_collective(
            "psum_scatter", elements=q.size, dtype=jnp.int8,
            axis_name=axis_name, mode="int8", emulated=True)
        dq = _dequantize_jnp(q, scales)
    total = lax.psum_scatter(q.astype(jnp.int32), axis_name, tiled=True)
    rank = lax.axis_index(axis_name)
    my_scales = lax.dynamic_slice_in_dim(scales, rank * (nb // world),
                                         nb // world)
    shard = dequantize_blockwise(total, my_scales)
    err = (x2d - dq).reshape(-1)
    return shard, err


def all_gather_compressed(shard, axis_name, *, mode="bf16",
                          block_size: int = BLOCK_SIZE):
    """ZeRO param gather: all-gather with a compressed payload.

    Unlike the emulated-int8 psum (int32 partials on the wire), a
    quantized all-gather genuinely ships int8 + scales through XLA
    today — each rank quantizes its own shard with LOCAL scales (no
    pmax needed; nothing is summed) and every receiver dequantizes the
    concatenation. Returns the full fp32 flat vector.
    """
    if mode == "bf16":
        _telemetry_comm.record_collective(
            "all_gather", elements=shard.size, dtype=jnp.bfloat16,
            axis_name=axis_name, mode="bf16")
        full = lax.all_gather(shard.astype(jnp.bfloat16), axis_name,
                              tiled=True)
        return full.astype(jnp.float32)
    if mode == "int4":
        return _all_gather_int4(shard, axis_name, block_size=block_size)
    if mode != "int8":
        raise ValueError(f"unknown compression mode {mode!r}")
    q, scales = quantize_blockwise(shard, block_size)
    _telemetry_comm.record_collective(
        "all_gather", elements=q.size, dtype=jnp.int8,
        axis_name=axis_name, mode="int8")
    _telemetry_comm.record_collective(
        "all_gather", elements=scales.size, dtype=jnp.float32,
        axis_name=axis_name, mode="int8")
    q_full = lax.all_gather(q, axis_name, tiled=True)
    s_full = lax.all_gather(scales, axis_name, tiled=True)
    return dequantize_blockwise(q_full, s_full)


def _all_gather_int4(shard, axis_name, *, block_size=BLOCK_SIZE):
    """The genuinely-int4 gather: each rank quantizes its own shard on
    LOCAL two-level scales (nothing is summed, so no pmax), PACKS the
    nibbles (apex_tpu.kernels.quant4 split-half format), and ships
    uint8 half-bytes + uint8 block scales + one fp32 per rank — real
    4-bit wire traffic through XLA today, like the int8 gather.

    When the ``fused_cc`` gate is live, quantize+pack runs as ONE
    kernel into the collective send and unpack+dequant as one kernel
    out of the receive (kernels/fused_cc family c): the int4 code
    tensor never round-trips HBM on either side of the ring.  Wire
    payloads, scales, and telemetry are identical either way."""
    from apex_tpu.kernels import fused_cc as _fused_cc

    x2d = pad_to_blocks(shard.astype(jnp.float32), block_size)
    nb = x2d.shape[0]
    absmax = jnp.maximum(jnp.max(jnp.abs(x2d), axis=-1, keepdims=True),
                         1e-12)
    sq, gmax = _quant4.int4_block_scales(absmax)
    scales = _quant4.effective_scales(sq, gmax)
    fused = _fused_cc.GATE.path(record=False) != "oracle"
    if fused:
        packed = _fused_cc.quantize_pack_int4(x2d, scales)
    else:
        q = _quant4.quantize_int4(x2d, scales)
        packed = _quant4.pack_int4(q)
    for elems, dt in ((packed.size, jnp.uint8), (sq.size, jnp.uint8),
                      (1, jnp.float32)):
        _telemetry_comm.record_collective(
            "all_gather", elements=elems, dtype=dt,
            axis_name=axis_name, mode="int4")
    p_full = lax.all_gather(packed, axis_name, tiled=True)
    sq_full = lax.all_gather(sq, axis_name, tiled=True)
    gmax_full = lax.all_gather(gmax.reshape(1), axis_name, tiled=True)
    s_full = sq_full.astype(jnp.float32) * (
        jnp.repeat(gmax_full, nb).reshape(-1, 1)
        / jnp.float32(255.0 * _quant4.QMAX4))
    if fused:
        return _fused_cc.unpack_dequantize_int4(p_full,
                                                s_full).reshape(-1)
    q_full = _quant4.unpack_int4(p_full)
    return dequantize_blockwise(q_full, s_full)


# ---------------------------------------------------------------------------
# comm-byte accounting (bench.py)
# ---------------------------------------------------------------------------

def estimate_allreduce_bytes(n, *, world=8, compress=None,
                             block_size: int = BLOCK_SIZE,
                             dtype_bytes: int = 4):
    """Estimated bytes EACH replica transmits for one gradient
    allreduce of ``n`` elements, ring model: ``2*(w-1)/w * payload``
    (reduce-scatter + all-gather phases). int8 counts the wire format a
    production quantized allreduce ships (1 byte/elem + fp32 per-block
    scales + the scale-pmax exchange); bf16 counts 2 bytes/elem. This
    is a MODEL — the lax.psum int8 emulation moves int32 partials until
    XLA grows an EQuARX-style quantized collective — kept in one place
    so bench.py's ``comm_bytes_per_step`` stays honest about what it
    estimates."""
    if world <= 1:
        return 0
    ring = 2.0 * (world - 1) / world
    if compress is None:
        payload = n * dtype_bytes
    elif compress == "bf16":
        payload = n * 2
    elif compress == "int8":
        nb = num_blocks(n, block_size)
        payload = n * 1 + nb * 4          # int8 lanes + shared fp32 scales
        payload += nb * 4                 # the scale pmax exchange
    elif compress == "int4":
        nb = num_blocks(n, block_size)
        payload = n * 0.5 + nb * 1 + 4    # packed nibbles + uint8 block
        #                                   scales + the fp32 bucket scale
        payload += nb * 4                 # the absmax pmax exchange (fp32)
    else:
        raise ValueError(f"unknown compression mode {compress!r}")
    return int(round(ring * payload))
