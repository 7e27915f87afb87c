"""Pallas streaming kernel for MLA latent-cache decode (TPU).

The absorbed-projection decode (models/mla.py MLAAttention._decode_tail)
scores each new query against the per-token LATENT rows c_t =
[normed kv latent | rotated shared k_pe] — every head contracts the SAME
cache row, and the value path reuses the first ``lat`` columns of that
row (ctx_lat = sum_t p_t * c_t[:lat]). That makes the per-step prefix
attention exactly a multi-query flash attention whose K *and* V are
views of one buffer:

    scores[n, t] = (q_full[n] . c_t) * scale,   q_full = [q_lat | q_pe]
    ctx_lat[n]   = softmax_t(scores) @ c[:, :lat]

(the nope and rope score terms of the einsum path are one concatenated
contraction — same arithmetic, one pass). The XLA einsum formulation
materializes [b, n, 1, T] fp32 scores in HBM and reads the cache twice
(scores + combine); this kernel streams the cache through VMEM in
``block_t`` tiles ONCE with an online softmax, fp32 accumulators, and
skips tiles beyond the live prefix via scalar-prefetched length (the
clamped index map repeats the last contributing tile, so Mosaic never
fetches dead cache rows).

Reference analog: apex/contrib/fmha exists purely to make attention
fast (fmha_api.cpp:363); this is the same move for the MLA decode hot
loop. Off TPU the public entry falls back to the einsum formulation
(also the parity oracle for the kernel tests).
"""

import functools

import jax
import jax.numpy as jnp

from apex_tpu.kernels.registry import (
    choose_block,
    kernel_gate,
    lane_block_ok,
)

NEG_INF = -1e30
DEFAULT_BLOCK_T = 512

_GATE = kernel_gate("mla_decode")


def force_interpret(on: bool):
    """Run the kernel in interpreter mode regardless of backend (tests:
    exercises the real kernel dataflow on the CPU mesh)."""
    _GATE.force_interpret(on)


def mla_decode_reference(q_full, cache, length, lat, scale):
    """Einsum formulation (the oracle): q_full [b, n, L], cache
    [T, b, L], length [] int32 -> ctx_lat [b, n, lat] fp32."""
    scores = jnp.einsum("bnl,tbl->bnt", q_full.astype(jnp.float32),
                        cache.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    t = jnp.arange(cache.shape[0])[None, None, :]
    scores = jnp.where(t >= length, NEG_INF, scores)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bnt,tbl->bnl", probs,
                      cache[..., :lat].astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _decode_kernel(len_ref, q_ref, c_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   scale, lat, block_t, num_t):
    """One (batch, cache-tile) grid cell: all heads at once (they share
    the tile), online softmax across the streamed tile axis."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0]

    @pl.when(j * block_t < length)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale      # [n, L]
        c = c_ref[...].astype(jnp.float32)            # [block_t, L]
        s = jnp.dot(q, c.T, preferred_element_type=jnp.float32)
        t_ids = j * block_t + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(t_ids >= length, NEG_INF, s)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)[:, None]
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, c[:, :lat], preferred_element_type=jnp.float32)

    @pl.when(j == num_t - 1)
    def _finish():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _decode_pallas(q_full, cache, length, lat, scale, block_t):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, L = q_full.shape
    T = cache.shape[0]
    num_t = T // block_t
    kernel = functools.partial(_decode_kernel, scale=scale, lat=lat,
                               block_t=block_t, num_t=num_t)

    def cache_index(bi, j, len_ref):
        # clamp to the last live tile: a repeated block index skips the
        # DMA, so dead prefix tiles are never fetched
        last = jnp.maximum(len_ref[0] - 1, 0) // block_t
        return (jnp.minimum(j, last), bi)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, num_t),
        in_specs=[
            pl.BlockSpec((1, n, L), lambda bi, j, len_ref: (bi, 0, 0)),
            # the cache streams as [T, b*L] (a free view): a
            # (block_t, 1, L) block of the 3-D buffer is refused by the
            # TPU lowering once b > 1
            pl.BlockSpec((block_t, L), cache_index),
        ],
        out_specs=pl.BlockSpec((1, n, lat),
                               lambda bi, j, len_ref: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, lat), jnp.float32),  # acc
            pltpu.VMEM((n, 1), jnp.float32),    # running max
            pltpu.VMEM((n, 1), jnp.float32),    # running sum
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, lat), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_GATE.interpret,
        name="mla_decode",
    )(jnp.asarray(length, jnp.int32).reshape(1), q_full,
      cache.reshape(T, b * L))


def _fits(cache_len, block_t, cache_shape=None) -> bool:
    """The kernel's own half of the rule: the block ladder finds a tile
    dividing the cache and — given the ``[T, b, L]`` cache shape — the
    (block_t, L) tile of its [T, b*L] view is one the TPU lowering
    accepts (:func:`lane_block_ok`)."""
    if choose_block(cache_len, block_t) is None:
        return False
    if cache_shape is None:
        return True
    _, b, L = cache_shape
    return lane_block_ok(_GATE, b, L)


def use_flash(cache_len: int, block_t: int = DEFAULT_BLOCK_T,
              cache_shape=None) -> bool:
    """True when :func:`mla_flash_decode` would run the kernel
    (TPU/interpret and :func:`_fits`; not counted). Callers gate on this
    so the non-kernel path is their own production einsum formulation,
    not this module's fp32 reference fallback."""
    return _GATE.path(fits=_fits(cache_len, block_t, cache_shape),
                      record=False) != "oracle"


def mla_flash_decode(q_full, cache, length, lat, scale,
                     block_t=DEFAULT_BLOCK_T):
    """Streaming latent-cache decode attention for one step.

    q_full: [b, n, lat + rope] absorbed queries ([q_lat | q_pe]).
    cache:  [T, b, lat + rope] latent rows (models/mla.py layout).
    length: [] int32 — live prefix length INCLUDING the current token.
    Returns ctx_lat [b, n, lat] fp32 (caller expands through W_v).

    Takes the einsum oracle off-TPU or when no block divides the cache
    length (``use_flash`` tells a caller which way it will go); the path
    taken is recorded as ``kernels/dispatch/mla_decode_<path>``.
    """
    T = cache.shape[0]
    if _GATE.path(fits=_fits(T, block_t, cache.shape)) == "oracle":
        return mla_decode_reference(q_full, cache, length, lat, scale)
    return _decode_pallas(q_full, cache, length, lat, scale,
                          choose_block(T, block_t))
