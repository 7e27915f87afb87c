"""Pallas streaming kernel for KV-cache decode attention (TPU).

The serving hot loop (transformer_lm.py ParallelAttention
._decode_attention, single-token steps) scores each new query against
the whole cache buffer with an XLA einsum: [b, g, rep, T] fp32 scores
materialize in HBM, the cache is read twice (scores + combine), and the
masked dead tail beyond the live prefix is still fetched. This kernel
streams K/V through VMEM in ``block_t`` tiles ONCE per (batch, kv-group)
with an online softmax over the tile axis; all ``rep`` query heads of a
group share the tile (the GQA memory saving survives into the kernel).
Scalar-prefetched prefix length clamps the tile index map, so tiles
beyond the live prefix — and, for sliding-window layers, tiles before
``length - window`` — are never DMA'd: windowed decode cost is
O(window), not O(max_len).

Gemma-2-style tanh soft-capping is applied in-kernel (elementwise on
scores before masking — the online softmax is unaffected). ALiBi decode
stays on the einsum path.

Reference analog: apex/contrib/fmha exists purely to make attention
fast (fmha_api.cpp:363); this is the same move for the decode loop the
way contrib/mla_decode.py is for the MLA latent cache. Off TPU the
public entry falls back to the einsum formulation (also the parity
oracle for the kernel tests).
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

_GATE = kernel_gate("gqa_decode")


def force_interpret(on: bool):
    """Run the kernel in interpreter mode regardless of backend (tests:
    exercises the real kernel dataflow on the CPU mesh)."""
    _GATE.force_interpret(on)


def gqa_decode_reference(q, k, v, length, sm_scale, window=None,
                         softcap=None):
    """Einsum formulation (the oracle): q [b, g, rep, d], k/v
    [T, b, g, d], length [] int32 -> ctx [b, g, rep, d] fp32."""
    s = jnp.einsum("bgrd,tbgd->bgrt", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    if softcap is not None:
        cap = jnp.float32(softcap)
        s = cap * jnp.tanh(s / cap)
    t = jnp.arange(k.shape[0])[None, None, None, :]
    masked = t >= length
    if window is not None:
        masked = masked | (t < length - window)
    s = jnp.where(masked, NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgrt,tbgd->bgrd", p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, sm_scale, softcap, window, block_t, num_t, g,
                   d):
    """One (batch, cache-tile) grid cell: the tile carries every kv
    group's lanes (``[block_t, g*d]`` — a lane-dense block the TPU
    lowering accepts), each group's rep query heads share its lane
    slice, online softmax across the streamed tile axis."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0]
    live = j * block_t < length
    if window is not None:
        start = jnp.maximum(length - window, 0)
        live = live & ((j + 1) * block_t > start)

    @pl.when(live)
    def _step():
        for gi in range(g):
            lanes = slice(gi * d, (gi + 1) * d)
            q = q_ref[0, gi].astype(jnp.float32) * sm_scale  # [rep, d]
            k = k_ref[:, lanes].astype(jnp.float32)          # [block_t, d]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap is not None:
                cap = jnp.float32(softcap)
                s = cap * jnp.tanh(s / cap)
            t_ids = j * block_t + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            masked = t_ids >= length
            if window is not None:
                masked = masked | (t_ids < length - window)
            s = jnp.where(masked, NEG_INF, s)
            m_prev = m_ref[gi]
            l_prev = l_ref[gi]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[gi] = alpha * l_prev + jnp.sum(p, axis=-1)[:, None]
            m_ref[gi] = m_new
            vv = v_ref[:, lanes].astype(jnp.float32)
            acc_ref[gi] = acc_ref[gi] * alpha + jnp.dot(
                p, vv, preferred_element_type=jnp.float32)

    @pl.when(j == num_t - 1)
    def _finish():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _decode_pallas(q, k, v, length, sm_scale, softcap, window, block_t):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, g, rep, d = q.shape
    T = k.shape[0]
    num_t = T // block_t
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               softcap=softcap, window=window,
                               block_t=block_t, num_t=num_t, g=g, d=d)

    def kv_index(bi, j, len_ref):
        # clamp into the live tile range: a repeated block index skips
        # the DMA, so neither the dead tail nor (with a window) the
        # expired head of the cache is ever fetched
        last = jnp.maximum(len_ref[0] - 1, 0) // block_t
        if window is None:
            first = 0
        else:
            first = jnp.maximum(len_ref[0] - window, 0) // block_t
        return (jnp.clip(j, first, last), bi)

    # K/V stream as [T, b*g*d] (a free view of the [T, b, g, d] cache):
    # a (block_t, 1, 1, d) block of the 4-D buffer is refused by the TPU
    # lowering (last two block dims must tile (8, 128) or span the array)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, num_t),
        in_specs=[
            pl.BlockSpec((1, g, rep, d),
                         lambda bi, j, len_ref: (bi, 0, 0, 0)),
            pl.BlockSpec((block_t, g * d), kv_index),
            pl.BlockSpec((block_t, g * d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, g, rep, d),
                               lambda bi, j, len_ref: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, rep, d), jnp.float32),  # acc
            pltpu.VMEM((g, rep, 1), jnp.float32),  # running max
            pltpu.VMEM((g, rep, 1), jnp.float32),  # running sum
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, rep, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_GATE.interpret,
        name="gqa_decode",
    )(jnp.asarray(length, jnp.int32).reshape(1), q,
      k.reshape(T, b * g * d), v.reshape(T, b * g * d))


def _fits(cache_len, block_t, kv_shape=None) -> bool:
    """The kernel's own half of the rule: the block ladder finds a tile
    dividing the cache buffer and — given the ``[T, b, g, d]`` cache
    shape — the (block_t, g*d) tile of its [T, b*g*d] view is one the
    TPU lowering accepts (:func:`lane_block_ok`)."""
    if choose_block(cache_len, block_t) is None:
        return False
    if kv_shape is None:
        return True
    _, b, g, d = kv_shape
    return lane_block_ok(_GATE, b, g * d)


def use_flash(cache_len: int, block_t: int = DEFAULT_BLOCK_T,
              kv_shape=None) -> bool:
    """True when :func:`gqa_flash_decode` would run the kernel
    (TPU/interpret and :func:`_fits`; not counted). Callers gate on this
    so the non-kernel path is their own production einsum formulation."""
    return _GATE.path(fits=_fits(cache_len, block_t, kv_shape),
                      record=False) != "oracle"


def gqa_flash_decode(q, k, v, length, sm_scale, window=None, softcap=None,
                     block_t=DEFAULT_BLOCK_T):
    """Streaming KV-cache decode attention for one token step.

    q:      [b, g, rep, d] grouped queries (rep = heads per kv group).
    k, v:   [T, b, g, d] cache buffers (transformer_lm decode layout).
    length: [] int32 — live prefix length INCLUDING the current token.
    window: optional sliding window (Mistral semantics).
    softcap: optional Gemma-2 tanh score cap.
    Returns ctx [b, g, rep, d] fp32.

    Takes the einsum oracle off-TPU or when :func:`use_flash` declines
    the shape; the path taken is recorded as
    ``kernels/dispatch/gqa_decode_<path>``.
    """
    T = k.shape[0]
    if _GATE.path(fits=_fits(T, block_t, k.shape)) == "oracle":
        return gqa_decode_reference(q, k, v, length, sm_scale, window,
                                    softcap)
    return _decode_pallas(q, k, v, length, sm_scale, softcap, window,
                          choose_block(T, block_t))
