"""Fused computation-collective kernels (ROADMAP open item 3).

Every hot path used to run compute-then-collective as two XLA ops: the
mesh2d row-parallel matmuls materialized a full fp32 partial before the
TP psum, the speculative engine dequantized int8 KV blocks into HBM
before the k+1-position verify attention, and the int4 collectives
round-tripped the packed payload through HBM on both sides of the ring.
This module fuses each pair, following arXiv 2305.06942 (GEMM +
reduce-scatter / all-gather + GEMM decompositions) and T3 (arXiv
2401.16677: fire the collective as tiles complete, not after the full
product):

- family (a) — ``matmul_reduce_from`` / ``matmul_reduce_scatter`` /
  ``all_gather_matmul``: the GEMM is tiled so each output tile enters
  the collective as it finishes.  ``matmul_reduce_from`` psums column
  tiles of the product (T small psums instead of one big one after the
  whole partial); the scatter/gather forms run the ring explicitly —
  one ``ppermute`` per step interleaved with the chunk GEMMs, so only
  a 1/g-size chunk is ever live instead of the full partial.
- family (b) — ``window_attention`` / ``spec_verify_attention``: one
  flash kernel for the w-position verify window of the speculative
  path (and any multi-token decode chunk).  The int8 form dequantizes
  KV blocks IN REGISTERS (scales applied in VMEM) — the dequantized
  cache tensor never exists in HBM.
- family (c) — ``quantize_pack_int4`` / ``unpack_dequantize_int4``:
  quant4's quantize+pack collapsed into one kernel on the send side
  and unpack+dequant on the receive side, so the int4 code tensor
  never round-trips HBM around the collective.

Every entry point carries a jnp/XLA oracle at IDENTICAL collective
semantics: the fused decomposition moves exactly the bytes the unfused
op moves (T psums of payload/T = one psum of payload under the ring
model; g-1 permutes of payload/g = one reduce-scatter; g-1 permutes of
a shard = one all-gather), records the same trace-time telemetry, and
prices identically under ``analysis/sharding.py``'s static auditor —
which also knows the TPU custom_call target names below so a fused op
in lowered HLO is priced, not dropped.  Gate: ``fused_cc``.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.kernels import quant4 as _quant4
from apex_tpu.kernels.registry import (
    choose_block,
    kernel_gate,
    lane_block_ok,
)
from apex_tpu.telemetry.comm import axis_world, record_collective

GATE = kernel_gate("fused_cc")

NEG_INF = -1e30
DEFAULT_BLOCK_T = 512
# column tiles for the tiled-psum matmul_reduce_from: each tile's psum
# fires as the tile finishes (the T3 track-and-trigger schedule)
DEFAULT_TILES = 4

# The custom_call target each fused family lowers to on TPU, mapped to
# the collective KIND it subsumes.  analysis/sharding.py prices a
# custom_call with one of these targets exactly like the named
# collective (payload from the ``apex_payload_bytes`` frontend
# attribute, group size from ``apex_group_size`` / replica_groups) —
# the static comm-bytes gate survives fusion.
FUSED_CC_CUSTOM_CALL_TARGETS = {
    "apex_fused_cc_matmul_all_reduce": "all_reduce",
    "apex_fused_cc_matmul_reduce_scatter": "reduce_scatter",
    "apex_fused_cc_all_gather_matmul": "all_gather",
    "apex_fused_cc_quant4_all_gather": "all_gather",
}


# ---------------------------------------------------------------------------
# family (a): matmul <-> collective fusion (mesh2d TP blocks)
# ---------------------------------------------------------------------------

def _mm_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32)


def _matmul(x, w):
    """``x @ w`` with the trailing contraction run as a row-tiled
    Pallas GEMM: the compute half of every fused form (their oracle
    branches do not come here)."""
    from jax.experimental import pallas as pl

    lead, k = x.shape[:-1], x.shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    pad = (-m) % 8
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    mp = x2.shape[0]
    rb = next(b for b in (128, 64, 32, 16, 8) if mp % b == 0)
    out = pl.pallas_call(
        _mm_kernel,
        grid=(mp // rb,),
        in_specs=[pl.BlockSpec((rb, k), lambda i: (i, 0)),
                  pl.BlockSpec((k, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        interpret=GATE.interpret,
        name="fused_cc_matmul",
    )(x2, w)
    return out[:m].reshape(*lead, n).astype(
        jnp.result_type(x.dtype, w.dtype))


def _col_tiles(n, want=DEFAULT_TILES):
    """Largest tile count <= ``want`` dividing the output width."""
    for t in range(min(want, n), 0, -1):
        if n % t == 0:
            return t
    return 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def matmul_reduce_from(x, w, axis_name, tiles=DEFAULT_TILES):
    """Row-parallel projection joined by the TP reduction:
    semantically ``reduce_from(x @ w)`` — psum forward, identity
    backward (the mesh2d ``_reduce_from(partial @ wo)`` composition).

    Fused path: the GEMM runs in ``tiles`` column tiles and each
    tile's psum fires as the tile completes, so the full fp32 partial
    product never materializes in HBM — only a 1/T-width tile is live
    at a time.  Oracle and fused path move identical wire bytes
    (T psums of payload/T == one psum of payload under the ring
    model)."""
    return _matmul_reduce_from_fwd(x, w, axis_name, tiles)[0]


def _matmul_reduce_from_fwd(x, w, axis_name, tiles):
    n = w.shape[-1]
    if GATE.path() == "oracle":
        partial = x @ w
        record_collective("psum", elements=partial.size,
                          dtype=partial.dtype, axis_name=axis_name)
        return lax.psum(partial, axis_name), (x, w)
    t = _col_tiles(n, tiles)
    tn = n // t
    outs = []
    for i in range(t):
        tile = _matmul(x, lax.slice_in_dim(w, i * tn, (i + 1) * tn,
                                           axis=-1))
        record_collective("psum", elements=tile.size, dtype=tile.dtype,
                          axis_name=axis_name)
        outs.append(lax.psum(tile, axis_name))
    return jnp.concatenate(outs, axis=-1), (x, w)


def _matmul_reduce_from_bwd(axis_name, tiles, res, dy):
    # reduce_from is identity under transposition; the matmul grads
    # are the plain local products (dw is the rank's own shard grad,
    # dx feeds _copy_to whose backward psums it)
    x, w = res
    dx = (dy @ w.T).astype(x.dtype)
    x2 = x.reshape(-1, x.shape[-1])
    dy2 = dy.reshape(-1, dy.shape[-1])
    dw = (x2.T @ dy2).astype(w.dtype)
    return dx, dw


matmul_reduce_from.defvjp(_matmul_reduce_from_fwd,
                          _matmul_reduce_from_bwd)


def matmul_reduce_scatter(x, w, axis_name):
    """``psum_scatter(x @ w)`` over the leading axis (tiled): each
    rank ends with its 1/g row-slice of the reduced product.

    Fused path: ring reduce-scatter interleaved with the chunk GEMMs —
    at step s each rank computes the chunk the partial sum passing
    through it needs next and adds it, so only an [m/g, n] chunk is
    ever live (never the [m, n] partial).  Wire bytes: g-1 permutes of
    payload/g == one reduce-scatter of payload."""
    m = x.shape[0]
    g = axis_world(axis_name)
    if GATE.path(fits=g > 1 and m % g == 0) == "oracle":
        partial = x @ w
        record_collective("psum_scatter", elements=partial.size,
                          dtype=partial.dtype, axis_name=axis_name)
        if g <= 1:
            return partial
        return lax.psum_scatter(partial, axis_name,
                                scatter_dimension=0, tiled=True)
    chunk = m // g
    r = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % g) for i in range(g)]

    def gemm_chunk(c):
        rows = lax.dynamic_slice_in_dim(x, c * chunk, chunk, axis=0)
        return _matmul(rows, w)

    acc = None
    for s in range(g):
        c = (r - 1 - s) % g
        if acc is None:
            acc = gemm_chunk(c)
        else:
            record_collective("ppermute", elements=acc.size,
                              dtype=acc.dtype, axis_name=axis_name)
            acc = lax.ppermute(acc, axis_name, perm) + gemm_chunk(c)
    return acc


def all_gather_matmul(x_shard, w, axis_name):
    """``all_gather(x_shard, tiled=True) @ w``: column-parallel input
    assembled on the fly.

    Fused path: each rank GEMMs its resident chunk into the right
    row-slice of the output while the ring permute ships the next
    chunk in — the gathered [m, k] activation never materializes.
    Wire bytes: g-1 permutes of the shard == one all-gather."""
    ms, k = x_shard.shape
    g = axis_world(axis_name)
    if GATE.path(fits=g > 1) == "oracle":
        record_collective("all_gather", elements=x_shard.size,
                          dtype=x_shard.dtype, axis_name=axis_name)
        full = x_shard if g <= 1 else lax.all_gather(
            x_shard, axis_name, axis=0, tiled=True)
        return full @ w
    r = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % g) for i in range(g)]
    n = w.shape[-1]
    out = jnp.zeros((g * ms, n),
                    jnp.result_type(x_shard.dtype, w.dtype))
    cur = x_shard
    for s in range(g):
        src = (r - s) % g
        out = lax.dynamic_update_slice_in_dim(out, _matmul(cur, w),
                                              src * ms, axis=0)
        if s < g - 1:
            record_collective("ppermute", elements=cur.size,
                              dtype=cur.dtype, axis_name=axis_name)
            cur = lax.ppermute(cur, axis_name, perm)
    return out


# ---------------------------------------------------------------------------
# family (b): flash verify-window attention (speculative engine)
# ---------------------------------------------------------------------------

# trace-time serving knob: ServeConfig.fused_verify enters here so the
# engine can opt its AOT-traced step functions out without touching
# the process-wide gate
_VERIFY_ENABLED = True


@contextlib.contextmanager
def verify_scope(enabled):
    global _VERIFY_ENABLED
    old = _VERIFY_ENABLED
    _VERIFY_ENABLED = bool(enabled)
    try:
        yield
    finally:
        _VERIFY_ENABLED = old


# scratch rows (g * q-block * rep) one window-kernel grid cell may
# hold: acc/m/l are fp32 with the lane dim padded to 128, so 2048 rows
# is ~3 MB of VMEM next to the streamed K/V tiles
_WINDOW_ROW_BUDGET = 2048


def _q_block(w, g, rep):
    """Window positions per grid cell: the whole window when its rows
    fit the scratch budget, else the largest 8-row-aligned divisor of
    ``w`` that does. None -> the kernel declines."""
    for bq in (w, 512, 256, 128, 64, 32, 16, 8):
        if bq <= w and w % bq == 0 \
                and g * bq * rep <= _WINDOW_ROW_BUDGET \
                and (bq == w or (bq * rep) % 8 == 0):
            return bq
    return None


def _window_fits(cache_len, block_t, q_shape=None):
    """The window kernel's own half of the rule: the serving scope hasn't
    opted out, a tile divides the cache buffer and — given the
    ``[w, b, g, rep, d]`` query shape — the K/V lane block is legal and a
    query block fits the scratch budget."""
    if not _VERIFY_ENABLED or choose_block(cache_len, block_t) is None:
        return False
    if q_shape is None:
        return True
    w, b, g, rep, d = q_shape
    return lane_block_ok(GATE, b, g * d) \
        and _q_block(w, g, rep) is not None


def use_window(cache_len, block_t=DEFAULT_BLOCK_T, q_shape=None):
    """True when :func:`window_attention` would run the kernel (asked by
    a caller that lays its operands out for it; not counted)."""
    return GATE.path(fits=_window_fits(cache_len, block_t, q_shape),
                     record=False) != "oracle"


def window_attention_reference(qg, kt, vt, start, sm_scale,
                               window=None, softcap=None):
    """Einsum oracle: qg [w, b, g, rep, d] queries at absolute
    positions ``start + i``, kt/vt [T, b, g, d] cache buffers (window
    rows already written) -> ctx [w, b, g, rep, d] fp32.  Mask: causal
    at each query's own position, plus the optional sliding window."""
    s = jnp.einsum("sbgrd,tbgd->bgrst", qg.astype(jnp.float32),
                   kt.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    if softcap is not None:
        cap = jnp.float32(softcap)
        s = cap * jnp.tanh(s / cap)
    w = qg.shape[0]
    ipos = start + jnp.arange(w)[:, None]
    jpos = jnp.arange(kt.shape[0])[None, :]
    masked = jpos > ipos
    if window is not None:
        masked = masked | (ipos - jpos >= window)
    s = jnp.where(masked[None, None, None], NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgrst,tbgd->sbgrd", p, vt.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _online_softmax_step(gi, s, v, acc_ref, m_ref, l_ref):
    """Fold one masked score tile ``s [rows, block_t]`` and its value
    tile ``v [block_t, d]`` into group ``gi``'s running (acc, m, l)."""
    m_prev = m_ref[gi]
    l_prev = l_ref[gi]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[gi] = alpha * l_prev + jnp.sum(p, axis=-1)[:, None]
    m_ref[gi] = m_new
    acc_ref[gi] = acc_ref[gi] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)


def _window_kernel(start_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                   m_ref, l_ref, *, sm_scale, softcap, window, block_t,
                   num_t, bq, rep, g, d):
    """One (batch, query-block, cache-tile) cell: the tile carries
    every kv group's lanes (``[block_t, g*d]``); each group's bq*rep
    query rows share its lane slice, online softmax across the tile
    axis, per-row causal mask at each window position."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    first = start_ref[0] + qi * bq       # this block's first query
    live = j * block_t <= first + bq - 1

    @pl.when(live)
    def _step():
        for gi in range(g):
            lanes = slice(gi * d, (gi + 1) * d)
            q = q_ref[0, gi].astype(jnp.float32) * sm_scale
            k = k_ref[:, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap is not None:
                cap = jnp.float32(softcap)
                s = cap * jnp.tanh(s / cap)
            t_ids = j * block_t + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            qpos = first + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) // rep
            masked = t_ids > qpos
            if window is not None:
                masked = masked | (qpos - t_ids >= window)
            s = jnp.where(masked, NEG_INF, s)
            _online_softmax_step(
                gi, s, v_ref[:, lanes].astype(jnp.float32), acc_ref,
                m_ref, l_ref)

    @pl.when(j == num_t - 1)
    def _finish():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _window_pallas(qg, kt, vt, start, sm_scale, softcap, window,
                   block_t):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w, b, g, rep, d = qg.shape
    T = kt.shape[0]
    num_t = T // block_t
    bq = _q_block(w, g, rep)
    rows = bq * rep
    kernel = functools.partial(
        _window_kernel, sm_scale=sm_scale, softcap=softcap,
        window=window, block_t=block_t, num_t=num_t, bq=bq, rep=rep,
        g=g, d=d)

    def kv_index(bi, qi, j, start_ref):
        # clamp into the live tile range: a repeated block index skips
        # the DMA for the dead tail beyond this query block
        last = jnp.maximum(start_ref[0] + (qi + 1) * bq - 1, 0) \
            // block_t
        return (jnp.minimum(j, last), bi)

    def q_index(bi, qi, j, start_ref):
        return (bi, 0, qi, 0)

    # K/V stream as [T, b*g*d] (a free view of the [T, b, g, d] cache):
    # a (block_t, 1, 1, d) block of the 4-D buffer is refused by the
    # TPU lowering (last two block dims must tile (8, 128) or span the
    # array). Queries go in as [b, g, w*rep, d] so a cell reads each
    # group's rows without an in-kernel relayout.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, w // bq, num_t),
        in_specs=[
            pl.BlockSpec((1, g, rows, d), q_index),
            pl.BlockSpec((block_t, g * d), kv_index),
            pl.BlockSpec((block_t, g * d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, g, rows, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((g, rows, d), jnp.float32),  # acc
            pltpu.VMEM((g, rows, 1), jnp.float32),  # running max
            pltpu.VMEM((g, rows, 1), jnp.float32),  # running sum
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, w * rep, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=GATE.interpret,
        name="fused_cc_window_attention",
    )(jnp.asarray(start, jnp.int32).reshape(1),
      qg.transpose(1, 2, 0, 3, 4).reshape(b, g, w * rep, d),
      kt.reshape(T, b * g * d), vt.reshape(T, b * g * d))
    return out.reshape(b, g, w, rep, d).transpose(2, 0, 1, 3, 4)


def window_attention(qg, kt, vt, start, sm_scale, window=None,
                     softcap=None, block_t=DEFAULT_BLOCK_T):
    """Flash attention for a w-position decode window (the speculative
    verify chunk; any multi-token continuation chunk).

    qg:     [w, b, g, rep, d] grouped queries at positions start..
            start+w-1.
    kt, vt: [T, b, g, d] cache buffers with the window rows written.
    start:  [] int32 — absolute position of the first window query.
    Returns ctx [w, b, g, rep, d] fp32.  Falls back to the einsum
    oracle when :func:`use_window` declines."""
    T = kt.shape[0]
    if GATE.path(fits=_window_fits(T, block_t, qg.shape)) == "oracle":
        return window_attention_reference(qg, kt, vt, start, sm_scale,
                                          window, softcap)
    return _window_pallas(qg, kt, vt, start, sm_scale, softcap, window,
                          choose_block(T, block_t))


def spec_verify_reference(q, kq, ks, vq, vs, start, sm_scale):
    """Unfused oracle for the int8-KV verify: dequantize the blockwise
    cache into a full fp32 tensor (exactly
    ``KVCacheSpec.materialize_rows``' semantics), then run the window
    attention.  q [w, g, rep, d]; kq/vq [T, nb, B] int8; ks/vs
    [T, nb, 1] fp32 scales."""
    from apex_tpu.parallel import compression

    T = kq.shape[0]
    w, g, rep, d = q.shape
    k = compression.dequantize_rows_blockwise(kq, ks, n=g * d) \
        .reshape(T, g, d)
    v = compression.dequantize_rows_blockwise(vq, vs, n=g * d) \
        .reshape(T, g, d)
    return window_attention_reference(
        q[:, None], k[:, None], v[:, None], start, sm_scale)[:, 0]


def _dequant_lanes(q_ref, s_ref, lo, hi, B):
    """fp32 lanes ``[lo, hi)`` of a ``[block_t, nb*B]`` int8 tile, each
    lane scaled by its own quantization block's ``[block_t, 1]`` scale
    column — static slices only (a group's lanes sit inside one block
    whenever ``B % d == 0``, the serving layout)."""
    parts = []
    while lo < hi:
        blk = lo // B
        end = min(hi, (blk + 1) * B)
        parts.append(q_ref[:, lo:end].astype(jnp.float32)
                     * s_ref[:, blk:blk + 1])
        lo = end
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _verify_kernel(start_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                   o_ref, acc_ref, m_ref, l_ref, *, sm_scale, block_t,
                   num_t, w, rep, g, d, B):
    """int8-KV verify cell: each group's quantized lanes are widened
    and scaled IN VMEM, so the dequantized cache never exists in HBM —
    the fused alternative to ``materialize_rows`` + einsum."""
    from jax.experimental import pallas as pl

    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = start_ref[0]
    live = j * block_t <= start + w - 1

    @pl.when(live)
    def _step():
        for gi in range(g):
            lo, hi = gi * d, (gi + 1) * d
            q = q_ref[gi].astype(jnp.float32) * sm_scale
            k = _dequant_lanes(kq_ref, ks_ref, lo, hi, B)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            t_ids = j * block_t + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            qpos = start + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) // rep
            s = jnp.where(t_ids > qpos, NEG_INF, s)
            _online_softmax_step(
                gi, s, _dequant_lanes(vq_ref, vs_ref, lo, hi, B),
                acc_ref, m_ref, l_ref)

    @pl.when(j == num_t - 1)
    def _finish():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def spec_verify_attention(q, kq, ks, vq, vs, start, sm_scale,
                          block_t=DEFAULT_BLOCK_T):
    """Fused verify attention over the int8 blockwise KV cache of ONE
    serving slot (``vmap`` over slots for a batch): q [w, g, rep, d]
    at positions start..start+w-1, kq/vq [T, nb, B] int8 codes, ks/vs
    [T, nb, 1] fp32 block scales, with g*d <= nb*B (trailing lanes are
    quantization padding).  Returns ctx [w, g, rep, d] fp32."""
    T = kq.shape[0]
    w, g, rep, d = q.shape
    if GATE.path(fits=_window_fits(T, block_t)) == "oracle":
        return spec_verify_reference(q, kq, ks, vq, vs, start, sm_scale)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block = choose_block(T, block_t)
    num_t = T // block
    nb, B = kq.shape[1], kq.shape[2]
    rows = w * rep
    kernel = functools.partial(
        _verify_kernel, sm_scale=sm_scale, block_t=block, num_t=num_t,
        w=w, rep=rep, g=g, d=d, B=B)

    def kv_index(j, start_ref):
        last = jnp.maximum(start_ref[0] + w - 1, 0) // block
        return (jnp.minimum(j, last), 0)

    def whole(j, start_ref):
        return (0, 0, 0)

    # codes stream lane-dense as [T, nb*B] and scales as [T, nb] (free
    # views): every group reads a STATIC lane slice — the Pallas TPU
    # lowering has no dynamic_slice on a value
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_t,),
        in_specs=[
            pl.BlockSpec((g, rows, d), whole),
            pl.BlockSpec((block, nb * B), kv_index),
            pl.BlockSpec((block, nb), kv_index),
            pl.BlockSpec((block, nb * B), kv_index),
            pl.BlockSpec((block, nb), kv_index),
        ],
        out_specs=pl.BlockSpec((g, rows, d), whole),
        scratch_shapes=[
            pltpu.VMEM((g, rows, d), jnp.float32),
            pltpu.VMEM((g, rows, 1), jnp.float32),
            pltpu.VMEM((g, rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=GATE.interpret,
        name="fused_cc_spec_verify",
    )(jnp.asarray(start, jnp.int32).reshape(1),
      q.transpose(1, 0, 2, 3).reshape(g, rows, d),
      kq.reshape(T, nb * B), ks.reshape(T, nb),
      vq.reshape(T, nb * B), vs.reshape(T, nb))
    return out.reshape(g, w, rep, d).transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# family (c): quantize-into-ring int4
# ---------------------------------------------------------------------------

def _cellwise(name, kernel, out_dtype, out_cols, x2d, *extra):
    """quant4's 32-row-cell launcher, under THIS gate's interpret flag
    (the two gates may be toggled independently in benches)."""
    from jax.experimental import pallas as pl

    x2d, nb = _quant4._pad_rows(x2d)
    args = [x2d]
    in_specs = [pl.BlockSpec((_quant4._ROWS, x2d.shape[1]),
                             lambda i: (i, 0))]
    for e in extra:
        if e.shape[1] == 1:  # scales column: pad with ones
            e = jnp.concatenate(
                [e, jnp.ones((x2d.shape[0] - nb, 1), e.dtype)])
        else:
            e, _ = _quant4._pad_rows(e)
        args.append(e)
        in_specs.append(pl.BlockSpec((_quant4._ROWS, e.shape[1]),
                                     lambda i: (i, 0)))
    out = pl.pallas_call(
        kernel,
        grid=(x2d.shape[0] // _quant4._ROWS,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((_quant4._ROWS, out_cols),
                               lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x2d.shape[0], out_cols),
                                       out_dtype),
        interpret=GATE.interpret,
        name=name,
    )(*args)
    return out[:nb]


def _qp_kernel(x_ref, s_ref, p_ref):
    q = jnp.clip(jnp.round(x_ref[...] / s_ref[...]),
                 -_quant4.QMAX4, _quant4.QMAX4).astype(jnp.int32)
    h = q.shape[1] // 2
    p_ref[...] = ((q[:, :h] & 0xF) | ((q[:, h:] & 0xF) << 4)) \
        .astype(jnp.uint8)


def _ud_kernel(p_ref, s_ref, o_ref):
    p = p_ref[...].astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8
    hi = (((p >> 4) & 0xF) ^ 8) - 8
    o_ref[...] = jnp.concatenate([lo, hi], axis=1) \
        .astype(jnp.float32) * s_ref[...]


def quantize_pack_int4(x2d, scales):
    """Send-side fusion of quant4's quantize + pack: [nb, B] fp32 ->
    [nb, ceil(B/2)] uint8 nibbles in ONE kernel — the int4 code tensor
    never lands in HBM before the collective."""
    if x2d.shape[1] % 2:
        x2d = jnp.pad(x2d, ((0, 0), (0, 1)))
    if GATE.path() != "oracle":
        return _cellwise("fused_cc_quantize_pack", _qp_kernel, jnp.uint8,
                         x2d.shape[1] // 2, x2d, scales)
    return _quant4._pack_jnp(_quant4._quantize_jnp(x2d, scales))


def unpack_dequantize_int4(p2d, scales, n=None):
    """Receive-side fusion of unpack + dequantize: [nb, B/2] uint8 ->
    [nb, B] fp32 (optionally truncated to ``n`` real lanes) in ONE
    kernel."""
    if GATE.path() != "oracle":
        out = _cellwise("fused_cc_unpack_dequantize", _ud_kernel,
                        jnp.float32, p2d.shape[1] * 2,
                        p2d, scales)
    else:
        out = _quant4._dequantize_jnp(_quant4._unpack_jnp(p2d), scales)
    return out[:, :n] if n is not None else out


# ---------------------------------------------------------------------------
# HBM-intermediate accounting (the bench's "eliminated tensors" count)
# ---------------------------------------------------------------------------

def count_jaxpr_avals(closed, predicate):
    """Count equation outputs in a traced jaxpr whose aval satisfies
    ``predicate`` — WITHOUT recursing into ``pallas_call`` bodies
    (kernel-interior values live in VMEM; everything at this level is
    an HBM tensor).  This is how the fused_cc bench proves the fp32
    partial / dequantized-cache / int4-code intermediates are gone:
    the fused trace simply no longer contains an HBM value of that
    shape."""
    def walk(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                if aval is not None and getattr(aval, "shape", None) \
                        is not None and predicate(aval):
                    total += 1
            for val in eqn.params.values():
                for sub in _subjaxprs(val):
                    total += walk(sub)
        return total

    def _subjaxprs(val):
        import jax.core as jcore

        if isinstance(val, jcore.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, jcore.Jaxpr):
            yield val
        elif isinstance(val, (tuple, list)):
            for v in val:
                yield from _subjaxprs(v)

    return walk(closed.jaxpr)


def shape_predicate(shape, dtype):
    """Predicate for :func:`count_jaxpr_avals`: an HBM value of
    exactly this shape and dtype."""
    shape = tuple(shape)
    dt = jnp.dtype(dtype)

    def pred(aval):
        return tuple(aval.shape) == shape and aval.dtype == dt

    return pred


def dtype_predicate(dtype):
    """Predicate matching any HBM value of the dtype (the int4-code
    int8 tensors family (c) eliminates)."""
    dt = jnp.dtype(dtype)

    def pred(aval):
        return aval.dtype == dt and len(aval.shape) > 0

    return pred
