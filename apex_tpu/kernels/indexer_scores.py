"""The sparse-attention indexer's index scores, formed tile by tile in VMEM.

``I[t, u] = sum_j w[t, j] * relu(q[t, j] . k[u])`` for ``u <= t``, and 0
above the diagonal (DeepSeek Sparse Attention's lightning indexer,
``models.transformer_lm.SparseIndexer``): ``q`` ``[b, s, heads * dim]``,
``k`` ``[b, s, dim]`` (one key head), ``w`` ``[b, s, heads]`` -> ``I``
``[b, s, s]`` float32. The oracle
(``models.transformer_lm._index_scores_oracle``) forms each chunk of
queries' logits against every key as a float32 ``[heads, chunk, s]``
array in HBM and reads it back for the ``relu`` and the weighted sum over
the heads. Here a grid cell holds a tile of query rows with all their
heads (lane-dense, each head a static lane slice of the load) and a tile
of keys, forms each head's logits by one MXU product into float32,
rectifies, weights and sums them in VMEM, and writes the tile of ``I``
once: **only ``I`` reaches HBM**.

Causal: a tile that holds no pair ``u <= t`` does no MXU work and fetches
nothing (its key tile's index stays at the last one its row ran); it is
written as zeros, so that every element of ``I`` is defined. A tile that
the diagonal crosses is masked by position.

Backward (``jax.custom_vjp``; the residuals are the inputs alone): with
``m_j = dI * [logit_j > 0]`` over the causal pairs,

- ``indexer_scores_dq`` walks the tiles that hold a causal pair q-major,
  from a scalar-prefetched visit list (``contrib/fmha.py``
  ``_visit_list``), forms each head's logits again and accumulates ``P_j
  = m_j . k`` over its row; at the row's last tile ``dq_j = w_j * P_j``
  and ``dw_j = rowsum(q_j * P_j)``, which is ``rowsum(relu(logit_j) *
  dI)``;
- ``indexer_scores_dk`` walks them kv-major and accumulates ``dk += m_j^T
  . (w_j * q_j)``, the logits formed turned (``k . q_j^T``) against the
  tile of ``dI`` turned once a visit.

MXU operands in the inputs' dtype (bf16 in the model: ``m_j`` and ``w_j *
q_j`` are rounded to it, as XLA's default precision takes a float32
operand), accumulation float32.

Gate: ``indexer_scores``. Each ``pallas_call`` sits under one
``jax.jit``, so a model's layers share one trace and one lowering.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from apex_tpu.kernels.registry import kernel_gate

GATE = kernel_gate("indexer_scores")

BLOCKS = (512, 256, 128)        # a tile's side: the largest that divides s
HEAD_DIMS = (64, 128, 256)
_VMEM_BUDGET = 96 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))      # a . b^T


def _block(s):
    return next((b for b in BLOCKS if s % b == 0), None)


def _vmem_bytes(block, width):
    """A walk's VMEM at most (dq's, float32 operands): q in and dq out
    double-buffered and dq's accumulator, dI in double-buffered and six
    float32 tiles of temporaries."""
    return block * width * (4 * 4 + 4) + 8 * block * block * 4


def fits(shape, heads) -> bool:
    """Can the kernels take ``q`` of ``shape`` ``[b, s, heads * dim]``? A
    predicate on the shapes alone: a tile side that divides ``s``, heads of
    64, 128 or 256 lanes in whole 128-lane columns, a walk within VMEM."""
    if len(shape) != 3 or shape[2] % heads:
        return False
    block = _block(shape[1])
    return (block is not None and shape[2] // heads in HEAD_DIMS
            and shape[2] % 128 == 0
            and _vmem_bytes(block, shape[2]) <= _VMEM_BUDGET)


class _Tiles(NamedTuple):
    """A call's static configuration (hashable: the jitted kernels' key)."""

    heads: int
    block_q: int
    block_k: int
    interpret: bool


def indexer_scores(q, k, w):
    """``I`` ``[b, s, s]`` float32 from ``q`` ``[b, s, heads * dim]``,
    ``k`` ``[b, s, dim]`` and ``w`` ``[b, s, heads]``, by the kernels (the
    caller has asked the gate). Sets the gauges
    ``indexer/scores_tiles_run`` and ``indexer/scores_tiles_skipped``: the
    call's tiles of ``I`` that do MXU work and those written as zeros."""
    from apex_tpu.contrib import fmha
    from apex_tpu.telemetry.registry import get_registry

    b, s, _ = q.shape
    block = _block(s)
    run = fmha._visit_list(s, block, block, True).size
    reg = get_registry()
    reg.gauge("indexer/scores_tiles_run").set(b * run)
    reg.gauge("indexer/scores_tiles_skipped").set(
        b * ((s // block) ** 2 - run))
    return _scores(q, k, w, _Tiles(w.shape[-1], block, block,
                                   GATE.interpret))


# ------------------------------------------------------------ kernel bodies

def _head(ref, h, dim):
    """Head ``h``'s lanes of a ``[1, rows, heads * dim]`` block: a static
    lane slice in the load (Mosaic takes one there, not in a ref view)."""
    return ref[0, :, h * dim:(h + 1) * dim]


def _column(w_ref, h):
    """Head ``h``'s weights of a ``[1, heads, rows]`` block as a column."""
    return w_ref[0, h:h + 1, :].T


def _causal(qi, kj, block_q, block_k):
    """``[block_q, block_k]``: key ``u`` at or before query ``t``."""
    t = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    u = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return u <= t


def _fwd_kernel(q_ref, k_ref, w_ref, out_ref, *, heads, dim, block_q,
                block_k):
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(1), pl.program_id(2)
    run = kj * block_k <= qi * block_q + block_q - 1

    @pl.when(run)
    def _tile():
        k = k_ref[0]
        total = None
        for h in range(heads):
            logits = jax.lax.dot_general(_head(q_ref, h, dim), k, _NT,
                                         preferred_element_type=jnp.float32)
            term = jnp.maximum(logits, 0.0) * _column(w_ref, h)
            total = term if total is None else total + term
        out_ref[0] = jnp.where(_causal(qi, kj, block_q, block_k), total, 0.0)

    @pl.when(jnp.logical_not(run))
    def _above_the_diagonal():
        out_ref[0] = jnp.zeros((block_q, block_k), jnp.float32)


def _dq_kernel(q_ref, k_ref, w_ref, ds_ref, dq_ref, dw_ref, acc_ref, *,
               step, heads, dim, block_q, block_k):
    """``acc_j += m_j . k`` over a q tile's visits; at its last, ``dq_j =
    w_j * acc_j`` and ``dw_j = rowsum(q_j * acc_j)``."""
    from jax.experimental import pallas as pl

    @pl.when(step.first)
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = k_ref[0]
    ds = jnp.where(_causal(step.qi, step.kj, block_q, block_k), ds_ref[0],
                   0.0)
    for h in range(heads):
        logits = jax.lax.dot_general(_head(q_ref, h, dim), k, _NT,
                                     preferred_element_type=jnp.float32)
        m = jnp.where(logits > 0, ds, 0.0).astype(k.dtype)
        lanes = slice(h * dim, (h + 1) * dim)
        acc_ref[:, lanes] += jnp.dot(m, k, preferred_element_type=jnp.float32)

    @pl.when(step.last)
    def _finish():
        for h in range(heads):
            lanes = slice(h * dim, (h + 1) * dim)
            p = acc_ref[:, lanes]
            dq_ref[0, :, lanes] = (p * _column(w_ref, h)).astype(dq_ref.dtype)
            dw_ref[0, h:h + 1, :] = jnp.sum(
                _head(q_ref, h, dim).astype(jnp.float32) * p, axis=-1,
                keepdims=True).T


def _dk_kernel(k_ref, q_ref, w_ref, ds_ref, dk_ref, acc_ref, *, step, heads,
               dim, block_q, block_k):
    """``acc += m_j^T . (w_j * q_j)`` over a kv tile's visits."""
    from jax.experimental import pallas as pl

    @pl.when(step.first)
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = k_ref[0]
    ds_t = jnp.where(_causal(step.qi, step.kj, block_q, block_k), ds_ref[0],
                     0.0).T
    for h in range(heads):
        q_h = _head(q_ref, h, dim)
        logits_t = jax.lax.dot_general(k, q_h, _NT,
                                       preferred_element_type=jnp.float32)
        m_t = jnp.where(logits_t > 0, ds_t, 0.0).astype(k.dtype)
        qw = (q_h.astype(jnp.float32) * _column(w_ref, h)).astype(k.dtype)
        acc_ref[...] += jnp.dot(m_t, qw, preferred_element_type=jnp.float32)

    @pl.when(step.last)
    def _finish():
        dk_ref[0] = acc_ref[...].astype(dk_ref.dtype)


# ------------------------------------------------------------- the calls

def _params(tiles, width, semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=_vmem_bytes(max(tiles.block_q, tiles.block_k),
                                     width) + 16 * 1024 * 1024)


def _spec(shape, index):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)


def _walk(body, visits, b, tiles, width, in_specs, out_specs, out_shape,
          scratch_shapes, name):
    """``pl.pallas_call`` of ``body`` over ``(b, visits)``, the body given
    each visit as its ``step``; index maps take ``(row, v, visits_ref)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from apex_tpu.contrib import fmha

    dim = width // tiles.heads

    def kernel(visits_ref, *refs):
        body(*refs, step=fmha._visit(visits_ref, pl.program_id(1)),
             heads=tiles.heads, dim=dim, block_q=tiles.block_q,
             block_k=tiles.block_k)

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, visits.size),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=_params(tiles, width, ("parallel", "arbitrary")),
        interpret=tiles.interpret,
        name=name,
    )
    return functools.partial(call, jnp.asarray(visits))


_jit = functools.partial(jax.jit, static_argnames=("tiles",))


@_jit
def _fwd(q, k, w_rows, *, tiles):
    from jax.experimental import pallas as pl

    b, s, width = q.shape
    bq, bk, heads = tiles.block_q, tiles.block_k, tiles.heads
    dim = width // heads

    def kv_tile(i, j):      # held at the last tile its row runs
        return jnp.minimum(j, ((i + 1) * bq - 1) // bk)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, dim=dim, block_q=bq,
                          block_k=bk),
        grid=(b, s // bq, s // bk),
        in_specs=[_spec((1, bq, width), lambda r, i, j: (r, i, 0)),
                  _spec((1, bk, dim), lambda r, i, j: (r, kv_tile(i, j), 0)),
                  _spec((1, heads, bq), lambda r, i, j: (r, 0, i))],
        out_specs=_spec((1, bq, bk), lambda r, i, j: (r, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=_params(tiles, width,
                                ("parallel", "parallel", "arbitrary")),
        interpret=tiles.interpret,
        name="indexer_scores_fwd",
    )(q, k, w_rows)


def _visit_specs(tiles, width, dim):
    """BlockSpecs over a ``(b, visits)`` walk: the visit's q tile of ``q``
    and of the ``[b, heads, s]`` rows, its kv tile of ``k``, its tile of
    ``dI``."""
    from apex_tpu.contrib.fmha import _kv_tile, _q_tile

    bq, bk = tiles.block_q, tiles.block_k
    return {
        "q": _spec((1, bq, width),
                   lambda r, v, visits: (r, _q_tile(visits, v), 0)),
        "k": _spec((1, bk, dim),
                   lambda r, v, visits: (r, _kv_tile(visits, v), 0)),
        "rows": _spec((1, tiles.heads, bq),
                      lambda r, v, visits: (r, 0, _q_tile(visits, v))),
        "tile": _spec((1, bq, bk), lambda r, v, visits: (
            r, _q_tile(visits, v), _kv_tile(visits, v))),
    }


@_jit
def _dq(q, k, w_rows, ds, *, tiles):
    from jax.experimental.pallas import tpu as pltpu

    from apex_tpu.contrib import fmha

    b, s, width = q.shape
    sp = _visit_specs(tiles, width, k.shape[-1])
    return _walk(
        _dq_kernel, fmha._visit_list(s, tiles.block_q, tiles.block_k, True),
        b, tiles, width,
        in_specs=[sp["q"], sp["k"], sp["rows"], sp["tile"]],
        out_specs=[sp["q"], sp["rows"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(w_rows.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tiles.block_q, width), jnp.float32)],
        name="indexer_scores_dq",
    )(q, k, w_rows, ds)


@_jit
def _dk(q, k, w_rows, ds, *, tiles):
    from jax.experimental.pallas import tpu as pltpu

    from apex_tpu.contrib import fmha

    b, s, width = q.shape
    dim = k.shape[-1]
    sp = _visit_specs(tiles, width, dim)
    return _walk(
        _dk_kernel,
        fmha._visit_list(s, tiles.block_q, tiles.block_k, True,
                         kv_major=True),
        b, tiles, width,
        in_specs=[sp["k"], sp["q"], sp["rows"], sp["tile"]],
        out_specs=sp["k"],
        out_shape=jax.ShapeDtypeStruct(k.shape, k.dtype),
        scratch_shapes=[pltpu.VMEM((tiles.block_k, dim), jnp.float32)],
        name="indexer_scores_dk",
    )(k, q, w_rows, ds)


def _rows(w):
    """``[b, s, heads]`` -> ``[b, heads, s]`` float32: a head's weights a
    row, which the kernels turn into a column in VMEM."""
    return jnp.swapaxes(w, 1, 2).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _scores(q, k, w, tiles):
    return _fwd(q, k, _rows(w), tiles=tiles)


def _scores_fwd(q, k, w, tiles):
    return _scores(q, k, w, tiles), (q, k, w)


def _scores_bwd(tiles, residuals, ds):
    q, k, w = residuals
    dq, dw = _dq(q, k, _rows(w), ds, tiles=tiles)
    dk = _dk(q, k, _rows(w), ds, tiles=tiles)
    return dq, dk, jnp.swapaxes(dw, 1, 2).astype(w.dtype)


_scores.defvjp(_scores_fwd, _scores_bwd)
