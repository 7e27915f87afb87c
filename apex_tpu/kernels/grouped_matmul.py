"""The experts' grouped matmul over the row tiles that hold assignments,
and over no others.

``grouped_matmul(lhs [m, k], rhs [g, k, n], group_sizes [g]) -> [m, n]``
multiplies the rows of group ``i`` (consecutive, in order) by ``rhs[i]``:
``lax.ragged_dot``, which is its oracle. ``sum(group_sizes) <= m``: the
rows past the last group (the *tail*) belong to no group and give zeros,
and zero gradients. The held-share path of ``SwitchMLP`` gathers every
assignment's worth of rows (a static shape) of which a few per cent hold
an assignment. XLA's TPU ``ragged-dot`` kernel follows the counts too,
but **writes nothing past them**: the tail of its output, and of its
input gradient, is whatever the buffer held (NaN included; my chip run
2, PR 34), so the oracle here is ``lax.ragged_dot`` under a row mask
(:func:`_oracle`).

Here the grid's row dimension walks a list of *visits* made from
``group_sizes`` (scalar-prefetched): one visit a (group, row tile) pair
that intersect, a tile that straddles two groups visited once a group
under a row mask; then one visit a tail tile, which stores zeros and
makes no MXU pass; the few visits left of the static bound ``tiles + g -
1`` do nothing.

Three products, one visit list:

- forward ``lhs @ rhs[i]`` and ``dlhs = dout @ rhs[i]^T`` are one kernel
  (``moe_grouped_matmul_fwd`` / ``moe_grouped_matmul_dlhs`` in a trace), the
  contraction whole in VMEM, the output's columns tiled;
- ``drhs[i] = lhs_i^T @ dout_i`` (``moe_grouped_matmul_drhs``) accumulates
  a group's row tiles into a float32 scratch and writes at the group's
  last visit; an empty group is visited once and writes zeros.

Operands keep their dtype (bf16 in the cells), accumulation is float32;
the backward products take ``dout`` in the operands' dtype, as XLA's
default precision takes it on the MXU.

Tiles come from ``(m, k, n)`` alone (:func:`fits`, :func:`_column_tile`).
No tile is padded in HBM: a last column tile that the array does not
fill is cut by the block's own bounds, and the contraction is never
tiled in the forward kernel, so a width of 1856 (14.5 lane tiles) costs
half a lane tile of one MXU pass.

Gate: ``grouped_matmul``. Under ``jax.jit``: a model's layers share one
trace and one lowering of each kernel.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.kernels.registry import kernel_gate

GATE = kernel_gate("grouped_matmul")

# rows a visit takes: of 256, 512 and 1024, 256 was fastest at both cells'
# expert layers and every real share (my chip run 2, PR 34)
ROWS = 256
DRHS_TILE = 1024      # most columns of lhs and of dout a drhs visit takes
_VMEM_BUDGET = 96 * 1024 * 1024
_RESIDENT = 24 * 1024 * 1024   # an expert's matrix held whole up to here


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """Output columns a forward visit takes: all of them while an
    expert's ``[k, n]`` matrix is within ``_RESIDENT`` (it is then read
    once a group), else 512."""
    return n if k * n * itemsize <= _RESIDENT else min(n, 512)


def _vmem_bytes(k: int, n: int, itemsize: int) -> int:
    tn = _column_tile(k, n, itemsize)
    # lhs and rhs blocks double-buffered, the float32 output block twice
    # and the product beside it
    return 2 * itemsize * (ROWS * k + k * tn) + 3 * 4 * ROWS * tn


def fits(m: int, k: int, n: int, itemsize: int = 2) -> bool:
    """Can the kernels take ``[m, k] x [g, k, n]``? A predicate on the
    shape alone: at least one row tile of rows (a decode step's handful
    of rows is all grid overhead: XLA's ``ragged-dot`` keeps those), both
    widths whole sublane-packed and at least a lane tile, and the forward
    and the ``dlhs`` blocks within VMEM. At the cells' four shapes and
    real shares of 6%, 12.5% and 28% an expert layer's forward and
    backward through the kernels beat XLA's ``ragged-dot`` over the same
    counts on the chip (Nemotron's 13.7 / 15.6 / 20.5 ms against 29.8 /
    43.8 / 78.3, Keye's 12.7 / 13.9 / 16.7 against 13.8 / 16.1 / 21.6: my
    chip run 2, PR 34), so no shape that fits is sent back."""
    return (m >= ROWS and k % 64 == 0 and n % 64 == 0
            and min(k, n) >= 128
            and max(_vmem_bytes(k, n, itemsize),
                    _vmem_bytes(n, k, itemsize)) <= _VMEM_BUDGET)


def _visits(group_sizes, m: int, *, for_drhs: bool):
    """The visit list of ``group_sizes`` over ``ceil(m / ROWS)`` row
    tiles, as the scalar-prefetch operands of both kernels:

    ``offsets [g + 1]`` the groups' first rows; ``group [V]``, ``tile
    [V]`` and ``lhs_tile [V]`` each visit's group, row tile and the row
    tile its input blocks show (a tail or idle visit keeps the last
    working visit's, so nothing is fetched for it); ``bounds [2]`` the
    number of working visits and of working + tail visits. ``V = tiles +
    g - 1``. ``for_drhs``: an empty group gets one visit (whose mask is
    empty), so that its zeros are written, and the tail gets none.
    """
    g = group_sizes.shape[0]
    tiles = -(-m // ROWS)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = jnp.minimum(starts // ROWS, tiles - 1)
    spans = jnp.where(group_sizes > 0, (ends - 1) // ROWS - first + 1,
                      int(for_drhs))
    span_ends = jnp.cumsum(spans)
    working = span_ends[-1]
    v = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(span_ends, v, side="right"), g - 1)
    tile = first[group] + v - (span_ends - spans)[group]
    last = jnp.maximum(working - 1, 0)
    tail_first = -(-ends[-1] // ROWS)
    in_tail = v >= working
    lhs_tile = jnp.where(in_tail, tile[last], tile)
    tile = jnp.where(in_tail, jnp.minimum(tail_first + v - working,
                                          tiles - 1), tile)
    group = jnp.where(in_tail, group[last], group)
    tail = 0 if for_drhs else tiles - tail_first
    bounds = jnp.stack([working, working + tail])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return tuple(a.astype(jnp.int32)
                 for a in (offsets, group, tile, lhs_tile, bounds))


def _row_mask(offsets, group, tile, v):
    """``[ROWS, 1]``: which rows of visit ``v``'s tile are its group's."""
    row = tile[v] * ROWS + lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
    return (row >= offsets[group[v]]) & (row < offsets[group[v] + 1])


def _rows_kernel(offsets, group, tile, lhs_tile, bounds, lhs_ref, rhs_ref,
                 out_ref, *, transpose_rhs):
    from jax.experimental import pallas as pl

    del lhs_tile
    v = pl.program_id(1)

    @pl.when(v < bounds[0])
    def _():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        acc = lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                              preferred_element_type=jnp.float32)
        mask = _row_mask(offsets, group, tile, v)
        revisit = (v > 0) & (tile[jnp.maximum(v - 1, 0)] == tile[v])

        @pl.when(revisit)       # the tile's earlier rows are another group's
        def _():
            out_ref[...] = jnp.where(mask, acc.astype(out_ref.dtype),
                                     out_ref[...])

        @pl.when(jnp.logical_not(revisit))
        def _():
            out_ref[...] = jnp.where(mask, acc, 0.0).astype(out_ref.dtype)

    @pl.when((v >= bounds[0]) & (v < bounds[1]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit,
                   static_argnames=("transpose_rhs", "out_dtype", "interpret"))
def _rows(lhs, rhs, group_sizes, *, transpose_rhs, out_dtype, interpret):
    """``lhs [m, k] x rhs [g, k, n]`` (``[g, n, k]`` transposed) ->
    ``[m, n]`` of ``out_dtype``, the tail zero."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _column_tile(k, n, lhs.dtype.itemsize)
    visits = _visits(group_sizes, m, for_drhs=False)

    def lhs_index(j, v, offsets, group, tile, lhs_tile, bounds):
        return lhs_tile[v], 0

    def rhs_index(j, v, offsets, group, tile, lhs_tile, bounds):
        return (group[v], j, 0) if transpose_rhs else (group[v], 0, j)

    def out_index(j, v, offsets, group, tile, lhs_tile, bounds):
        return tile[v], j

    return pl.pallas_call(
        functools.partial(_rows_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(-(-n // tn), visits[1].shape[0]),
            in_specs=[
                pl.BlockSpec((ROWS, k), lhs_index),
                pl.BlockSpec((None, tn, k) if transpose_rhs
                             else (None, k, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((ROWS, tn), out_index),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(k, n, lhs.dtype.itemsize)
            + 16 * 1024 * 1024),
        interpret=interpret,
        name="moe_grouped_matmul_dlhs" if transpose_rhs
        else "moe_grouped_matmul_fwd",
    )(*visits, lhs, rhs)


def _drhs_kernel(offsets, group, tile, lhs_tile, bounds, lhs_ref, dout_ref,
                 out_ref, acc_ref, *, mask_dout):
    from jax.experimental import pallas as pl

    del lhs_tile
    v = pl.program_id(2)
    last = pl.num_programs(2) - 1

    @pl.when(v < bounds[0])
    def _():
        @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != group[v]))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        mask = _row_mask(offsets, group, tile, v)
        rows = jnp.where(mask, lhs_ref[...], jnp.zeros_like(lhs_ref))
        dout = dout_ref[...]
        if mask_dout:   # a last tile past ``m`` holds what was in VMEM
            dout = jnp.where(mask, dout, jnp.zeros_like(dout))
        acc_ref[...] += lax.dot_general(
            rows, dout, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when((v == bounds[0] - 1)
                 | (group[jnp.minimum(v + 1, last)] != group[v]))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _drhs(lhs, dout, group_sizes, *, out_dtype, interpret):
    """``lhs [m, k]``, ``dout [m, n]`` -> ``[g, k, n]``: ``lhs_i^T @
    dout_i`` a group, zeros for an empty one; the tail is never read."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), n = lhs.shape, dout.shape[1]
    g = group_sizes.shape[0]
    tk, tn = min(k, DRHS_TILE), min(n, DRHS_TILE)
    visits = _visits(group_sizes, m, for_drhs=True)

    def lhs_index(i, j, v, offsets, group, tile, lhs_tile, bounds):
        return lhs_tile[v], i

    def dout_index(i, j, v, offsets, group, tile, lhs_tile, bounds):
        return lhs_tile[v], j

    def out_index(i, j, v, offsets, group, tile, lhs_tile, bounds):
        return group[v], i, j

    return pl.pallas_call(
        functools.partial(_drhs_kernel, mask_dout=m % ROWS != 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(-(-k // tk), -(-n // tn), visits[1].shape[0]),
            in_specs=[pl.BlockSpec((ROWS, tk), lhs_index),
                      pl.BlockSpec((ROWS, tn), dout_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="moe_grouped_matmul_drhs",
    )(*visits, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel(lhs, rhs, group_sizes, interpret):
    return _rows(lhs, rhs, group_sizes, transpose_rhs=False,
                 out_dtype=jnp.float32, interpret=interpret)


def _kernel_fwd(lhs, rhs, group_sizes, interpret):
    return (_kernel(lhs, rhs, group_sizes, interpret),
            (lhs, rhs, group_sizes))


def _kernel_bwd(interpret, residuals, dout):
    lhs, rhs, group_sizes = residuals
    dout = dout.astype(lhs.dtype)
    dlhs = _rows(dout, rhs, group_sizes, transpose_rhs=True,
                 out_dtype=lhs.dtype, interpret=interpret)
    drhs = _drhs(lhs, dout, group_sizes, out_dtype=rhs.dtype,
                 interpret=interpret)
    return dlhs, drhs, None


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def _oracle(lhs, rhs, group_sizes):
    """``lax.ragged_dot`` with the same ``group_sizes`` and the tail
    defined: zeros out, and (the input mask's transpose) zeros in the
    input gradient. XLA's CPU lowering gives those zeros itself; its TPU
    kernel leaves both tails unwritten."""
    real = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]
    out = lax.ragged_dot(jnp.where(real, lhs, 0), rhs, group_sizes,
                         preferred_element_type=jnp.float32)
    return jnp.where(real, out, 0)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [m, k]``, ``rhs [g, k, n]`` (one dtype), ``group_sizes [g]``
    with ``sum <= m`` -> float32 ``[m, n]``: rows of group ``i`` times
    ``rhs[i]``, the tail zero. ``PallasGate.path`` decides: the kernels
    where :func:`fits`, :func:`_oracle` (and its own derivative)
    otherwise."""
    (m, k), n = lhs.shape, rhs.shape[2]
    ok = (lhs.dtype == rhs.dtype and jnp.issubdtype(lhs.dtype, jnp.floating)
          and fits(m, k, n, lhs.dtype.itemsize))
    if GATE.path(ok) == "oracle":
        return _oracle(lhs, rhs, group_sizes)
    return _kernel(lhs, rhs, group_sizes.astype(jnp.int32), GATE.interpret)
