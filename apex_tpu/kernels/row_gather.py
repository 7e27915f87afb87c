"""Row gather and row scatter-add over the row tiles below a count, and
over no others.

The held-share path of ``SwitchMLP`` sorts every assignment's worth of
rows (a static shape, ``rows``) of which the first ``kept`` (known on the
device only) hold an assignment. Two primitives, each the other's
transpose, take ``kept`` as an operand:

- ``gather_rows(src [T, h], idx [rows], kept) -> [rows, h]``: row ``r <
  kept`` is ``src[idx[r]]``, every row from ``kept`` on is zero;
- ``scatter_add_rows(vals [rows, h], idx [rows], kept, T, weights [rows])
  -> float32 [T, h]``: ``out[idx[r]] += weights[r] * vals[r]`` for ``r <
  kept``, the product and the sum in float32. Rows from ``kept`` on are
  never read: a NaN in ``vals`` or ``weights`` there, or an ``idx`` out
  of range, reaches nothing.

Both walk the row tiles below ``kept`` in a loop whose trip count is
their number (``ceil(kept / ROW_TILE)`` for the gather, ``ceil(kept /
SCATTER_TILE)`` for the scatter-add, whose trip takes eight of the
gather's tiles): each trip is XLA's own gather or scatter-add on one
``dynamic_slice``d tile, written into a zero-initialised buffer, so the
rows fall to those that are real, a last tile's worth at the most
beside them. A ``custom_vjp`` each: the backward pass is the other primitive's
loop, and nothing differentiates through a ``while``.

No Pallas here and no gate: the loop is plain XLA on every backend.
:func:`fits` (``rows > ROW_TILE``) decides alone; up to it the whole
array is one tile and no loop is made: the oracle, XLA's gather and
scatter-add under the ``r < kept`` mask. Each walk under one
``jax.jit``: a model's layers share a trace.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

# rows a gather trip takes (XLA's gather keeps its pace a row at any tile:
# PERF.md section 6, PR 36, for what 512-8192 read on the chip)
ROW_TILE = 2048
# rows a scatter-add trip takes. XLA's scatter-add on a tile is serial
# and its pace a row improves with the tile (0.42 us a row of 2688 floats
# at 2048, 0.27 at 8192, 0.20 at 16384), and a trip costs the same for its
# dead rows as for its live ones: one trip of 16384 holds what the three
# cells' expert layers keep (3.6-14.5% of 98,304 rows), so their step does
# not follow a seed's count through this pass
SCATTER_TILE = 16384


def fits(rows: int) -> bool:
    """Is there more than one tile to walk? A predicate on the shape."""
    return rows > ROW_TILE


def row_tile(rows: int) -> int:
    """Rows a gather trip takes over ``rows`` rows: ``ROW_TILE``, or all
    of them (one tile, no loop) where they do not :func:`fits`."""
    return ROW_TILE if fits(rows) else rows


def scatter_tile(rows: int) -> int:
    """Rows a scatter-add trip takes: ``SCATTER_TILE`` where there are
    more rows than that, else as :func:`row_tile`."""
    return SCATTER_TILE if rows > SCATTER_TILE else row_tile(rows)


def _gathered(src, idx, live, weights, pair, out_dtype):
    """One tile: ``(where(live, weights * src[idx], 0), where(live,
    <pair, src[idx]>, 0))``; a ``None`` weight is 1 and a ``None`` pair
    gives no dots. Float32 products where there is a weight or a pair."""
    rows = src.at[jnp.where(live, idx, 0)].get(mode="promise_in_bounds")
    if weights is None and pair is None:
        return jnp.where(live[:, None], rows, 0).astype(out_dtype), None
    rows = rows.astype(jnp.float32)
    out = rows if weights is None else rows * weights[:, None]
    out = jnp.where(live[:, None], out, 0).astype(out_dtype)
    if pair is None:
        return out, None
    dots = jnp.sum(pair.astype(jnp.float32) * rows, axis=1)
    return out, jnp.where(live, dots, 0)


def _trips(kept, rows, tile):
    return (jnp.clip(kept, 0, rows) + tile - 1) // tile


def _tile_at(t, tile, rows):
    """Trip ``t``'s first row and row numbers. The last tile of a number
    of rows that is no whole number of tiles starts early, so that every
    slice has ``tile`` rows, and overlaps the tile before it."""
    start = jnp.minimum(t * tile, rows - tile)
    return start, start + jnp.arange(tile, dtype=jnp.int32)


def _slice(a, start, tile):
    return None if a is None else lax.dynamic_slice_in_dim(a, start, tile)


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype"))
def _gather_walk(src, idx, kept, weights, pair, *, tile, out_dtype):
    """``out[r] = weights[r] * src[idx[r]]`` and ``dots[r] = <pair[r],
    src[idx[r]]>`` for ``r < kept``, zeros after: ``(out [rows, h] of
    out_dtype, dots float32 [rows] or None)``."""
    rows = idx.shape[0]
    kept = kept.astype(jnp.int32)
    if tile >= rows:
        live = jnp.arange(rows, dtype=jnp.int32) < kept
        return _gathered(src, idx, live, weights, pair, out_dtype)

    def trip(t, carry):
        out, dots = carry
        start, row = _tile_at(t, tile, rows)
        # an overlapped row is written again with what it held
        got, dot = _gathered(src, _slice(idx, start, tile), row < kept,
                             _slice(weights, start, tile),
                             _slice(pair, start, tile), out_dtype)
        out = lax.dynamic_update_slice_in_dim(out, got, start, 0)
        if dots is not None:
            dots = lax.dynamic_update_slice_in_dim(dots, dot, start, 0)
        return out, dots

    out = jnp.zeros((rows, src.shape[1]), out_dtype)
    dots = None if pair is None else jnp.zeros((rows,), jnp.float32)
    return lax.fori_loop(0, _trips(kept, rows, tile), trip, (out, dots))


@functools.partial(jax.jit, static_argnames=("tile", "num_rows"))
def _scatter_walk(vals, weights, idx, kept, *, tile, num_rows):
    """float32 ``[num_rows, h]``: ``out[idx[r]] += weights[r] * vals[r]``
    for ``r < kept``."""
    rows = idx.shape[0]
    kept = kept.astype(jnp.int32)

    def add(out, vals, weights, idx, live):
        vals = vals.astype(jnp.float32)
        if weights is not None:
            vals = vals * weights[:, None]
        # a row that is not live adds a zero to row 0
        return out.at[jnp.where(live, idx, 0)].add(
            jnp.where(live[:, None], vals, 0), mode="promise_in_bounds")

    out = jnp.zeros((num_rows, vals.shape[1]), jnp.float32)
    if tile >= rows:
        live = jnp.arange(rows, dtype=jnp.int32) < kept
        return add(out, vals, weights, idx, live)

    def trip(t, out):
        start, row = _tile_at(t, tile, rows)
        # an overlapped row was added by the tile before
        live = (row >= t * tile) & (row < kept)
        return add(out, _slice(vals, start, tile),
                   _slice(weights, start, tile), _slice(idx, start, tile),
                   live)

    return lax.fori_loop(0, _trips(kept, rows, tile), trip, out)


# ``spec``, static: (rows a gather trip takes, rows a scatter-add trip
# takes, rows of the source, its dtype)
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather(src, idx, kept, spec):
    return _gather_walk(src, idx, kept, None, None, tile=spec[0],
                        out_dtype=src.dtype)[0]


def _gather_fwd(src, idx, kept, spec):
    return _gather(src, idx, kept, spec), (idx, kept)


def _gather_bwd(spec, residuals, dout):
    idx, kept = residuals
    _, tile, num_rows, dtype = spec
    dsrc = _scatter_walk(dout, None, idx, kept, tile=tile,
                         num_rows=num_rows)
    return dsrc.astype(dtype), None, None


_gather.defvjp(_gather_fwd, _gather_bwd)


# ``spec``, static: (rows a gather trip takes, rows a scatter-add trip
# takes, rows of the output)
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _scatter(vals, weights, idx, kept, spec):
    return _scatter_walk(vals, weights, idx, kept, tile=spec[1],
                         num_rows=spec[2])


def _scatter_fwd(vals, weights, idx, kept, spec):
    return _scatter(vals, weights, idx, kept, spec), (vals, weights, idx,
                                                      kept)


def _scatter_bwd(spec, residuals, dout):
    vals, weights, idx, kept = residuals
    dvals, dweights = _gather_walk(
        dout, idx, kept, weights, None if weights is None else vals,
        tile=spec[0], out_dtype=vals.dtype)
    if weights is not None:
        dweights = dweights.astype(weights.dtype)
    return dvals, dweights, None, None


_scatter.defvjp(_scatter_fwd, _scatter_bwd)


def _tiles(rows):
    return row_tile(rows), scatter_tile(rows)


def gather_rows(src, idx, kept):
    """``src [T, h]``, ``idx [rows]``, ``kept`` (a scalar on the device)
    -> ``[rows, h]`` of ``src``'s dtype: row ``r < kept`` is
    ``src[idx[r]]``, every row from ``kept`` on is zero, whatever
    ``idx`` holds there. Its gradient is :func:`scatter_add_rows` of the
    cotangent's rows below ``kept``, summed in float32."""
    return _gather(src, idx, kept, _tiles(idx.shape[0]) + (
        src.shape[0], jnp.dtype(src.dtype).name))


def scatter_add_rows(vals, idx, kept, num_rows, weights=None):
    """``vals [rows, h]``, ``idx [rows]``, ``kept``, ``weights [rows]``
    (float32, or None for ones) -> float32 ``[num_rows, h]``:
    ``out[idx[r]] += weights[r] * vals[r]`` for ``r < kept``, float32
    products and sums. Rows from ``kept`` on are never read. Its
    gradients: ``dvals[r] = weights[r] * dout[idx[r]]`` (in ``vals``'
    dtype) and ``dweights[r] = <vals[r], dout[idx[r]]>`` for ``r <
    kept``, zeros after."""
    return _scatter(vals, weights, idx, kept,
                    _tiles(idx.shape[0]) + (num_rows,))
