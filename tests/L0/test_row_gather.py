"""``kernels/row_gather.py``: the row gather and the row scatter-add that
walk only the row tiles below a count, each against XLA's whole-array
``src[idx]`` / ``.at[idx].add`` under the ``r < kept`` mask, as a walk of
several tiles and as the one-tile oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.kernels import row_gather
from apex_tpu.kernels.row_gather import gather_rows, scatter_add_rows

T, H, TILE = 24, 16, 8


@pytest.fixture(params=["walk", "wide_scatter", "oracle"])
def path(request, monkeypatch):
    """``walk``: tiles of 8 rows for both, so the shapes here take several
    trips; ``wide_scatter``: the scatter-add's trips take two of the
    gather's tiles, as the module's take eight; ``oracle``: the module's
    own tiles, which none of the shapes fills."""
    if request.param != "oracle":
        monkeypatch.setattr(row_gather, "ROW_TILE", TILE)
        monkeypatch.setattr(row_gather, "SCATTER_TILE",
                            TILE if request.param == "walk" else 2 * TILE)
    return request.param


def _rows(rows, dtype, seed=0, spread=T):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(T, H)), dtype),
            jnp.asarray(rng.integers(0, spread, size=rows), jnp.int32),
            jnp.asarray(rng.normal(size=(rows, H)), dtype),
            jnp.asarray(rng.uniform(0.1, 1.0, size=rows), jnp.float32))


def _masked_gather(src, idx, kept):
    live = np.arange(idx.shape[0]) < kept
    return np.where(live[:, None], np.asarray(src, np.float32)[
        np.where(live, np.asarray(idx), 0)], 0)


def _masked_scatter_add(vals, idx, kept, weights=None):
    live = np.arange(idx.shape[0]) < kept
    vals = np.asarray(vals, np.float32)
    if weights is not None:
        vals = vals * np.asarray(weights)[:, None]
    out = np.zeros((T, vals.shape[1]), np.float32)
    np.add.at(out, np.asarray(idx)[live], vals[live])
    return out


# kept = 0, 1, one tile exactly, a tile and a row, every row; 32 rows are
# whole tiles of 8 and 29 are not (the last tile overlaps the one before)
KEPT = [(32, 0), (32, 1), (32, 8), (32, 9), (32, 32), (29, 0), (29, 1),
        (29, 8), (29, 9), (29, 25), (29, 29)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,kept", KEPT)
def test_gather_is_the_masked_whole_array_gather(path, rows, kept, dtype):
    src, idx, _, _ = _rows(rows, dtype)
    got = gather_rows(src, idx, jnp.int32(kept))
    assert got.shape == (rows, H) and got.dtype == dtype
    assert (np.asarray(got, np.float32)
            == _masked_gather(src, idx, kept)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("rows,kept", KEPT)
def test_scatter_add_is_the_masked_whole_array_scatter_add(
        path, rows, kept, weighted, dtype):
    _, idx, vals, weights = _rows(rows, dtype)
    weights = weights if weighted else None
    got = scatter_add_rows(vals, idx, jnp.int32(kept), T, weights=weights)
    assert got.shape == (T, H) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        got, _masked_scatter_add(vals, idx, kept, weights),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("spread", [1, 3])
def test_duplicate_indices_within_and_across_tiles_all_add(path, spread):
    """Every row lands on one of ``spread`` output rows: several a tile
    and the same ones in every tile."""
    _, idx, vals, weights = _rows(32, jnp.float32, seed=3, spread=spread)
    got = scatter_add_rows(vals, idx, jnp.int32(27), T, weights=weights)
    np.testing.assert_allclose(
        got, _masked_scatter_add(vals, idx, 27, weights), rtol=1e-5,
        atol=1e-5)
    assert (np.asarray(got)[spread:] == 0).all()


@pytest.mark.parametrize("kept", [0, 5, 8, 13])
def test_nothing_from_kept_on_is_read(path, kept):
    """NaN in ``vals`` and ``weights`` and an index out of range from
    ``kept`` on reach nothing, in the outputs and in the gradients; the
    gather's tail is exactly zero."""
    src, idx, vals, weights = _rows(29, jnp.float32, seed=1)
    k = jnp.int32(kept)
    bad_idx = idx.at[kept:].set(10 ** 6)
    bad_vals = vals.at[kept:].set(jnp.nan)
    bad_weights = weights.at[kept:].set(jnp.nan)

    got = gather_rows(src, bad_idx, k)
    assert (np.asarray(got) == _masked_gather(src, idx, kept)).all()
    assert (np.asarray(got)[kept:] == 0).all()
    out = scatter_add_rows(bad_vals, bad_idx, k, T, weights=bad_weights)
    np.testing.assert_allclose(
        out, _masked_scatter_add(vals, idx, kept, weights), rtol=1e-6,
        atol=1e-6)

    dsrc = jax.grad(lambda s: jnp.sum(
        gather_rows(s, bad_idx, k) * jnp.nan_to_num(bad_vals)))(src)
    np.testing.assert_allclose(dsrc, _masked_scatter_add(vals, idx, kept),
                               rtol=1e-6, atol=1e-6)
    dvals, dweights = jax.grad(lambda v, w: jnp.sum(
        scatter_add_rows(v, bad_idx, k, T, weights=w) * src), (0, 1))(
            bad_vals, bad_weights)
    assert bool(jnp.isfinite(dvals).all() & jnp.isfinite(dweights).all())
    assert (np.asarray(dvals)[kept:] == 0).all()
    assert (np.asarray(dweights)[kept:] == 0).all()


@pytest.mark.parametrize("rows,kept", [(32, 19), (29, 29), (29, 0)])
def test_each_one_s_gradient_is_the_other_s_forward(path, rows, kept):
    src, idx, vals, weights = _rows(rows, jnp.float32, seed=2)
    k = jnp.int32(kept)
    dout_rows, dout_src = vals, src

    _, vjp = jax.vjp(lambda s: gather_rows(s, idx, k), src)
    np.testing.assert_allclose(
        vjp(dout_rows)[0], scatter_add_rows(dout_rows, idx, k, T),
        rtol=1e-6, atol=1e-6)

    _, vjp = jax.vjp(lambda v: scatter_add_rows(v, idx, k, T), vals)
    assert (np.asarray(vjp(dout_src)[0])
            == np.asarray(gather_rows(dout_src, idx, k))).all()

    _, vjp = jax.vjp(
        lambda v, w: scatter_add_rows(v, idx, k, T, weights=w), vals,
        weights)
    dvals, dweights = vjp(dout_src)
    gathered = _masked_gather(dout_src, idx, kept)
    np.testing.assert_allclose(
        dvals, np.asarray(weights)[:, None] * gathered, rtol=1e-6)
    np.testing.assert_allclose(
        dweights, np.sum(np.asarray(vals) * gathered, axis=1), rtol=1e-5,
        atol=1e-6)


@pytest.mark.parametrize("through", ["scatter_add_rows", "gather_rows"])
def test_bf16_rows_are_summed_in_float32(path, through):
    """4,095 rows of 2**-9 onto one output row: a bf16 sum would stall at
    1 (from there the addend is under half a unit in the last place), the
    float32 sum is 4095 / 512, rounded to bf16 once where the output is
    bf16 (the gather's transposed sum)."""
    rows = 4096
    idx = jnp.zeros((rows,), jnp.int32).at[0].set(1)
    vals = jnp.full((rows, H), 2.0 ** -9, jnp.bfloat16).at[0].set(7.0)
    k = jnp.int32(rows)
    if through == "scatter_add_rows":
        got = scatter_add_rows(vals, idx, k, T)
        assert got.dtype == jnp.float32
    else:
        src = jnp.zeros((T, H), jnp.bfloat16)
        _, vjp = jax.vjp(lambda s: gather_rows(s, idx, k), src)
        got, = vjp(vals)
        assert got.dtype == jnp.bfloat16
    want = (rows - 1) * 2.0 ** -9
    assert (np.asarray(got, np.float32)[0] == np.float32(
        jnp.asarray(want, got.dtype))).all()
    assert (np.asarray(got, np.float32)[1] == 7.0).all()


def _loops(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("while[")


def test_fits_decides_and_a_misfit_makes_no_loop(monkeypatch):
    """More rows than a tile are walked in a loop, forward and backward
    one loop each; up to a tile they are one whole-array pass."""
    assert row_gather.fits(98304) and row_gather.fits(131072)
    assert row_gather.row_tile(98304) == row_gather.ROW_TILE == 2048
    assert not row_gather.fits(2048) and row_gather.row_tile(512) == 512
    assert row_gather.scatter_tile(98304) == row_gather.SCATTER_TILE == 16384
    assert row_gather.scatter_tile(16384) == 2048
    assert row_gather.scatter_tile(512) == 512
    src, idx, vals, weights = _rows(32, jnp.float32)
    k = jnp.int32(11)

    def both():     # a new function a trace: jax caches a jaxpr by it
        return lambda s, v, w: (
            jnp.sum(gather_rows(s, idx, k))
            + jnp.sum(scatter_add_rows(v, idx, k, T, weights=w)))

    assert _loops(both(), src, vals, weights) == 0
    assert _loops(jax.grad(both(), (0, 1, 2)), src, vals, weights) == 0
    monkeypatch.setattr(row_gather, "ROW_TILE", TILE)
    assert row_gather.fits(32) and row_gather.row_tile(32) == TILE
    assert row_gather.scatter_tile(32) == TILE
    assert _loops(both(), src, vals, weights) == 2
    assert _loops(jax.grad(both(), (0, 1, 2)), src, vals, weights) == 4


@pytest.mark.parametrize("kept,trips", [(0, 0), (1, 1), (8, 1), (9, 2),
                                        (32, 4), (40, 4)])
def test_the_loop_takes_a_trip_a_tile_below_the_count(kept, trips):
    assert int(row_gather._trips(jnp.int32(kept), 32, TILE)) == trips


def test_the_walk_equals_the_oracle_to_the_bit(monkeypatch):
    """One tile or several: the same gathered rows, and the same sums
    where no two rows of different tiles meet."""
    src, _, vals, weights = _rows(24, jnp.float32, seed=4)
    idx = jnp.asarray(np.random.default_rng(4).permutation(T), jnp.int32)
    k = jnp.int32(21)
    whole = (gather_rows(src, idx, k),
             scatter_add_rows(vals, idx, k, T, weights=weights))
    monkeypatch.setattr(row_gather, "ROW_TILE", TILE)
    tiled = (gather_rows(src, idx, k),
             scatter_add_rows(vals, idx, k, T, weights=weights))
    for a, b in zip(whole, tiled):
        assert (np.asarray(a) == np.asarray(b)).all()
