"""The experts' grouped matmul (``kernels/grouped_matmul.py``) in the Pallas
interpreter against its oracle, ``lax.ragged_dot`` with the same
``group_sizes``: forward, ``dlhs`` and ``drhs`` to float32 rounding, the
tail's rows and an empty group's ``drhs`` exactly zero; the visit list;
and the rule that sends a shape that does not fit to the oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from apex_tpu.kernels import grouped_matmul as gm
from apex_tpu.kernels import registry as kreg
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

KREG = kreg.get_kernel_registry()
R = gm.ROWS


@pytest.fixture
def interpret():
    KREG.force_interpret(True, ["grouped_matmul"])
    yield
    KREG.force_interpret(False, ["grouped_matmul"])


# (m, k, n, group_sizes): tile-aligned groups; groups that straddle tiles;
# an empty group first, in the middle and last; sums equal to m and under
# it (a tail of whole tiles, of part of a tile, of everything); m that is
# no whole number of row tiles (of 256 rows); n = 320 and 192 (1856-like:
# 2.5 and 1.5 lane tiles, so the last column tile is not filled)
SIZES = {
    "aligned-full": (1024, 128, 192, [512, 512]),
    "aligned-tail": (2048, 128, 192, [512, 0, 512]),
    "straddle-full": (1024, 128, 320, [300, 5, 619, 100]),
    "straddle-tail": (2048, 192, 320, [300, 400, 13]),
    "one-tile-three-groups": (1024, 128, 192, [60, 100, 90]),
    "empty-first": (1024, 128, 192, [0, 700, 324]),
    "empty-middle": (1536, 128, 192, [400, 0, 0, 500]),
    "empty-last": (1024, 128, 192, [513, 99, 0]),
    "all-empty": (1024, 128, 128, [0, 0, 0]),
    "ragged-m-full": (1088, 128, 192, [513, 3, 572]),
    "ragged-m-tail": (1088, 128, 192, [511, 514]),
    "group-of-many-tiles": (2048, 128, 128, [7, 1536, 50]),
}


def _operands(m, k, n, sizes, dtype):
    key = jax.random.PRNGKey(m + 7 * k + 11 * n + len(sizes))
    a, b, c = jax.random.split(key, 3)
    lhs = jax.random.normal(a, (m, k), jnp.float32).astype(dtype)
    rhs = jax.random.normal(b, (len(sizes), k, n), jnp.float32).astype(dtype)
    # a cotangent the operands' dtype holds: the kernels take it so
    dout = jax.random.normal(c, (m, n), jnp.float32).astype(dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32), dout.astype(jnp.float32)


def _oracle(lhs, rhs, sizes):
    return lax.ragged_dot(lhs, rhs, sizes,
                          preferred_element_type=jnp.float32)


def _three(fn, lhs, rhs, sizes, dout):
    out, vjp = jax.vjp(lambda a, b: fn(a, b, sizes), lhs, rhs)
    return (out,) + tuple(vjp(dout))


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # float32 accumulation in another order; a bf16 result one rounding off
    tol = 2.0 ** -7 if dtype == jnp.bfloat16 else 2e-5
    scale = max(float(np.abs(want).max()), 1.0)
    assert float(np.abs(got - want).max()) <= tol * scale


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", SIZES, ids=list(SIZES))
def test_forward_and_both_gradients_are_the_oracles(interpret, case, dtype):
    m, k, n, sizes = SIZES[case]
    lhs, rhs, group_sizes, dout = _operands(m, k, n, sizes, dtype)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        out, dlhs, drhs = _three(gm.grouped_matmul, lhs, rhs, group_sizes,
                                 dout)
    assert reg.counter_value("kernels/dispatch/grouped_matmul_interpret") == 1
    want = _three(_oracle, lhs, rhs, group_sizes, dout)
    assert out.dtype == jnp.float32 and out.shape == (m, n)
    assert dlhs.dtype == dtype and drhs.dtype == dtype
    _close(out, want[0], jnp.float32)
    _close(dlhs, want[1], dtype)
    _close(drhs, want[2], dtype)
    total = sum(sizes)
    # rows in no group: exactly zero, whatever the operands hold there
    assert not np.asarray(out)[total:].any()
    assert not np.asarray(dlhs, np.float32)[total:].any()
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(drhs[g], np.float32).any()


@pytest.mark.parametrize("case", ["straddle-tail", "ragged-m-full"])
def test_an_expert_matrix_past_the_resident_budget_is_taken_in_column_tiles(
        interpret, monkeypatch, case):
    """``_column_tile``'s other branch: 512 columns a visit (here all 320,
    and 640 as a whole tile and a cut one)."""
    m, k, n, sizes = SIZES[case]
    monkeypatch.setattr(gm, "_RESIDENT", 0)
    for width in (n, 640):
        lhs, rhs, group_sizes, dout = _operands(m, k, width, sizes,
                                                jnp.float32)
        jax.clear_caches()
        got = _three(gm.grouped_matmul, lhs, rhs, group_sizes, dout)
        for a, b in zip(got, _three(_oracle, lhs, rhs, group_sizes, dout)):
            _close(a, b, jnp.float32)
    jax.clear_caches()


@pytest.mark.parametrize("path", ["interpret", "oracle"])
@pytest.mark.parametrize("case", ["straddle-tail", "empty-middle",
                                  "ragged-m-tail"])
def test_the_tail_is_never_read(case, path):
    """NaN in the rows past the groups (of the input and of the
    cotangent) reaches no output: those tiles are not multiplied."""
    m, k, n, sizes = SIZES[case]
    lhs, rhs, group_sizes, dout = _operands(m, k, n, sizes, jnp.float32)
    total = sum(sizes)
    last = -(-total // R) * R      # the tile that holds the last row is read
    KREG.force_interpret(path == "interpret", ["grouped_matmul"])
    try:
        got = _three(gm.grouped_matmul, lhs.at[last:].set(jnp.nan), rhs,
                     group_sizes, dout.at[last:].set(jnp.nan))
        want = _three(gm.grouped_matmul, lhs, rhs, group_sizes, dout)
    finally:
        KREG.force_interpret(False, ["grouped_matmul"])
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("case", SIZES, ids=list(SIZES))
@pytest.mark.parametrize("for_drhs", [False, True], ids=["rows", "drhs"])
def test_the_visit_list_covers_each_row_once(case, for_drhs):
    m, _, _, sizes = SIZES[case]
    offsets, group, tile, lhs_tile, bounds = (
        np.asarray(a) for a in gm._visits(
            jnp.asarray(sizes, jnp.int32), m, for_drhs=for_drhs))
    tiles = -(-m // R)
    assert len(group) == len(tile) == len(lhs_tile) == tiles + len(sizes) - 1
    working, done = bounds
    assert working <= done <= len(tile)
    owner = np.full(tiles * R, -1)
    for v in range(working):
        rows = np.arange(tile[v] * R, (tile[v] + 1) * R)
        mine = (rows >= offsets[group[v]]) & (rows < offsets[group[v] + 1])
        assert mine.any() or (for_drhs and sizes[group[v]] == 0)
        assert (owner[rows[mine]] == -1).all()
        owner[rows[mine]] = group[v]
        assert lhs_tile[v] == tile[v]
    want = np.repeat(np.arange(len(sizes)), sizes)
    assert (owner[:len(want)] == want).all()
    assert (owner[len(want):] == -1).all()
    # visits of one output tile are consecutive (it is written back once)
    assert (np.diff(tile[:done]) >= 0).all()
    seen = set(group[:working].tolist())
    assert seen == {g for g, s in enumerate(sizes) if s or for_drhs}
    # the tail: every tile past the last row, once (drhs: none); its
    # inputs stay put
    assert sorted(tile[working:done]) == ([] if for_drhs else list(
        range(-(-sum(sizes) // R), tiles)))
    assert (lhs_tile[working:] == lhs_tile[max(working - 1, 0)]).all()
    assert (group[working:] == group[max(working - 1, 0)]).all()
    if not for_drhs:    # an idle visit stays on the last tile written
        assert (tile[done:] == tiles - 1).all()


@pytest.mark.parametrize("m,k,n,want", [
    (R, 128, 128, True),
    (98304, 2688, 1856, True), (98304, 1856, 2688, True),
    (131072, 2048, 1536, True), (131072, 768, 2048, True),
    (R - 8, 2688, 1856, False),        # under one row tile: a decode step
    (16, 128, 128, False),
    (4 * R, 100, 128, False),          # no whole packed sublanes of k
    (4 * R, 128, 72, False),
    (4 * R, 64, 128, False),           # under a lane tile
    (4 * R, 65536, 512, False),        # a row tile of k past VMEM
])
def test_fits_is_a_rule_on_the_shape(m, k, n, want):
    assert gm.fits(m, k, n) is want


def _dispatched(reg):
    return {p: reg.counter_value(f"kernels/dispatch/grouped_matmul_{p}")
            for p in ("pallas", "interpret", "oracle")}


def test_a_shape_that_does_not_fit_takes_the_oracle(interpret):
    lhs, rhs, sizes, _ = _operands(64, 128, 128, [10, 50], jnp.float32)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        got = gm.grouped_matmul(lhs, rhs, sizes)
    assert _dispatched(reg) == {"pallas": 0, "interpret": 0, "oracle": 1}
    assert (np.asarray(got) == np.asarray(_oracle(lhs, rhs, sizes))).all()


def test_the_switch_takes_the_oracle_and_the_counters_say_so(
        interpret, monkeypatch):
    m, k, n, sizes = SIZES["straddle-tail"]
    lhs, rhs, group_sizes, dout = _operands(m, k, n, sizes, jnp.float32)
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        got = _three(gm.grouped_matmul, lhs, rhs, group_sizes, dout)
    assert _dispatched(reg) == {"pallas": 0, "interpret": 0, "oracle": 1}
    for a, b in zip(got, _three(_oracle, lhs, rhs, group_sizes, dout)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_mixed_dtypes_take_the_oracle(interpret):
    lhs, rhs, sizes, _ = _operands(2 * R, 128, 128, [R, R], jnp.float32)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        gm.grouped_matmul(lhs.astype(jnp.bfloat16), rhs, sizes)
    assert _dispatched(reg)["oracle"] == 1


def test_the_kernels_names_in_a_trace(interpret):
    m, k, n, sizes = SIZES["aligned-tail"]
    lhs, rhs, group_sizes, dout = _operands(m, k, n, sizes, jnp.float32)
    text = str(jax.make_jaxpr(
        lambda a, b: _three(gm.grouped_matmul, a, b, group_sizes, dout))(
            lhs, rhs))
    for name in ("moe_grouped_matmul_fwd", "moe_grouped_matmul_dlhs",
                 "moe_grouped_matmul_drhs"):
        assert f"name={name}\n" in text or f"name={name} " in text
