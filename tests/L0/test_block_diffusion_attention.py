"""The block-diffusion rule of the flash kernels (``contrib/fmha.py``
``_BlockDiffusion``; kernels ``blockdiff_attention_flash_{fwd,dq,dkv}``)
in interpret mode: a row of ``2L`` that holds ``L`` clean tokens and their
``L`` noised copies, the rule worked out from positions inside the kernels.
Against the boolean-mask oracle (``block_diffusion_mask``), forward and all
three gradients, through the batch-major entry (what 32 heads of 128 take)
and the head-major one; the tile counts of the dispatch record; the index
maps (no skipped tile is fetched); and what the rule means."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib import fmha
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

N, D = 2, 128


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(fmha.GATE, "interpret", True)


def _normal(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def _head_major(bl, block):
    def entry(q, k, v):
        return fmha.flash_attention(q, k, v, False, None, block, block,
                                    block_diffusion=bl)
    return entry


def _batch_major(bl, block):
    def entry(q, k, v):
        return fmha._to_head_major(fmha.flash_attention_bsnd(
            *(fmha._to_batch_major(x) for x in (q, k, v)), N, False, None,
            block, block, block_diffusion=bl), N)
    return entry


ENTRIES = {"bsnd": _batch_major, "bnsd": _head_major}


def _oracle(L, bl):
    rule = fmha._BlockDiffusion(L, bl)

    def oracle(q, k, v):
        return fmha._attention_reference(q, k, v, D ** -0.5, False,
                                         rule=rule)
    return oracle


def _with_gradients(f, operands, g):
    out, vjp = jax.vjp(f, *operands)
    return (out, *vjp(g))


def _records(f, *operands):
    reg = MetricsRegistry(enabled=True)
    seen = {}
    reg.add_event_tap(lambda rec: rec["kind"] == "kernel"
                      and seen.update({rec["kernel"]: rec}))
    with use_registry(reg):
        jax.eval_shape(f, *operands)
    return seen, reg.snapshot()


def _direct_count(L, bl, bq, bk):
    """(tiles that hold a visible pair, visible pairs) by looking at every
    pair of the mask."""
    mask = fmha.block_diffusion_mask(L, bl)
    tiles = mask.reshape(2 * L // bq, bq, 2 * L // bk, bk).any(axis=(1, 3))
    return tiles, int(mask.sum())


# --------------------------------------------------------- the mask itself

def test_the_mask_is_the_three_lines_of_the_rule():
    L, bl = 12, 4
    mask = fmha.block_diffusion_mask(L, bl)
    for i in range(2 * L):
        for j in range(2 * L):
            ci, cj = i < L, j < L
            bi, bj = (i % L) // bl, (j % L) // bl
            want = ((ci and cj and bj <= bi) or (not ci and cj and bj < bi)
                    or (not ci and not cj and bj == bi))
            assert mask[i, j] == want, (i, j)
    # a clean query never sees a noisy key; no row is empty
    assert not mask[:L, L:].any() and mask.any(axis=1).all()


# ------------------------------------------------- kernels against the oracle

@pytest.mark.parametrize("entry", sorted(ENTRIES))
@pytest.mark.parametrize("block", [128, 256, 512])
@pytest.mark.parametrize("bl", [4, 32])
@pytest.mark.parametrize("L", [256, 1024])
def test_the_kernels_are_the_oracle_forward_and_backward(
        interpret, L, bl, block, entry):
    q, k, v, g = (_normal(i, (1, N, 2 * L, D)) for i in range(4))
    f = ENTRIES[entry](bl, block)
    seen, snap = _records(f, q, k, v)
    rec = seen[fmha.BLOCKDIFF_ENTRY]
    assert rec["path"] == "interpret"
    fitted = min(block, L)
    tiles, visible = _direct_count(L, bl, fitted, fitted)
    assert rec["tiles_diagonal"] == 0
    assert rec["tiles_whole"] == int(tiles.sum())
    assert rec["tiles_skipped"] == int((~tiles).sum())
    assert rec["pairs_visible"] == visible == L * L + L * bl
    assert snap["counters"][
        "kernels/dispatch/flash_attention_blockdiff_interpret"] == 1
    got = _with_gradients(f, (q, k, v), g)
    want = _with_gradients(_oracle(L, bl), (q, k, v), g)
    # float32 operands on both sides; the kernels accumulate tile by tile
    for name, x, y in zip(("out", "dq", "dk", "dv"), got, want, strict=True):
        np.testing.assert_allclose(x, y, rtol=0, atol=3e-5, err_msg=name)


def test_the_kernels_carry_the_rule_s_name(interpret):
    q = jax.ShapeDtypeStruct((1, N, 512, D), jnp.float32)

    def step(q, k, v):
        out, vjp = jax.vjp(_batch_major(4, 128), q, k, v)
        return out, vjp(out)

    text = str(jax.make_jaxpr(step)(q, q, q))
    for which in ("fwd", "dq", "dkv"):
        assert f"name=blockdiff_attention_flash_{which}" in text
    assert "self_attention_flash" not in text
    # the rule is worked out from positions: no [2L, 2L] array anywhere
    assert "512,512]" not in text


# ------------------------------------------------------- tiles and index maps

def test_tile_classes_at_the_cell_s_shape():
    rule = fmha._BlockDiffusion(8192, 4)
    got = fmha._tile_classes(16384, 512, 512, False, None, rule)
    assert got == {"tiles_diagonal": 0, "tiles_whole": 288,
                   "tiles_skipped": 736,
                   "pairs_computed": 288 * 512 * 512,
                   "pairs_visible": 67_141_632}


GRIDS = [(256, 4, 128, 128), (1024, 4, 128, 128), (1024, 32, 256, 256),
         (1024, 4, 512, 512), (1024, 128, 128, 128), (1024, 32, 256, 128),
         (1024, 32, 128, 512), (512, 512, 512, 512)]


@pytest.mark.parametrize("L,bl,bq,bk", GRIDS)
def test_tile_classes_are_a_direct_count(L, bl, bq, bk):
    tiles, visible = _direct_count(L, bl, bq, bk)
    got = fmha._tile_classes(2 * L, bq, bk, False, None,
                             fmha._BlockDiffusion(L, bl))
    assert got["tiles_whole"] == int(tiles.sum())
    assert got["tiles_skipped"] == int((~tiles).sum())
    assert got["pairs_visible"] == visible
    qi, kj = np.ogrid[:2 * L // bq, :2 * L // bk]
    np.testing.assert_array_equal(
        np.asarray(fmha._BlockDiffusion(L, bl).tile_runs(qi, kj, bq, bk)),
        tiles)


@pytest.mark.parametrize("L,bl,bq,bk", GRIDS + [(8192, 4, 512, 512)])
def test_no_skipped_tile_is_fetched(L, bl, bq, bk):
    """Every grid cell of the forward and dq (kv streamed) and of dkv (q
    streamed) fetches a tile that runs; a cell whose own tile runs fetches
    that; and along the streamed axis a row fetches each of its tiles
    once and no other."""
    rule = fmha._BlockDiffusion(L, bl)
    nq, nk = 2 * L // bq, 2 * L // bk
    qi, kj = np.ogrid[:nq, :nk]
    runs = np.broadcast_to(np.asarray(rule.tile_runs(qi, kj, bq, bk)),
                           (nq, nk))
    assert runs.any(axis=1).all() and runs.any(axis=0).all()

    kv = np.asarray(fmha._fetched_kv_block(qi, kj, bq, bk, False, None,
                                           rule))
    assert np.take_along_axis(runs, kv, axis=1).all()
    np.testing.assert_array_equal(kv[runs], np.broadcast_to(kj, (nq, nk))[runs])
    fetches = 1 + (np.diff(kv, axis=1) != 0).sum(axis=1)
    np.testing.assert_array_equal(fetches, runs.sum(axis=1))

    q = np.asarray(fmha._fetched_q_block(kj, qi, bq, bk, False, None, rule))
    assert np.take_along_axis(runs, q, axis=0).all()
    np.testing.assert_array_equal(q[runs], np.broadcast_to(qi, (nq, nk))[runs])
    fetches = 1 + (np.diff(q, axis=0) != 0).sum(axis=0)
    np.testing.assert_array_equal(fetches, runs.sum(axis=0))


# ------------------------------------------------------- what the rule means

@pytest.mark.parametrize("half,touched", [("clean", 3), ("noisy", 3),
                                          ("clean", 0), ("noisy", 7)])
def test_what_the_rule_means(interpret, half, touched):
    """Changing a clean token of block ``b`` moves no noisy row's output in
    blocks ``<= b`` and no clean row before ``b``; changing a noisy token
    of block ``b`` moves the noisy rows of block ``b`` alone."""
    L, bl = 256, 32
    q, k, v = (_normal(i, (1, N, 2 * L, D)) for i in range(3))
    f = _batch_major(bl, 128)
    at = touched * bl + 5 + (L if half == "noisy" else 0)
    k2 = k.at[:, :, at].add(1.0)
    v2 = v.at[:, :, at].add(1.0)
    moved = np.asarray(jnp.any(f(q, k, v) != f(q, k2, v2), axis=(0, 1, 3)))
    block = (np.arange(2 * L) % L) // bl
    noisy = np.arange(2 * L) >= L
    if half == "clean":
        want = np.where(noisy, block > touched, block >= touched)
    else:
        want = noisy & (block == touched)
    np.testing.assert_array_equal(moved, want)


# ------------------------------------------------------------ off the kernels

@pytest.mark.parametrize("L,bl", [(576, 4), (96, 3)])
def test_where_no_block_fits_the_oracle_runs_and_is_counted(interpret, L,
                                                            bl):
    # no block of 512, 256 or 128 divides a half of 576, and it is over
    # 512, so the half itself is no block either; a diffusion block of 3
    # is no power of two (the kernels find a token's block by a shift)
    q, k, v = (_normal(i, (1, N, 2 * L, D)) for i in range(3))
    f = _head_major(bl, 512)
    seen, snap = _records(f, q, k, v)
    assert seen[fmha.BLOCKDIFF_ENTRY]["path"] == "oracle"
    assert snap["counters"][
        "kernels/dispatch/flash_attention_blockdiff_oracle"] == 1
    np.testing.assert_allclose(f(q, k, v), _oracle(L, bl)(q, k, v),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("kw", [
    {"causal": True}, {"window": 64}, {"block_diffusion": 3},
    {"block_diffusion": 0}, {"block_diffusion": True},
    {"selection": jnp.ones((1, 256, 256), jnp.int8)},
    {"alibi_slopes": jnp.ones((N,))}])
def test_the_rule_is_a_rule_of_its_own(kw):
    q = jnp.zeros((1, N, 256, D))
    args = dict(causal=False, window=None, alibi_slopes=None,
                selection=None, block_diffusion=4)
    args.update(kw)
    if args["window"] is not None:
        args["causal"] = True
    with pytest.raises(ValueError):
        fmha.flash_attention(q, q, q, args["causal"], None, 128, 128,
                             args["window"], args["alibi_slopes"],
                             args["selection"], args["block_diffusion"])
