"""The causal flash bodies' tiles on the diagonal in strips
(``contrib/fmha.py``: strips of rows in the forward and in dq where the
blocks are square and there is no window, every other tile whole under
the mask) in interpret mode: the four entries against
``_attention_reference`` at sequences of two and of four blocks, the
shapes whose tiles all run whole, and the tile counts of the dispatch
record against a direct count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib import fmha
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

N, D = 2, 64
BLOCK = 2 * fmha.STRIP      # a diagonal tile of two strips


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(fmha.GATE, "interpret", True)


def _normal(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def _qkvg(s, b=1):
    return tuple(_normal(i, (b, N, s, D)) for i in range(4))


def _with_gradients(f, operands, g):
    out, vjp = jax.vjp(f, *operands)
    return (out, *vjp(g))


def _same(got, want, names):
    for name, x, y in zip(names, got, want, strict=True):
        np.testing.assert_allclose(x, y, rtol=0, atol=2e-5, err_msg=name)


def _records(f, *operands):
    """The dispatch records of one trace of ``f``, by entry."""
    reg = MetricsRegistry(enabled=True)
    seen = {}
    reg.add_event_tap(lambda rec: rec["kind"] == "kernel"
                      and seen.update({rec["kernel"]: rec}))
    with use_registry(reg):
        jax.eval_shape(f, *operands)
    return seen, reg.snapshot()


def _selection(s, emptied):
    """A seeded causal selection, every query keeping its own key; with
    ``emptied`` the second strip of every diagonal tile but the first
    selects nothing of its tile (its queries keep keys of earlier tiles
    alone), so that a strip's visible part is empty in a tile that
    runs."""
    sel = np.tril(np.asarray(
        jax.random.uniform(jax.random.PRNGKey(7), (1, s, s)) < 0.3))
    sel |= np.eye(s, dtype=bool)
    if emptied:
        for t in range(BLOCK, s, BLOCK):
            sel[:, t + fmha.STRIP:t + BLOCK, t:t + BLOCK] = False
            sel[:, t + fmha.STRIP:t + BLOCK, 0] = True
    return jnp.asarray(sel, jnp.int8)


def _head_major(q, k, v):
    return fmha.flash_attention(q, k, v, True, None, BLOCK, BLOCK)


def _batch_major(q, k, v):
    return fmha._to_head_major(fmha.flash_attention_bsnd(
        *(fmha._to_batch_major(x) for x in (q, k, v)), N, True, None, BLOCK,
        BLOCK), N)


@pytest.mark.parametrize("blocks", [2, 4])
@pytest.mark.parametrize("entry,name", [
    (_head_major, "flash_attention"), (_batch_major, "flash_attention_bsnd")])
def test_dense_entries_are_the_reference_over_every_tile_class(
        interpret, entry, name, blocks):
    q, k, v, g = _qkvg(blocks * BLOCK, b=2)
    seen, _ = _records(entry, q, k, v)
    rec = seen[name]
    # tiles in strips, whole tiles under the diagonal and skipped tiles
    # all occur
    assert rec["tiles_diagonal"] == blocks
    assert rec["tiles_whole"] == rec["tiles_skipped"] \
        == blocks * (blocks - 1) // 2
    _same(_with_gradients(entry, (q, k, v), g),
          _with_gradients(lambda q, k, v: fmha._attention_reference(
              q, k, v, D ** -0.5, True), (q, k, v), g),
          ("out", "dq", "dk", "dv"))


@pytest.mark.parametrize("emptied", [False, True])
@pytest.mark.parametrize("blocks", [2, 4])
def test_the_selection_entry_is_the_reference_over_every_tile_class(
        interpret, blocks, emptied):
    s = blocks * BLOCK
    q, k, v, g = _qkvg(s)
    sel = _selection(s, emptied)

    def kernel(q, k, v):
        return fmha.sparse_attention(q, k, v, sel, True, None, BLOCK, BLOCK)

    def oracle(q, k, v):
        scores = fmha._reference_scores(q, k, D ** -0.5, True, selection=sel)
        p = jnp.where(sel[:, None] != 0, jax.nn.softmax(scores, -1), 0.0)
        return (fmha._attention_reference(q, k, v, D ** -0.5, True,
                                          selection=sel),
                jax.lax.stop_gradient(p.sum(1)))

    cotangent = (g, jnp.zeros((1, s, s)))
    _same(jax.tree_util.tree_leaves(_with_gradients(kernel, (q, k, v),
                                                    cotangent)),
          jax.tree_util.tree_leaves(_with_gradients(oracle, (q, k, v),
                                                    cotangent)),
          ("out", "probs", "dq", "dk", "dv"))


@pytest.mark.parametrize("blocks", [2, 4])
def test_the_latent_entry_is_the_reference_over_every_tile_class(
        interpret, blocks):
    """All five gradients, the shared rotary key's among them (every head
    of a cell adds its ``ds^T q^R`` to the one key's rows) and the rotary
    query's, which dq forms strip by strip."""
    s, heads, widths = blocks * BLOCK, 2, (128, 64, 128, 64, 128)
    operands = tuple(
        _normal(10 + i, (1, s, w if i == 3 else heads * w))
        for i, w in enumerate(widths))
    g = _normal(20, (1, s, heads * 128))

    def kernel(*x):
        return fmha.mla_flash_attention(*x, heads, True, BLOCK, BLOCK)

    seen, _ = _records(kernel, *operands)
    assert seen["flash_attention_mla"]["tiles_diagonal"] == blocks
    _same(_with_gradients(kernel, operands, g),
          _with_gradients(lambda *x: fmha.mla_attention_reference(
              *x, heads, True), operands, g),
          ("out", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"))


# sequence, block_q, block_k, window, alibi: square blocks without a window
# run their diagonal tiles in strips (with the alibi bias in them); a
# window, whether its band edge crosses a tile under the diagonal (400,
# 600) or one on it (100), and blocks that are not square send every tile
# through the whole-tile mask
WHOLE = [
    (4 * BLOCK, BLOCK, BLOCK, 400, False),
    (4 * BLOCK, BLOCK, BLOCK, 100, False),
    (4 * BLOCK, BLOCK, BLOCK, 600, True),
    (2 * BLOCK, BLOCK, BLOCK // 2, None, False),
    (2 * BLOCK, BLOCK // 2, BLOCK, None, True),
    (4 * BLOCK, BLOCK // 2, BLOCK, 300, False),
]
STRIPS = [(2 * BLOCK, BLOCK, BLOCK, None, True),
          (4 * BLOCK, 2 * BLOCK, 2 * BLOCK, None, True)]


@pytest.mark.parametrize("batch_major", [False, True])
@pytest.mark.parametrize("s,bq,bk,window,alibi", WHOLE + STRIPS)
def test_windows_alibi_and_blocks_that_are_not_square(
        interpret, s, bq, bk, window, alibi, batch_major):
    q, k, v, g = _qkvg(s)
    slopes = jnp.asarray([0.02, 0.05]) if alibi else None

    def kernel(q, k, v):
        if not batch_major:
            return fmha.flash_attention(q, k, v, True, None, bq, bk, window,
                                        slopes)
        return fmha._to_head_major(fmha.flash_attention_bsnd(
            *(fmha._to_batch_major(x) for x in (q, k, v)), N, True, None,
            bq, bk, window, slopes), N)

    seen, _ = _records(kernel, q, k, v)
    rec = seen["flash_attention_bsnd" if batch_major else "flash_attention"]
    assert (rec["tiles_diagonal"] > 0) == ((s, bq, bk, window, alibi)
                                           in STRIPS)
    assert rec["tiles_whole"] > 0
    _same(_with_gradients(kernel, (q, k, v), g),
          _with_gradients(lambda q, k, v: fmha._attention_reference(
              q, k, v, D ** -0.5, True, window, slopes), (q, k, v), g),
          ("out", "dq", "dk", "dv"))


def _direct_count(s, bq, bk, causal, window):
    """The tiles by what runs them and the pairs, by looking at every
    (query, key) pair."""
    i, j = np.ogrid[:s, :s]
    visible = np.ones((s, s), bool) if not causal else (
        (i >= j) if window is None else (i >= j) & (i - j < window))
    some = visible.reshape(s // bq, bq, s // bk, bk).any((1, 3))
    strips = (causal and bq == bk and window is None
              and bq % fmha.STRIP == 0 and bq > fmha.STRIP)
    diagonal = int(np.trace(some)) if strips else 0
    # a strip's step takes the keys up to the strip's last query: every
    # STRIP x STRIP square of a tile on the diagonal with a visible pair
    n = s // fmha.STRIP if strips else 0
    squares = visible[:n * fmha.STRIP, :n * fmha.STRIP].reshape(
        n, fmha.STRIP, n, fmha.STRIP).any((1, 3))
    in_strips = sum(
        int(squares[t:t + bq // fmha.STRIP, t:t + bq // fmha.STRIP].sum())
        for t in range(0, n, bq // fmha.STRIP)) * fmha.STRIP ** 2
    return {"tiles_diagonal": diagonal,
            "tiles_whole": int(some.sum()) - diagonal,
            "tiles_skipped": int((~some).sum()),
            "pairs_computed": (int(some.sum()) - diagonal) * bq * bk
            + in_strips,
            "pairs_visible": int(visible.sum())}


@pytest.mark.parametrize("s,bq,bk,causal,window", [
    (512, 512, 512, True, None), (1024, 512, 512, True, None),
    (8192, 512, 512, True, None), (8192, 512, 512, False, None),
    (1024, 256, 128, True, None), (2048, 256, 256, True, 700),
    (2048, 512, 512, True, 5000), (192, 512, 512, True, None),
    (512, 128, 128, True, None)])
def test_the_dispatch_record_counts_the_tiles_as_a_direct_count_does(
        interpret, s, bq, bk, causal, window):
    """Each entry's record under its own name: a batch-major call leaves
    ``flash_attention``'s gauges to the head-major calls."""
    want = _direct_count(s, min(bq, s), min(bk, s), causal, window)
    q = jax.ShapeDtypeStruct((1, s, N * D), jnp.float32)
    seen, summary = _records(
        lambda q, k, v: fmha.flash_attention_bsnd(q, k, v, N, causal, None,
                                                  bq, bk, window), q, q, q)
    assert {f: seen["flash_attention_bsnd"][f] for f in want} == want
    assert {f: summary["gauges"][f"kernels/flash_attention_bsnd/{f}"]
            for f in want} == want
    assert not set(want) & set(seen["flash_attention"])
    q = jax.ShapeDtypeStruct((1, N, s, D), jnp.float32)
    seen, summary = _records(
        lambda q, k, v: fmha.flash_attention(q, k, v, causal, None, bq, bk,
                                             window), q, q, q)
    assert {f: seen["flash_attention"][f] for f in want} == want
    assert {f: summary["gauges"][f"kernels/flash_attention/{f}"]
            for f in want} == want


def test_a_gpt2_step_s_record(interpret):
    """2 tiles in strips, 1 whole and 1 skipped a head and kernel at 1024
    positions under the default blocks; the strips take the pairs computed
    from 1.5 times those a query sees to 1.12."""
    q = jax.ShapeDtypeStruct((16, 1024, 1024), jnp.bfloat16)
    seen, _ = _records(
        lambda q, k, v: fmha.flash_attention_bsnd(q, k, v, 16, True), q, q,
        q)
    rec = seen["flash_attention_bsnd"]
    assert (rec["tiles_diagonal"], rec["tiles_whole"],
            rec["tiles_skipped"]) == (2, 1, 1)
    assert rec["pairs_visible"] == 1024 * 1025 // 2
    strips = 512 // fmha.STRIP
    assert rec["pairs_computed"] == (
        512 * 512 + 2 * fmha.STRIP ** 2 * strips * (strips + 1) // 2)
    assert 1.12 < rec["pairs_computed"] / rec["pairs_visible"] < 1.13


def test_the_head_probabilities_record_no_tiles(interpret):
    """``head_summed_probs`` and dkv run whole tiles: the call counts its
    path and says nothing of strips."""
    q = jax.ShapeDtypeStruct((1, N, 2 * BLOCK, D), jnp.float32)
    sel = jax.ShapeDtypeStruct((1, 2 * BLOCK, 2 * BLOCK), jnp.int8)
    seen, _ = _records(
        lambda q, k, sel: fmha.head_summed_probs(q, k, sel, True, None,
                                                 BLOCK, BLOCK), q, q, sel)
    assert seen["flash_attention"]["path"] == "interpret"
    assert "tiles_diagonal" not in seen["flash_attention"]


def test_off_the_kernel_path_the_record_counts_no_tiles():
    q = jax.ShapeDtypeStruct((1, 2, 1000, 64), jnp.float32)   # no block fits
    seen, _ = _records(lambda q, k, v: fmha.flash_attention(q, k, v, True),
                       q, q, q)
    assert seen["flash_attention"]["path"] == "oracle"
    assert "tiles_diagonal" not in seen["flash_attention"]


@pytest.mark.parametrize("bq,bk,causal,window,strips", [
    (512, 512, True, None, 4), (256, 256, True, None, 2),
    (384, 384, True, None, 3), (128, 128, True, None, None),
    (64, 64, True, None, None), (200, 200, True, None, None),
    (512, 256, True, None, None), (512, 512, True, 4096, None),
    (512, 512, False, None, None)])
def test_which_calls_run_their_diagonal_tiles_in_strips(bq, bk, causal,
                                                        window, strips):
    got = fmha._diagonal_strips(bq, bk, causal, window)
    if strips is None:
        assert got is None
    else:
        assert got == [(slice(r, r + fmha.STRIP), slice(0, r + fmha.STRIP))
                       for r in range(0, bq, fmha.STRIP)]
        assert len(got) == strips
