"""The dense flash calls' walk (``contrib/fmha.py`` ``_visit_list``): the
grid's second axis visits the (q tile, kv tile) pairs that run and no
other, q-major for the forward and dq, kv-major for dkv, each row's visits
consecutive and in the order a full grid meets them, with its first and
last visit marked. Under causal (square and non-square blocks, with and
without strips), a window under, equal to and over a block, the
block-diffusion rule and non-causal calls; the dispatch record's
``grid_steps`` at the cells' shapes."""

import numpy as np
import pytest

from apex_tpu.contrib import fmha

# (seq, block_q, block_k, causal, window)
DENSE = [
    (1024, 512, 512, True, None),      # GPT-2: strips on the diagonal
    (8192, 512, 512, True, None),
    (1024, 256, 128, True, None),      # blocks that are not square
    (1024, 128, 256, True, None),
    (2048, 256, 256, True, 100),       # a window under a block
    (2048, 256, 256, True, 256),       # equal to one
    (2048, 256, 256, True, 700),       # over one
    (2048, 128, 256, True, 300),
    (8192, 512, 512, True, 512),       # Laguna's window layers
    (1024, 256, 256, False, None),
    (768, 256, 128, False, None),
]
# (L, diffusion block, block_q, block_k): the rule's tests' grids and the
# cell's shape
RULE = [(256, 4, 128, 128), (1024, 4, 128, 128), (1024, 32, 256, 256),
        (1024, 4, 512, 512), (1024, 128, 128, 128), (1024, 32, 256, 128),
        (1024, 32, 128, 512), (512, 512, 512, 512), (8192, 4, 512, 512)]
CALLS = ([pytest.param(*c, None, id=f"dense-{c}") for c in DENSE]
         + [pytest.param(2 * L, bq, bk, False, None,
                         fmha._BlockDiffusion(L, bl),
                         id=f"rule-{(L, bl, bq, bk)}")
            for L, bl, bq, bk in RULE])


def _runs(seq, bq, bk, causal, window, rule):
    """The tiles that run, by looking at every (query, key) pair."""
    if rule is not None:
        visible = fmha.block_diffusion_mask(*rule)
    else:
        i, j = np.ogrid[:seq, :seq]
        visible = np.ones((seq, seq), bool) if not causal else (
            (i >= j) if window is None else (i >= j) & (i - j < window))
    return visible.reshape(seq // bq, bq, seq // bk, bk).any((1, 3))


def _walk(seq, bq, bk, causal, window, rule, kv_major):
    visits = fmha._visit_list(seq, bq, bk, causal, window, rule, kv_major)
    assert visits.dtype == np.int32
    return fmha._visit(visits, np.arange(visits.size))


@pytest.mark.parametrize("kv_major", [False, True], ids=["q-major",
                                                          "kv-major"])
@pytest.mark.parametrize("seq,bq,bk,causal,window,rule", CALLS)
def test_the_walk_visits_the_tiles_that_run_in_the_full_grid_s_order(
        seq, bq, bk, causal, window, rule, kv_major):
    runs = _runs(seq, bq, bk, causal, window, rule)
    step = _walk(seq, bq, bk, causal, window, rule, kv_major)
    # exactly the tiles _stream_kv_run marks, each once
    marked = fmha._tile_runs(seq, bq, bk, causal, window, rule)
    np.testing.assert_array_equal(marked, runs)
    visited = np.zeros(runs.shape, int)
    np.add.at(visited, (step.qi, step.kj), 1)
    np.testing.assert_array_equal(visited, runs)
    # the order in which a full grid meets them: rows ascending, and
    # within a row the streamed tiles ascending
    row, streamed = (step.kj, step.qi) if kv_major else (step.qi, step.kj)
    order = np.lexsort((streamed, row))
    np.testing.assert_array_equal(order, np.arange(row.size))
    # every q tile and every kv tile has a visit, so every output block
    # is written
    assert np.isin(np.arange(runs.shape[0]), step.qi).all()
    assert np.isin(np.arange(runs.shape[1]), step.kj).all()
    # a row's visits are consecutive, the first and the last marked
    starts = np.flatnonzero(np.r_[True, row[1:] != row[:-1]])
    ends = np.r_[starts[1:] - 1, row.size - 1]
    assert len(np.unique(row)) == starts.size
    np.testing.assert_array_equal(np.flatnonzero(step.first), starts)
    np.testing.assert_array_equal(np.flatnonzero(step.last), ends)


@pytest.mark.parametrize("kv_major", [False, True])
def test_a_call_that_skips_nothing_walks_the_full_grid(kv_major):
    step = _walk(1024, 256, 128, False, None, None, kv_major)
    qi, kj = np.meshgrid(np.arange(4), np.arange(8), indexing="ij")
    if kv_major:
        qi, kj = qi.T, kj.T
    np.testing.assert_array_equal(step.qi, qi.ravel())
    np.testing.assert_array_equal(step.kj, kj.ravel())


@pytest.mark.parametrize("seq,bq,bk,causal,window,rule,steps", [
    pytest.param(8192, 512, 512, True, 512, None, 31, id="window"),
    pytest.param(8192, 512, 512, True, None, None, 136, id="full-causal"),
    pytest.param(16384, 512, 512, False, None, fmha._BlockDiffusion(8192, 4),
                 288, id="block-diffusion"),
    pytest.param(1024, 512, 512, True, None, None, 3, id="gpt2"),
])
def test_grid_steps_at_the_cells_shapes(seq, bq, bk, causal, window, rule,
                                        steps):
    rec = fmha._tile_classes(seq, bq, bk, causal, window, rule)
    assert rec["grid_steps"] == rec["tiles_whole"] + rec["tiles_diagonal"] \
        == steps
    for kv_major in (False, True):
        assert fmha._visit_list(seq, bq, bk, causal, window, rule,
                                kv_major).size == steps
