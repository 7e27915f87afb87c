"""The indexer's score kernels (``kernels/indexer_scores.py``) in the
Pallas interpreter against their jnp oracle: the scores on every element
(float32 operands, so that only the order of the sum over the heads
differs), zeros above the diagonal on both sides, ``dq``, ``dk`` and
``dw`` against ``jax.grad`` of the oracle; the rule that sends a shape
that does not fit, or ``APEX_TPU_KERNELS=0``, to the oracle; the dispatch
counter and the tile gauges; and ``SparseIndexer`` giving the same scores,
selection and gradients on either path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.kernels import indexer_scores
from apex_tpu.kernels import registry as kreg
from apex_tpu.models import transformer_lm
from apex_tpu.models.transformer_lm import (
    SparseIndexer,
    TransformerConfig,
    index_scores,
)
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

KREG = kreg.get_kernel_registry()
# float32 sums of the same products in another order
RTOL = 1e-5
# the kernels round the backward products' operands to bf16, as the MXU
# takes them; the oracle's products on the CPU do not
BF16_RTOL = 2e-2


@pytest.fixture
def interpret():
    KREG.force_interpret(True, ["indexer_scores"])
    yield
    KREG.force_interpret(False, ["indexer_scores"])


def _operands(b, s, heads, dim, key=0):
    kq, kk, kw = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(kq, (b, s, heads * dim)),
            jax.random.normal(kk, (b, s, dim)),
            jax.random.normal(kw, (b, s, heads)) * 0.1)


def _close(got, want, rtol=RTOL):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol,
                               atol=rtol * float(jnp.abs(want).max()))


# (b, s, heads, dim, block_q, block_k): several q and kv tiles; square
# tiles, whose diagonal tiles the diagonal cuts corner to corner, and
# oblong ones, whose diagonal tiles it cuts part of the way; 16 heads of
# 64 (the Keye cell's) and fewer, of 64 and of 128
CASES = [(2, 384, 16, 64, 128, 128), (1, 512, 4, 64, 128, 256),
         (1, 512, 2, 128, 256, 128), (2, 256, 2, 64, 128, 128)]
IDS = [f"b{b}-s{s}-{h}x{d}-tiles{bq}x{bk}" for b, s, h, d, bq, bk in CASES]


def _tiles(heads, block_q, block_k):
    return indexer_scores._Tiles(heads, block_q, block_k, True)


@pytest.mark.parametrize("b,s,heads,dim,block_q,block_k", CASES, ids=IDS)
def test_the_scores_are_the_oracles_on_every_element(b, s, heads, dim,
                                                     block_q, block_k):
    q, k, w = _operands(b, s, heads, dim)
    got = indexer_scores._scores(q, k, w, _tiles(heads, block_q, block_k))
    want = transformer_lm._index_scores_oracle(q, k, w)
    assert got.shape == want.shape == (b, s, s)
    assert got.dtype == want.dtype == jnp.float32
    _close(got, want)
    above = np.triu(np.ones((s, s), bool), 1)
    assert (np.asarray(got)[:, above] == 0).all()
    assert (np.asarray(want)[:, above] == 0).all()
    # the relu leaves some causal pairs at zero and most above it
    below = np.asarray(got)[:, ~above]
    assert 0 < (below != 0).mean() < 1


@pytest.mark.parametrize("b,s,heads,dim,block_q,block_k", CASES, ids=IDS)
def test_dq_dk_dw_are_the_oracles_gradients(b, s, heads, dim, block_q,
                                            block_k):
    q, k, w = _operands(b, s, heads, dim, key=1)
    # a cotangent above the diagonal too: nothing may flow from there
    d_scores = jax.random.normal(jax.random.PRNGKey(2), (b, s, s))
    tiles = _tiles(heads, block_q, block_k)
    _, vjp = jax.vjp(lambda *a: indexer_scores._scores(*a, tiles), q, k, w)
    _, want_vjp = jax.vjp(transformer_lm._index_scores_oracle, q, k, w)
    for got, want in zip(vjp(d_scores), want_vjp(d_scores)):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert float(jnp.abs(want).max()) > 0
        _close(got, want)


def test_the_public_entry_counts_its_path_and_its_tiles(interpret):
    b, s = 2, 384            # 128-tiles: 3 x 3 a sequence, 6 of them causal
    q, k, w = _operands(b, s, 4, 64)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        got = jax.grad(lambda q: jnp.sum(index_scores(q, k, w) ** 2))(q)
    assert reg.counter_value("kernels/dispatch/indexer_scores_interpret") == 1
    assert reg.counter_value("kernels/dispatch/indexer_scores_oracle") == 0
    gauges = reg.snapshot()["gauges"]
    assert gauges["indexer/scores_tiles_run"] == b * 6
    assert gauges["indexer/scores_tiles_skipped"] == b * 3
    want = jax.grad(lambda q: jnp.sum(
        transformer_lm._index_scores_oracle(q, k, w) ** 2))(q)
    _close(got, want)


def test_the_switch_gives_the_oracle(interpret, monkeypatch):
    q, k, w = _operands(1, 256, 2, 64)
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        got = index_scores(q, k, w)
    assert reg.counter_value("kernels/dispatch/indexer_scores_oracle") == 1
    assert reg.counter_value("kernels/dispatch/indexer_scores_interpret") == 0
    assert "indexer/scores_tiles_run" not in reg.snapshot()["gauges"]
    np.testing.assert_array_equal(
        got, transformer_lm._index_scores_oracle(q, k, w))


# (q's shape, heads) that the kernels refuse and the oracle runs
REFUSED = [((2, 200, 128), 2),      # no tile side divides the sequence
           ((2, 256, 96), 3),       # heads of 32 lanes
           ((2, 256, 192), 1),      # a head of 192 lanes
           ((2, 256, 64), 1)]       # one head of 64: half a lane column


@pytest.mark.parametrize("shape,heads", REFUSED,
                         ids=lambda x: "x".join(map(str, x))
                         if isinstance(x, tuple) else f"{x}heads")
def test_a_shape_that_does_not_fit_takes_and_counts_the_oracle(
        interpret, shape, heads):
    assert not indexer_scores.fits(shape, heads)
    b, s, width = shape
    q, k, w = _operands(b, s, heads, width // heads)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        got = index_scores(q, k, w)
    assert reg.counter_value("kernels/dispatch/indexer_scores_oracle") == 1
    assert reg.counter_value("kernels/dispatch/indexer_scores_interpret") == 0
    np.testing.assert_array_equal(
        got, transformer_lm._index_scores_oracle(q, k, w))


def test_fits_asks_the_shapes_alone():
    assert indexer_scores.fits((2, 8192, 1024), 16)       # the Keye cell
    assert indexer_scores.fits((1, 128, 128), 2)
    assert indexer_scores.fits((1, 384, 512), 2)          # heads of 256
    assert not indexer_scores.fits((2, 256, 128), 3)      # 3 into 128 lanes
    assert not indexer_scores.fits((256, 128), 2)         # no batch
    # a walk's VMEM past the budget: 64 heads of 256 lanes
    assert not indexer_scores.fits((1, 8192, 64 * 256), 64)


def _indexer(dtype):
    cfg = TransformerConfig(
        hidden_size=64, num_layers=1, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=384,
        position_embedding_type="rope", indexer_heads=2,
        indexer_head_dim=64, indexer_topk=48, compute_dtype=dtype)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (384, 2, 64))
    module = SparseIndexer(cfg)
    params = module.init(jax.random.PRNGKey(2), hidden)["params"]
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(3), (2, 384, 384)), axis=-1)

    def run(params):
        def both(m):
            scores, selection = m(hidden)
            return m.loss(scores, selection,
                          probs * (selection != 0)), (scores, selection)

        (loss, out), _ = module.apply(
            {"params": params}, method=both, mutable=["moe_losses"])
        return loss, out

    return params, run


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, RTOL),
                                        (jnp.bfloat16, BF16_RTOL)],
                         ids=["float32", "bfloat16"])
def test_sparse_indexer_is_the_same_on_the_kernel_path(interpret,
                                                       monkeypatch, dtype,
                                                       rtol):
    params, run = _indexer(dtype)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        (loss, (scores, sel)), grads = jax.value_and_grad(
            run, has_aux=True)(params)
    assert reg.counter_value("kernels/dispatch/indexer_scores_interpret") >= 1
    assert reg.counter_value("kernels/dispatch/indexer_scores_oracle") == 0
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        (want_loss, (want_scores, want_sel)), want_grads = \
            jax.value_and_grad(run, has_aux=True)(params)
    assert reg.counter_value("kernels/dispatch/indexer_scores_interpret") == 0
    _close(scores, want_scores)
    assert (np.asarray(sel) == np.asarray(want_sel)).all()
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=RTOL)
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.abs(want).max()) > 0
        _close(got, want, rtol)
