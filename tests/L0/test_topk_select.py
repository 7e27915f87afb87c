"""The indexer's top-k selection kernel (``kernels/topk_select.py``) in the
Pallas interpreter against its jnp oracle, **bit for bit**; the rule that
sends a shape that does not fit to the oracle; and ``SparseIndexer`` giving
the same selection and gradients on either path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.kernels import registry as kreg
from apex_tpu.kernels import topk_select
from apex_tpu.models import transformer_lm
from apex_tpu.models.transformer_lm import (
    SparseIndexer,
    TransformerConfig,
    topk_selection,
)
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

KREG = kreg.get_kernel_registry()


@pytest.fixture
def interpret():
    KREG.force_interpret(True, ["topk_select"])
    yield
    KREG.force_interpret(False, ["topk_select"])


def _plain(b, s, key):
    return jax.random.normal(jax.random.PRNGKey(key), (b, s, s))


def _ties(b, s, key):
    # eight distinct values a row: the threshold always sits in a tie
    return jnp.round(_plain(b, s, key) * 2) / 2


def _specials(b, s, key):
    """Negative scores, ``-0.0`` beside ``+0.0``, both infinities and a
    denormal, in columns every row of the second block reaches."""
    x = -jnp.abs(_plain(b, s, key))
    x = x.at[:, :, 3].set(-0.0).at[:, :, 4].set(0.0)
    x = x.at[:, :, 9].set(jnp.inf).at[:, :, 10].set(-jnp.inf)
    return x.at[:, :, 11].set(-1e-40).at[0, :, 12:40].set(0.0)


# (scores, b, s, topk): s in {256, 1024}; topk below, equal to and above s;
# a first block wholly below topk (128, 256, 300, 1024, 2000) and one it
# cuts (40, 200); row blocks with different causal limits (every case has
# at least two); b > 1
CASES = [
    (_plain, 2, 256, 40), (_plain, 1, 256, 128), (_plain, 2, 256, 256),
    (_plain, 1, 256, 300), (_plain, 2, 1024, 200), (_plain, 1, 1024, 1024),
    (_plain, 1, 1024, 2000), (_ties, 2, 256, 40), (_ties, 1, 1024, 300),
    (_specials, 2, 256, 40), (_specials, 2, 256, 150),
    (_specials, 1, 1024, 5),
]


@pytest.mark.parametrize(
    "make,b,s,topk", CASES,
    ids=[f"{m.__name__[1:]}-b{b}-s{s}-top{k}" for m, b, s, k in CASES])
def test_the_kernel_gives_the_oracles_selection_bit_for_bit(
        interpret, make, b, s, topk):
    scores = make(b, s, s + topk)
    want = transformer_lm._topk_selection_oracle(scores, topk)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        got = topk_selection(scores, topk)
    assert reg.counter_value("kernels/dispatch/topk_select_interpret") == 1
    assert got.dtype == want.dtype == jnp.int8
    assert (np.asarray(got) == np.asarray(want)).all()
    # what the oracle promises: rows below topk fully causal, and at least
    # topk keys elsewhere (more only where scores tie with the threshold)
    count = np.asarray(got, np.int32).sum(-1)
    t = np.arange(s)
    assert (count >= np.minimum(t + 1, topk)).all()
    if make is _plain:
        assert (count == np.minimum(t + 1, topk)).all()


@pytest.mark.parametrize("shape", [(2, 192, 192), (1, 100, 100),
                                   (3, 48, 48)])
def test_a_shape_that_does_not_fit_takes_and_counts_the_oracle(interpret,
                                                               shape):
    assert not topk_select.fits(shape)
    scores = jax.random.normal(jax.random.PRNGKey(0), shape)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        got = topk_selection(scores, 17)
    assert reg.counter_value("kernels/dispatch/topk_select_oracle") == 1
    assert reg.counter_value("kernels/dispatch/topk_select_interpret") == 0
    assert (np.asarray(got) == np.asarray(
        transformer_lm._topk_selection_oracle(scores, 17))).all()


def test_fits_asks_the_shape_alone():
    assert topk_select.fits((2, 8192, 8192))
    assert topk_select.fits((1, 128, 128))
    assert not topk_select.fits((1, 128, 256))
    # a row block of a 128k-token sequence is past the VMEM budget
    assert not topk_select.fits((1, 131072, 131072))


def _indexer():
    cfg = TransformerConfig(
        hidden_size=64, num_layers=1, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=256,
        position_embedding_type="rope", indexer_heads=4,
        indexer_head_dim=16, indexer_topk=48)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (256, 2, 64))
    module = SparseIndexer(cfg)
    params = module.init(jax.random.PRNGKey(2), hidden)["params"]
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(3), (2, 256, 256)), axis=-1)

    def run(params):
        def both(m):
            scores, selection = m(hidden)
            return m.loss(scores, selection,
                          probs * (selection != 0)), selection

        (loss, selection), _ = module.apply(
            {"params": params}, method=both, mutable=["moe_losses"])
        return loss, selection

    return params, run


def test_sparse_indexer_is_the_same_on_the_kernel_path(interpret,
                                                       monkeypatch):
    params, run = _indexer()
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        (loss, sel), grads = jax.value_and_grad(run, has_aux=True)(params)
    assert reg.counter_value("kernels/dispatch/topk_select_interpret") >= 1
    assert reg.counter_value("kernels/dispatch/topk_select_oracle") == 0
    # the one switch gives the oracle, the parent's lowering
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        (want_loss, want_sel), want_grads = jax.value_and_grad(
            run, has_aux=True)(params)
    assert reg.counter_value("kernels/dispatch/topk_select_interpret") == 0
    assert (np.asarray(sel) == np.asarray(want_sel)).all()
    assert float(loss) == float(want_loss)
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_array_equal(got, want)
