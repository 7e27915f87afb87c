"""The batch-major entry of the flash kernels (``contrib/fmha.py``
``flash_attention_bsnd``: q, k, v and the context as ``[b, s, n * d]``,
``lse`` and delta with the sequence in lanes) in interpret mode: against
the oracle and against the head-major entry on the same numbers, what a
checkpointed layer keeps of it, and ``ParallelAttention`` taking it from
the shape of its heads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib import fmha
from apex_tpu.kernels import registry as kreg
from apex_tpu.models import TransformerConfig
from apex_tpu.models.transformer_lm import ParallelTransformer
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry
from apex_tpu.transformer.tensor_parallel import ColumnParallelLinear


@pytest.fixture
def interpret(monkeypatch):
    """The flash kernels in interpret mode, and the model's gate open at
    widths it would refuse on a chip."""
    monkeypatch.setattr(fmha.GATE, "interpret", True)
    monkeypatch.setattr(
        fmha, "dense_layout", lambda s, n, d: (
            "bnsd" if fmha._heads_per_cell(n, d) is None else "bsnd"))
    return monkeypatch


# head size, causal, window, alibi, sequence, block: every block choice
# gives more than one kv block
CASES = [
    (64, True, None, False, 256, 128),
    (64, False, None, False, 256, 128),
    (128, True, None, False, 256, 128),
    (128, False, None, False, 256, 128),
    (64, True, 100, False, 256, 128),
    (64, True, None, True, 256, 128),
    (128, True, 300, True, 1024, 256),
    (64, True, None, False, 1024, 512),
    (64, False, None, False, 1024, 256),
]


@pytest.mark.parametrize("d,causal,window,alibi,s,block", CASES)
def test_batch_major_kernels_match_the_oracle_and_the_head_major_entry(
        interpret, d, causal, window, alibi, s, block):
    b, n = 2, 256 // d
    keys = jax.random.split(jax.random.PRNGKey(s + d), 4)
    q, k, v, g = (jax.random.normal(key, (b, n, s, d), jnp.float32)
                  for key in keys)
    slopes = 0.03 * jnp.arange(1, n + 1, dtype=jnp.float32) if alibi \
        else None

    def head_major(q, k, v):
        return fmha.flash_attention(q, k, v, causal, None, block, block,
                                    window, slopes)

    def batch_major(q, k, v):
        out = fmha.flash_attention_bsnd(
            *(fmha._to_batch_major(x) for x in (q, k, v)), n, causal, None,
            block, block, window, slopes)
        assert out.shape == (b, s, n * d)
        return fmha._to_head_major(out, n)

    def oracle(q, k, v):
        return fmha._attention_reference(q, k, v, d ** -0.5, causal, window,
                                         slopes)

    def with_gradients(f):
        out, vjp = jax.vjp(f, q, k, v)
        return (out, *vjp(g))

    got = with_gradients(batch_major)
    for name, x, same, want in zip(("out", "dq", "dk", "dv"), got,
                                   with_gradients(head_major),
                                   with_gradients(oracle)):
        # the same bodies on the same blocks in the same order
        np.testing.assert_allclose(x, same, rtol=0, atol=1e-6, err_msg=name)
        np.testing.assert_allclose(x, want, rtol=0, atol=2e-5, err_msg=name)


def test_off_the_kernel_path_the_entry_is_the_oracle():
    b, n, s, d = 1, 2, 64, 64
    q, k, v = (jax.random.normal(key, (b, s, n * d), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(3), 3))
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        (out, vjp) = jax.vjp(
            lambda *x: fmha.flash_attention_bsnd(*x, n, True), q, k, v)
        dq, _, _ = vjp(out)
        assert reg.counter_value(
            "kernels/dispatch/flash_attention_bsnd_oracle") == 1
        assert reg.counter_value(
            "kernels/dispatch/flash_attention_oracle") == 1
    want = fmha._attention_reference(
        *(fmha._to_head_major(x, n) for x in (q, k, v)), d ** -0.5, True)
    np.testing.assert_allclose(fmha._to_head_major(out, n), want, atol=1e-6)
    assert dq.shape == q.shape


@pytest.mark.parametrize("heads,width", [(3, 192), (2, 96), (4, 130)])
def test_heads_that_fill_no_128_lane_column_are_refused(heads, width):
    x = jnp.zeros((1, 128, width))
    assert not (width % heads == 0
                and fmha._heads_per_cell(heads, width // heads))
    with pytest.raises(ValueError, match="128-lane columns"):
        fmha.flash_attention_bsnd(x, x, x, heads)


@pytest.mark.parametrize("heads,head_dim,fits", [
    (16, 64, True), (1, 64, False), (3, 128, True), (2, 256, True),
    (4, 32, True), (2, 32, False), (2, 96, False)])
def test_which_heads_fit(heads, head_dim, fits):
    assert (fmha._heads_per_cell(heads, head_dim) is not None) is fits


# (sequence, local heads, head size) -> (flash?, batch-major?): what
# ``transformer_lm._flash_available(seq, head_dim)`` and
# ``fmha.fits_batch_major(heads, head_dim)`` gave on the commit before
# the model's rule moved into ``fmha.dense_layout`` (PR 31), written down
# from that code: flash at whole 128-row tiles and head sizes 64, 128,
# 256; batch-major where the heads fill whole 128-lane columns.
MODEL_RULE = [
    (96, 16, 64, False, False),       # fmha itself would take one block of 96
    (128, 16, 64, True, True),
    (1024, 16, 64, True, True),       # GPT-2 345M
    (1024, 15, 64, True, False),      # an odd number of heads of 64
    (1024, 1, 64, True, False),
    (1024, 4, 128, True, True),
    (8192, 3, 128, True, True),
    (8192, 32, 128, True, True),      # Keye's heads (the indexer keeps it
                                      # head-major; that is the model's)
    (256, 2, 256, True, True),
    (1024, 8, 96, False, False),
    (1024, 4, 32, False, False),      # bsnd takes 4 heads of 32; no model does
    (1024, 16, 512, False, False),
    (640, 16, 64, True, True),        # 128 divides it, 512 and 256 do not
    (200, 16, 64, False, False),
    (64, 2, 64, False, False),
]


@pytest.mark.parametrize("seq,heads,head_dim,flash,batch_major", MODEL_RULE)
def test_the_models_question_has_the_answers_it_had(
        monkeypatch, seq, heads, head_dim, flash, batch_major):
    want = (None if not flash else "bsnd" if batch_major else "bnsd")
    # on a CPU no kernel runs: the model keeps its softmax path
    assert fmha.dense_layout(seq, heads, head_dim) is None
    monkeypatch.setattr(kreg, "_on_tpu", lambda: True)
    assert fmha.dense_layout(seq, heads, head_dim) == want
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    assert fmha.dense_layout(seq, heads, head_dim) is None
    monkeypatch.delenv("APEX_TPU_KERNELS")
    monkeypatch.setattr(kreg, "_on_tpu", lambda: False)
    monkeypatch.setattr(fmha.GATE, "interpret", True)
    assert fmha.dense_layout(seq, heads, head_dim) == want


def test_the_models_question_is_not_counted():
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        fmha.dense_layout(1024, 16, 64)
    assert reg.snapshot()["counters"] == {}


def test_a_direct_call_keeps_the_wider_rule(monkeypatch):
    """96 rows: one block of 96 for ``flash_attention`` itself, the
    softmax path for a model."""
    monkeypatch.setattr(fmha.GATE, "interpret", True)
    assert fmha.dense_layout(96, 2, 64) is None
    q = jnp.ones((1, 2, 96, 64), jnp.float32)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        jax.eval_shape(lambda: fmha.flash_attention(q, q, q))
    assert reg.counter_value(
        "kernels/dispatch/flash_attention_interpret") == 1


def _stack(checkpointing=True, **kw):
    """A float32 two-layer ``ParallelTransformer`` of two heads of 64 (one
    128-lane column), its parameters, input and loss."""
    cfg = dict(hidden_size=128, num_layers=2, num_attention_heads=2,
               vocab_size=128, max_position_embeddings=256,
               compute_dtype=jnp.float32, use_flash_attention=True,
               activation_checkpointing=checkpointing)
    cfg.update(kw)
    stack = ParallelTransformer(TransformerConfig(**cfg))
    hidden = jax.random.normal(jax.random.PRNGKey(1),
                               (256, 2, cfg["hidden_size"]))

    def loss(params, hidden):
        return jnp.sum(stack.apply(params, hidden) ** 2)

    return stack.init(jax.random.PRNGKey(0), hidden), hidden, loss


MODELS = {
    "gpt2": {},
    "rope": dict(position_embedding_type="rope"),
    "gqa-rope-qknorm": dict(num_query_groups=1, qk_norm="head",
                            position_embedding_type="rope"),
    "window": dict(sliding_window=100),
    "alibi": dict(position_embedding_type="alibi"),
    "clip-no-bias": dict(qkv_clip=0.5, attention_bias=False),
}


@pytest.mark.parametrize("model", MODELS)
def test_the_model_takes_the_entry_from_its_heads_and_nothing_else_moves(
        interpret, model):
    """Same parameter tree, same loss and gradients as through the
    head-major call, and the counter of its own says which ran."""
    def run(batch_major):
        if not batch_major:
            interpret.setattr(fmha, "dense_layout", lambda s, n, d: "bnsd")
        params, hidden, loss = _stack(**MODELS[model])
        with use_registry(MetricsRegistry(enabled=True)) as reg:
            value, grads = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1)))(params, hidden)
            return (params, value, grads, reg.counter_value(
                "kernels/dispatch/flash_attention_bsnd_interpret"),
                reg.counter_value(
                    "kernels/dispatch/flash_attention_interpret"))

    params, value, grads, counted, kernels = run(True)
    assert counted >= 2 and kernels == counted
    old_params, old_value, old_grads, counted, kernels = run(False)
    assert counted == 0 and kernels >= 2
    jax.tree_util.tree_map(np.testing.assert_array_equal, params,
                           old_params)
    scale = max(float(jnp.abs(g).max())
                for g in jax.tree_util.tree_leaves(old_grads))
    np.testing.assert_allclose(value, old_value, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=0,
                                                atol=3e-6 * scale),
        grads, old_grads)


def test_heads_of_32_keep_the_head_major_call(interpret):
    """Two heads of 32 fill no column: the call and its counter are the
    head-major ones."""
    params, hidden, loss = _stack(hidden_size=64)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        jax.make_jaxpr(jax.grad(loss))(params, hidden)
        assert reg.counter_value(
            "kernels/dispatch/flash_attention_interpret") >= 2
        assert reg.counter_value(
            "kernels/dispatch/flash_attention_bsnd_interpret") == 0


def _equations(jaxpr):
    from apex_tpu.analysis.rules import _iter_subjaxprs

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _iter_subjaxprs(eqn):
            yield from _equations(sub)


def test_a_checkpointed_layer_keeps_what_the_kernel_wrote(interpret):
    """``flash_out`` ``[b, s, n * d]`` and ``flash_lse`` ``[b, n / c, c,
    s]`` go from the forward kernel to the two backward kernels as they
    are: nothing reshapes, broadcasts, transposes or reduces an array of
    ``lse``'s shape, and the forward kernel runs once a layer."""
    params, hidden, loss = _stack()
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params, hidden).jaxpr
        assert reg.counter_value("remat/save_flash_residuals") == 2
    b, s, n, d = 2, 256, 2, 64
    lse = jax.core.ShapedArray((b, 1, n, s), jnp.float32)
    out = jax.core.ShapedArray((b, s, n * d), jnp.float32)

    def avals(variables):
        return [getattr(x, "aval", None) for x in variables]

    eqns = list(_equations(jaxpr))
    kernels = {}
    for eqn in eqns:
        if eqn.primitive.name == "pallas_call":
            kernels.setdefault(eqn.params["name"], []).append(eqn)
    assert {name: len(calls) for name, calls in kernels.items()} == {
        "self_attention_flash_fwd": 2, "self_attention_flash_dq": 2,
        "self_attention_flash_dkv": 2}
    for call in kernels["self_attention_flash_fwd"]:
        assert avals(call.outvars) == [out, lse]
    for call in kernels["self_attention_flash_dq"]:
        # q, k, v, do, out, lse, slopes -> dq, delta in lse's layout
        assert avals(call.invars)[4:6] == [out, lse]
        assert avals(call.outvars) == [out, lse]
    for call in kernels["self_attention_flash_dkv"]:
        assert avals(call.invars)[4:6] == [lse, lse]
    named = [eqn for eqn in eqns if eqn.primitive.name == "name"]
    assert sorted((eqn.params["name"], eqn.outvars[0].aval.shape)
                  for eqn in named) == sorted(
        2 * [("flash_out", out.shape), ("flash_lse", lse.shape)])
    moved = [eqn.primitive.name for eqn in eqns
             if eqn.primitive.name in (
                 "reshape", "broadcast_in_dim", "squeeze", "transpose",
                 "reduce_sum", "reduce_max", "convert_element_type")
             and lse in avals(eqn.invars) + avals(eqn.outvars)]
    assert moved == []


def test_column_groups_are_matmuls_of_their_own_over_the_same_weight():
    """``ColumnParallelLinear(column_groups=)``: the stored weight and bias
    are the plain call's, and each output is its columns of the plain
    output."""
    layer = ColumnParallelLinear(input_size=32, output_size=96,
                                 gather_output=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 2, 32))
    params = layer.init(jax.random.PRNGKey(1), x)
    params = jax.tree_util.tree_map(
        lambda p: p + jax.random.normal(jax.random.PRNGKey(2), p.shape),
        params)

    def groups(w):   # columns [4, 3, 8] -> three [.., 32]
        w = w.reshape(*w.shape[:-1], 4, 3, 8)
        return tuple(w[..., i, :].reshape(*w.shape[:-3], 32)
                     for i in range(3))

    plain = layer.apply(params, x)
    grouped = layer.apply(params, x, column_groups=groups)
    assert jax.tree_util.tree_structure(
        layer.init(jax.random.PRNGKey(1), x, column_groups=groups)
    ) == jax.tree_util.tree_structure(params)
    for got, want in zip(grouped, groups(plain)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="gather_output=False"):
        ColumnParallelLinear(input_size=32, output_size=96).apply(
            params, x, column_groups=groups)


def test_the_registry_keeps_one_gate_for_both_entries():
    assert "flash_attention_bsnd" not in kreg.get_kernel_registry().names()
