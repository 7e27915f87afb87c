"""``moe_first_dense_layers``, ``moe_ffn_hidden_size`` and the
sequence-wise balance loss of the bias-balanced sigmoid router
(DeepSeek-V3 ``seq_aux``), at small sizes and seeded; a configuration
without them lowers to what it did."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.gpt import gpt_loss_fn
from apex_tpu.transformer.moe import (SharedExpertMoE, SwitchMLP,
                                      compute_routing_sorted,
                                      seq_aux_loss_from_variables,
                                      sequence_balance_loss)

SEQ = 16


def moe_config(**kw):
    return TransformerConfig(**dict(dict(
        hidden_size=32, num_layers=4, num_attention_heads=4,
        ffn_hidden_size=80, vocab_size=64, max_position_embeddings=SEQ,
        compute_dtype=jnp.float32, normalization="rmsnorm",
        activation="swiglu", attention_bias=False,
        position_embedding_type="rope", num_moe_experts=8, moe_top_k=3,
        moe_router_score="sigmoid_bias", moe_routed_scaling_factor=2.0,
        moe_shared_expert_size=24, moe_shared_expert_gated=False,
        use_flash_attention=False, tie_word_embeddings=False), **kw))


def layer_trees(cfg):
    tokens = jnp.zeros((2, SEQ), jnp.int32)
    shapes = jax.eval_shape(lambda: GPTModel(cfg).init(
        jax.random.PRNGKey(0), tokens))
    return shapes["params"]["transformer"]


@pytest.mark.parametrize("first,freq,want", [
    (0, 1, "EEEE"), (1, 1, "DEEE"), (2, 1, "DDEE"), (4, 1, "DDDD"),
    (0, 2, "EDED"), (1, 2, "DEDE"), (1, 3, "DEDD")])
def test_leading_dense_layers(first, freq, want):
    """The first ``moe_first_dense_layers`` layers keep the dense MLP and
    ``moe_layer_freq`` counts from the layer after them."""
    layers = layer_trees(moe_config(moe_first_dense_layers=first,
                                    moe_layer_freq=freq))
    got = "".join("E" if "routed" in layers[f"layer_{i}"]["mlp"] else "D"
                  for i in range(4))
    assert got == want


def test_experts_have_a_width_of_their_own():
    layers = layer_trees(moe_config(moe_first_dense_layers=1,
                                    moe_ffn_hidden_size=12))
    dense, expert = layers["layer_0"]["mlp"], layers["layer_1"]["mlp"]
    assert dense["dense_h_to_4h"]["weight"].shape == (32, 2 * 80)
    assert dense["dense_4h_to_h"]["weight"].shape == (80, 32)
    assert expert["routed"]["experts"]["w1"].shape == (8, 32, 2 * 12)
    assert expert["routed"]["experts"]["w2"].shape == (8, 12, 32)
    assert expert["shared_gate_up"]["weight"].shape == (32, 2 * 24)
    # absent: the dense MLP's width, as before
    plain = layer_trees(moe_config())["layer_0"]["mlp"]
    assert plain["routed"]["experts"]["w2"].shape == (8, 80, 32)


@pytest.mark.parametrize("bad", [
    dict(moe_first_dense_layers=-1),
    dict(moe_first_dense_layers=1, num_moe_experts=None,
         moe_router_score="softmax", moe_shared_expert_size=None),
    dict(moe_first_dense_layers=1, scan_layers=True),
    dict(moe_ffn_hidden_size=0),
    dict(moe_ffn_hidden_size=8, num_moe_experts=None,
         moe_router_score="softmax", moe_shared_expert_size=None),
    dict(moe_seq_aux_loss_coeff=-0.1),
    dict(moe_seq_aux_loss_coeff=0.1, moe_router_score="softmax")])
def test_fields_are_validated(bad):
    with pytest.raises(ValueError):
        moe_config(**bad)


# ---- the sequence-wise balance loss against its formula

E, K, T, B = 8, 3, 10, 3


def written_out(logits):
    """``logits [T, B, E]`` -> the mean over the B sequences of
    ``sum_i f_i P_i``, in float64 with loops."""
    s = 1 / (1 + np.exp(-np.asarray(logits, np.float64)))
    total = 0.0
    for b in range(B):
        f, P = np.zeros(E), np.zeros(E)
        for t in range(T):
            chosen = np.argsort(-s[t, b], kind="stable")[:K]
            f[chosen] += E / (K * T)
            P += s[t, b] / s[t, b].sum() / T
        total += float(f @ P)
    return total / B


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_balance_loss_is_its_formula(seed):
    logits = jnp.asarray(np.random.default_rng(seed).normal(
        size=(T, B, E)) * 2, jnp.float32)
    flat = logits.reshape(T * B, E)       # [s, b] flattened, as SwitchMLP
    routing = compute_routing_sorted(flat, K, None, True,
                                     score_bias=jnp.zeros((E,)))
    got = sequence_balance_loss(routing.probs, routing.chosen, B)
    assert float(got) == pytest.approx(written_out(logits), rel=1e-5)
    # a perfectly even choice reads 1; a random one a little over
    assert 0.9 < float(got) < 1.6


def test_balance_loss_s_gradient_comes_through_the_scores():
    """The counts are integers of a discrete choice: the gradient is the
    mean share's, ``f`` a constant beside it."""
    logits = jnp.asarray(np.random.default_rng(3).normal(size=(T * B, E)),
                         jnp.float32)
    chosen = jnp.tile(jnp.arange(K), (T * B, 1))

    def loss(z):
        return sequence_balance_loss(jax.nn.sigmoid(z), chosen, B)

    def by_hand(z):
        s = jax.nn.sigmoid(z)
        share = (s / s.sum(-1, keepdims=True)).reshape(T, B, E)
        return jnp.mean(jnp.sum(share[..., :K], axis=-1)) * E / K

    np.testing.assert_allclose(jax.grad(loss)(logits),
                               jax.grad(by_hand)(logits), atol=1e-7)
    assert float(jnp.abs(jax.grad(loss)(logits)).max()) > 0


def layer(**kw):
    return SwitchMLP(**dict(dict(
        hidden_size=16, ffn_hidden_size=8, num_experts=E, top_k=K,
        router_score="sigmoid_bias", activation="swiglu",
        dispatch_mode="ragged", compute_dtype=jnp.float32,
        warn_on_dropped_losses=False), **kw))


def test_the_layer_sows_the_loss_over_all_experts_it_routes_over():
    """In the held-share mode too the loss is over all the router's
    experts: the router is whole on every chip."""
    x = jnp.asarray(np.random.default_rng(4).normal(size=(T, B, 16)),
                    jnp.float32)
    whole = layer(seq_aux_loss=True)
    params = {"params": whole.init(jax.random.PRNGKey(0), x)["params"]}
    _, sown = whole.apply(params, x, mutable=["moe_losses"])
    logits = x.reshape(T * B, 16) @ params["params"]["router"]["gate_weight"]
    want = written_out(logits.reshape(T, B, E))
    (got,) = sown["moe_losses"]["seq_aux_loss"]
    assert float(got) == pytest.approx(want, rel=1e-5)
    assert float(seq_aux_loss_from_variables(sown)) == pytest.approx(want,
                                                                     rel=1e-5)
    held = layer(seq_aux_loss=True, local_experts=2, expert_offset=2,
                 capacity_factor=4.0)
    held_params = jax.tree_util.tree_map(lambda a: a, params)
    held_params["params"]["experts"] = jax.tree_util.tree_map(
        lambda w: w[2:4], params["params"]["experts"])
    _, held_sown = held.apply(held_params, x, mutable=["moe_losses"])
    assert float(held_sown["moe_losses"]["seq_aux_loss"][0]) == \
        pytest.approx(want, rel=1e-5)
    assert "held_assignments" in held_sown["moe_losses"]


def test_off_nothing_is_sown_and_the_softmax_router_is_refused():
    x = jnp.ones((T, B, 16))
    off = layer()
    _, sown = off.apply(
        {"params": off.init(jax.random.PRNGKey(0), x)["params"]}, x,
        mutable=["moe_losses"])
    assert "seq_aux_loss" not in sown["moe_losses"]
    assert float(seq_aux_loss_from_variables(sown)) == 0
    with pytest.raises(ValueError, match="sigmoid_bias"):
        layer(seq_aux_loss=True, router_score="softmax").init(
            jax.random.PRNGKey(0), x)


def test_the_shared_expert_layer_passes_the_switch_on():
    x = jnp.ones((T, B, 16))
    both = SharedExpertMoE(
        hidden_size=16, ffn_hidden_size=8, shared_expert_size=12,
        num_experts=E, top_k=K, router_score="sigmoid_bias",
        shared_expert_gated=False, dispatch_mode="ragged",
        compute_dtype=jnp.float32, seq_aux_loss=True,
        warn_on_dropped_losses=False)
    _, sown = both.apply(
        {"params": both.init(jax.random.PRNGKey(0), x)["params"]}, x,
        mutable=["moe_losses"])
    assert "seq_aux_loss" in sown["moe_losses"]["routed"]


def test_the_model_s_loss_takes_the_coefficient():
    cfg = moe_config(num_layers=3, moe_first_dense_layers=1,
                     moe_seq_aux_loss_coeff=0.01)
    model = GPTModel(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    _, sown = model.apply({"params": params}, tokens,
                          mutable=["moe_losses"])
    total = float(seq_aux_loss_from_variables(sown))
    assert 1.8 < total < 3.2         # two expert layers, about 1 each
    layers = sown["moe_losses"]["transformer"]
    assert "layer_0" not in layers and "seq_aux_loss" in \
        layers["layer_1"]["mlp"]["routed"]


# ---- Nemotron-H's configuration lowers to what it did

# sha256 of a three-layer ``MEx`` Nemotron-H configuration's loss and
# gradient as a jaxpr (source lines and addresses stripped), read on the
# parent commit of the PR that brought the balance loss (PR 35:
# 4a992719...); read again at PR 36, which changed the held-share path's
# dispatch, combine and window in every configuration (the balance loss
# is off in this one, as before)
NEMOTRON_JAXPR = \
    "b318928789305bad4d2fabed781952222ebe59888fe2c8e8c44aba7d6e506523"


def nemotron_tiny(**kw):
    return TransformerConfig(**dict(dict(
        hidden_size=32, num_layers=3, layer_pattern="ME*",
        num_attention_heads=4, head_dim=8, num_query_groups=1,
        ffn_hidden_size=16, vocab_size=64, max_position_embeddings=16,
        compute_dtype=jnp.float32, normalization="rmsnorm",
        activation="relu2", attention_bias=False,
        position_embedding_type="none", mamba_num_heads=4, mamba_head_dim=8,
        mamba_n_groups=2, mamba_state_size=8, mamba_chunk_size=4,
        num_moe_experts=8, moe_top_k=3, moe_normalize_topk=True,
        moe_router_score="sigmoid_bias", moe_routed_scaling_factor=2.5,
        moe_shared_expert_size=24, moe_shared_expert_gated=False,
        moe_local_experts=2, moe_capacity_factor=4.0,
        use_flash_attention=False, tie_word_embeddings=False,
        activation_checkpointing=True), **kw))


def _jaxpr_digest(cfg):
    model = GPTModel(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]

    def loss(p):
        logits, _ = model.apply({"params": p}, tokens,
                                mutable=["moe_losses"])
        return gpt_loss_fn(logits, tokens)

    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(params))
    text = re.sub(r" at [^\s:]+:\d+", "", text)
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return hashlib.sha256(text.encode()).hexdigest()


def test_at_weight_zero_nemotron_s_jaxpr_is_what_it_was():
    assert nemotron_tiny().moe_seq_aux_loss_coeff == 0.0
    assert _jaxpr_digest(nemotron_tiny()) == NEMOTRON_JAXPR


def test_with_a_weight_the_jaxpr_differs():
    assert _jaxpr_digest(nemotron_tiny(moe_seq_aux_loss_coeff=0.001)) != \
        NEMOTRON_JAXPR
