"""``TransformerConfig.layer_pattern``: one sub-block a layer, by its
letter; without it the model is what it was."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig

SEQ = 16


def hybrid(pattern="MEMEM*EME", **kw):
    return TransformerConfig(**dict(dict(
        hidden_size=32, num_layers=len(pattern), num_attention_heads=2,
        head_dim=16, num_query_groups=1, ffn_hidden_size=16, vocab_size=64,
        max_position_embeddings=SEQ, compute_dtype=jnp.float32,
        use_flash_attention=False, normalization="rmsnorm",
        activation="relu2", attention_bias=False,
        position_embedding_type="none", layer_pattern=pattern,
        mamba_num_heads=4, mamba_head_dim=8, mamba_n_groups=2,
        mamba_state_size=8, mamba_chunk_size=8, num_moe_experts=8,
        moe_top_k=2, moe_local_experts=4, moe_capacity_factor=2.0,
        moe_router_score="sigmoid_bias", moe_routed_scaling_factor=2.5,
        moe_shared_expert_size=24, moe_shared_expert_gated=False,
        activation_checkpointing=False), **kw))


def tree(cfg):
    tokens = jnp.zeros((2, SEQ), jnp.int32)
    shapes = jax.eval_shape(
        lambda: GPTModel(cfg).init(jax.random.PRNGKey(0), tokens))
    return shapes["params"]


def test_the_published_pattern_builds_4_4_1_sub_blocks():
    layers = tree(hybrid())["transformer"]
    kinds = []
    for i in range(9):
        (sub,) = set(layers[f"layer_{i}"]) - {"input_layernorm"}
        kinds.append(sub)
    assert kinds == ["mixer", "mlp", "mixer", "mlp", "mixer",
                     "self_attention", "mlp", "mixer", "mlp"]
    assert (kinds.count("mixer"), kinds.count("mlp"),
            kinds.count("self_attention")) == (4, 4, 1)
    assert set(layers["layer_1"]["mlp"]) == {"routed", "shared_up",
                                            "shared_down"}
    assert set(layers["layer_1"]["mlp"]["routed"]["router"]) == {
        "gate_weight", "e_score_correction_bias"}
    assert layers["layer_1"]["mlp"]["routed"]["experts"]["w1"].shape == \
        (4, 32, 16)
    assert set(layers["layer_5"]["self_attention"]) == {"query_key_value",
                                                        "dense"}


def test_no_positional_encoding_has_no_position_table():
    assert "position_embeddings" not in tree(hybrid("M*"))
    assert "position_embeddings" in tree(
        hybrid("M*", position_embedding_type="learned"))


# sha256 over the sorted (path, shape) of a 2-layer GPT-2 config's tree,
# read on the parent commit of the PR that brought ``layer_pattern``
GPT2_TREE = "e65a6a618277092d"


def _digest(params):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    text = ";".join(f"{jax.tree_util.keystr(k)}{v.shape}{v.dtype}"
                    for k, v in sorted(flat, key=lambda kv: str(kv[0])))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_without_a_pattern_the_tree_is_today_s():
    cfg = TransformerConfig(hidden_size=32, num_layers=2,
                            num_attention_heads=2, vocab_size=64,
                            max_position_embeddings=SEQ,
                            tie_word_embeddings=True)
    params = tree(cfg)
    assert set(params["transformer"]["layer_0"]) == {
        "input_layernorm", "self_attention", "post_attention_layernorm",
        "mlp"}
    assert set(params) == {"word_embeddings", "position_embeddings",
                           "transformer", "final_layernorm"}
    assert _digest(params) == GPT2_TREE


@pytest.mark.parametrize("bad,match", [
    (dict(layer_pattern="ME"), "num_layers"),
    (dict(layer_pattern="MEMEMxEME"), "letters"),
    (dict(layer_pattern="MEMEM-EME"), "letters"),
    (dict(scan_layers=True), "scan_layers"),
    (dict(num_moe_experts=None, moe_local_experts=None), "num_moe_experts"),
    (dict(mamba_n_groups=3), "multiple"),
    (dict(moe_router_score="tanh"), "moe_router_score"),
    (dict(position_embedding_type="sinusoid"), "position_embedding_type"),
])
def test_what_the_config_refuses(bad, match):
    with pytest.raises(ValueError, match=match):
        hybrid(**bad)


def test_attention_without_positions_sees_no_order():
    """A ``*`` layer alone under ``position_embedding_type="none"``: the
    last position's output does not change when earlier tokens swap
    places; with learned positions it does."""
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 64, (1, SEQ)), jnp.int32)
    swapped = tokens.at[0, 2].set(tokens[0, 9]).at[0, 9].set(tokens[0, 2])
    assert int(tokens[0, 2]) != int(tokens[0, 9])

    def last_logits(cfg):
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(1), tokens)["params"]
        return (model.apply({"params": params}, tokens)[0, -1],
                model.apply({"params": params}, swapped)[0, -1])

    a, b = last_logits(hybrid("*"))
    np.testing.assert_allclose(a, b, atol=1e-6)
    a, b = last_logits(hybrid("*", position_embedding_type="learned"))
    assert float(jnp.abs(a - b).max()) > 1e-4


def test_a_mamba_layer_sees_the_order():
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 64, (1, SEQ)), jnp.int32)
    swapped = tokens.at[0, 2].set(tokens[0, 9]).at[0, 9].set(tokens[0, 2])
    model = GPTModel(hybrid("M"))
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    a = model.apply({"params": params}, tokens)[0, -1]
    b = model.apply({"params": params}, swapped)[0, -1]
    assert float(jnp.abs(a - b).max()) > 1e-5


_LOSSES = {}    # recomputation on or off -> the step's loss


@pytest.mark.parametrize("remat", [False, True])
def test_the_hybrid_trains_a_step(remat):
    """Loss and a finite gradient for every parameter but the router's
    bias, which no gradient reaches; recomputation changes no number."""
    from apex_tpu.models.gpt import gpt_loss_fn

    cfg = hybrid(activation_checkpointing=remat)
    model = GPTModel(cfg)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, 64, (2, SEQ)), jnp.int32)
    params = model.init(jax.random.PRNGKey(2), tokens)["params"]

    def loss(p):
        logits, sown = model.apply({"params": p}, tokens,
                                   mutable=["moe_losses"])
        return gpt_loss_fn(logits, tokens), sown

    (value, sown), grads = jax.value_and_grad(loss, has_aux=True)(params)
    assert np.isfinite(float(value))
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:
            assert float(jnp.abs(g).max()) == 0, name
        else:
            assert float(jnp.abs(g).max()) > 0, name
    held = sown["moe_losses"]["transformer"]["layer_1"]["mlp"]["routed"]
    assert {"held_assignments", "held_load_max_over_mean",
            "held_dropped_fraction"} <= set(held)
    _LOSSES[remat] = float(value)
    if len(_LOSSES) == 2:
        assert _LOSSES[False] == pytest.approx(_LOSSES[True], rel=1e-6)
