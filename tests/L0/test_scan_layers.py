"""scan_layers: the lax.scan-compiled stack equals the unrolled stack.

The scan path exists for compile time (O(1) in depth vs O(n) for the
unrolled loop — material for the 16-24 layer bench models); numerics must
be identical given the same weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.transformer_lm import (
    ParallelTransformer,
    TransformerConfig,
)
from apex_tpu.transformer import parallel_state


def _cfg(**kw):
    base = dict(hidden_size=32, num_layers=3, num_attention_heads=4,
                vocab_size=64, max_position_embeddings=16,
                compute_dtype=jnp.float32, use_flash_attention=False)
    base.update(kw)
    return TransformerConfig(**base)


def _unrolled_params_from_stacked(stacked, n):
    """layers/layer/<tree> with leading [n] axis -> {layer_i: <tree>}."""
    inner = stacked["layers"]["layer"]
    return {f"layer_{i}": jax.tree_util.tree_map(lambda a, i=i: a[i], inner)
            for i in range(n)}


def test_scan_matches_unrolled_dense():
    parallel_state.destroy_model_parallel()
    x = jnp.asarray(np.random.RandomState(0).randn(8, 2, 32), jnp.float32)
    scan_model = ParallelTransformer(_cfg(scan_layers=True))
    unroll_model = ParallelTransformer(_cfg())

    stacked = scan_model.init(jax.random.PRNGKey(0), x)["params"]
    out_scan = scan_model.apply({"params": stacked}, x)
    unrolled = _unrolled_params_from_stacked(stacked, 3)
    out_unroll = unroll_model.apply({"params": unrolled}, x)
    np.testing.assert_allclose(np.asarray(out_scan), np.asarray(out_unroll),
                               rtol=2e-5, atol=2e-5)


def test_scan_grads_match_unrolled():
    parallel_state.destroy_model_parallel()
    x = jnp.asarray(np.random.RandomState(1).randn(8, 2, 32), jnp.float32)
    scan_model = ParallelTransformer(_cfg(scan_layers=True))
    unroll_model = ParallelTransformer(_cfg())
    stacked = scan_model.init(jax.random.PRNGKey(0), x)["params"]
    unrolled = _unrolled_params_from_stacked(stacked, 3)

    g_scan = jax.grad(
        lambda p: jnp.sum(scan_model.apply({"params": p}, x) ** 2))(stacked)
    g_unroll = jax.grad(
        lambda p: jnp.sum(unroll_model.apply({"params": p}, x) ** 2))(unrolled)
    g_scan_inner = g_scan["layers"]["layer"]
    for i in range(3):
        a = jax.tree_util.tree_map(lambda t, i=i: t[i], g_scan_inner)
        b = g_unroll[f"layer_{i}"]
        for (pa, la), (_, lb) in zip(
                jax.tree_util.tree_leaves_with_path(a),
                jax.tree_util.tree_leaves_with_path(b)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=5e-5, atol=5e-5,
                                       err_msg=f"layer {i} {pa}")


def test_scan_with_moe_collects_losses():
    from apex_tpu.transformer.moe import moe_loss_from_variables

    parallel_state.destroy_model_parallel()
    cfg = _cfg(scan_layers=True, num_moe_experts=2, moe_capacity_factor=4.0)
    x = jnp.asarray(np.random.RandomState(2).randn(8, 2, 32), jnp.float32)
    model = ParallelTransformer(cfg)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    out, mut = model.apply({"params": params}, x, mutable=["moe_losses"])
    total = moe_loss_from_variables(mut, aux_loss_coeff=1.0)
    assert out.shape == x.shape
    assert total.shape == ()
    # every one of the 3 scanned MoE layers contributes ~balanced aux >= 1
    assert float(total) > 2.0


def test_scan_moe_jitter_rng_threaded():
    """nn.scan must forward the 'jitter' rng stream (split per layer) —
    unlisted streams are dropped, which would silently disable jitter."""
    parallel_state.destroy_model_parallel()
    cfg = _cfg(scan_layers=True, num_moe_experts=2, moe_capacity_factor=4.0,
               moe_jitter_eps=0.3)
    x = jnp.asarray(np.random.RandomState(4).randn(8, 2, 32), jnp.float32)
    model = ParallelTransformer(cfg)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    base, _ = model.apply({"params": params}, x, mutable=["moe_losses"])
    jittered, _ = model.apply({"params": params}, x,
                              rngs={"jitter": jax.random.PRNGKey(7)},
                              mutable=["moe_losses"])
    assert not np.allclose(np.asarray(base), np.asarray(jittered))


def test_scan_moe_requires_uniform_stack():
    import pytest

    parallel_state.destroy_model_parallel()
    x = jnp.ones((4, 1, 32))
    with pytest.raises(ValueError, match="uniform"):
        cfg = _cfg(scan_layers=True, num_moe_experts=2, moe_layer_freq=2)
        ParallelTransformer(cfg).init(jax.random.PRNGKey(0), x)


def test_scan_gpt_model_trains():
    from apex_tpu.models import GPTModel
    from apex_tpu.models.gpt import gpt_loss_fn

    parallel_state.destroy_model_parallel()
    cfg = _cfg(scan_layers=True)
    model = GPTModel(cfg)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    def loss_fn(p):
        return gpt_loss_fn(model.apply({"params": p}, tokens),
                           jnp.roll(tokens, -1, axis=-1))

    loss, g = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    assert float(jnp.abs(
        jax.tree_util.tree_leaves(g["transformer"])[0]).sum()) > 0


def test_activation_checkpointing_off_matches_on(rng):
    """cfg.activation_checkpointing only changes the memory/compute
    schedule (VERDICT r1 item 6 MFU lever), never the math: loss and
    grads must match with remat on vs off."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.models.gpt import gpt_loss_fn

    cfg_on = TransformerConfig(
        hidden_size=32, num_layers=2, num_attention_heads=2,
        vocab_size=64, max_position_embeddings=16,
        compute_dtype=jnp.float32, use_flash_attention=False,
        activation_checkpointing=True)
    cfg_off = dataclasses.replace(cfg_on, activation_checkpointing=False)
    tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
    labels = jnp.asarray(rng.randint(0, 64, (2, 16)))
    params = GPTModel(cfg_on).init(jax.random.PRNGKey(0), tokens)["params"]

    def lg(cfg):
        model = GPTModel(cfg)
        return jax.value_and_grad(lambda p: gpt_loss_fn(
            model.apply({"params": p}, tokens), labels))(params)

    loss_on, g_on = lg(cfg_on)
    loss_off, g_off = lg(cfg_off)
    assert float(loss_on) == pytest.approx(float(loss_off), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_on),
                    jax.tree_util.tree_leaves(g_off)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
