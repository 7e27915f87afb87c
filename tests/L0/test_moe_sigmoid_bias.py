"""The bias-balanced sigmoid router (DeepSeek-V3 / Nemotron-H), the ungated
relu² experts and the unweighted shared expert, against the equations
written out, at a small size and seeded."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.moe import (SharedExpertMoE, SwitchMLP,
                                      compute_routing_sorted)

H, F, FS, E, K, T = 32, 16, 24, 8, 3, 40
SCALE = 2.5


def _logits(seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(T, E)) * 2,
                       jnp.float32)


def written_out(logits, bias, k=K, scale=SCALE):
    """-> (chosen experts ``[T, k]`` sorted, weights ``[T, E]``)."""
    s = 1 / (1 + np.exp(-np.asarray(logits, np.float64)))
    idx = np.argsort(-(s + np.asarray(bias)), axis=-1, kind="stable")[:, :k]
    w = np.zeros_like(s)
    for t in range(s.shape[0]):
        chosen = s[t, idx[t]]
        w[t, idx[t]] = scale * chosen / (chosen.sum() + 1e-20)
    return np.sort(idx, -1), w


def dense_weights(routing):
    """``[T, E]`` gate weights of a sorted routing."""
    w = np.zeros((T, E))
    np.add.at(w, (np.asarray(routing.token_idx),
                  np.asarray(routing.expert_idx)), np.asarray(routing.gate))
    return w


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("bias", ["zero", "tilted"])
def test_routing_is_the_written_out_top_k(seed, bias):
    logits = _logits(seed)
    b = jnp.zeros((E,)) if bias == "zero" else jnp.linspace(-0.4, 0.4, E)
    routing = compute_routing_sorted(logits, K, None, True, score_bias=b,
                                     routed_scaling_factor=SCALE)
    idx, want = written_out(logits, b)
    got = dense_weights(routing)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert np.array_equal(np.sort(np.nonzero(got)[1].reshape(T, K), -1), idx)
    np.testing.assert_allclose(got.sum(-1), SCALE, rtol=1e-6)
    assert float(routing.aux_loss) == 0 and float(routing.z_loss) == 0
    np.testing.assert_allclose(routing.probs, jax.nn.sigmoid(logits))
    assert np.array_equal(np.asarray(routing.counts),
                          np.bincount(idx.ravel(), minlength=E))


def test_a_bias_changes_the_choice_and_not_the_weights():
    logits = _logits(3)
    bias = jnp.zeros((E,)).at[5].set(10.0)         # expert 5 always chosen
    plain = dense_weights(compute_routing_sorted(
        logits, K, None, True, score_bias=jnp.zeros((E,)),
        routed_scaling_factor=SCALE))
    tilted = dense_weights(compute_routing_sorted(
        logits, K, None, True, score_bias=bias,
        routed_scaling_factor=SCALE))
    assert (tilted[:, 5] > 0).all() and not (plain[:, 5] > 0).all()
    # the weights are the unbiased scores of the chosen, over their sum:
    # nothing of the 10 is in them
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = tilted > 0
    want = SCALE * np.where(chosen, s, 0) / np.where(chosen, s, 0).sum(
        -1, keepdims=True)
    np.testing.assert_allclose(tilted, want, atol=1e-6)
    assert tilted.max() <= SCALE


def test_without_normalisation_the_weights_are_the_scores():
    logits = _logits(4)
    got = dense_weights(compute_routing_sorted(
        logits, K, None, False, score_bias=jnp.zeros((E,)),
        routed_scaling_factor=SCALE))
    s = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_allclose(got[got > 0], SCALE * s[got > 0], rtol=1e-6)


def test_no_gradient_reaches_the_bias_and_the_logits_get_theirs():
    logits = _logits(5)

    def total(logits, bias):
        r = compute_routing_sorted(logits, K, None, True, score_bias=bias,
                                   routed_scaling_factor=SCALE)
        return jnp.sum(r.gate * jnp.arange(r.gate.shape[0]))

    dl, db = jax.grad(total, argnums=(0, 1))(logits, jnp.zeros((E,)) + 0.1)
    assert float(jnp.abs(db).max()) == 0 and float(jnp.abs(dl).max()) > 0


def test_the_softmax_router_is_what_it_was():
    """No ``score_bias``: the softmax path, its gates and its losses."""
    logits = _logits(6)
    r = compute_routing_sorted(logits, K, None, True)
    p = np.asarray(jax.nn.softmax(logits))
    top = np.sort(p, -1)[:, -K:]
    np.testing.assert_allclose(dense_weights(r).sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.sort(dense_weights(r), -1)[:, -K:],
                               top / top.sum(-1, keepdims=True), rtol=1e-5)
    assert float(r.aux_loss) > 0


# ------------------------------------------------ experts and shared expert

def _params(seed=0, n=E, off=0):
    rng = np.random.default_rng(seed)
    full = {
        "router": {"gate_weight": rng.normal(size=(H, E)) * 0.5,
                   "e_score_correction_bias": np.linspace(-0.2, 0.2, E)},
        "experts": {"w1": rng.normal(size=(E, H, F)) * 0.2,
                    "w2": rng.normal(size=(E, F, H)) * 0.2},
    }
    shared = {"shared_up": {"weight": rng.normal(size=(H, FS)) * 0.2},
              "shared_down": {"weight": rng.normal(size=(FS, H)) * 0.2}}
    full, shared = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x, jnp.float32), (full, shared))
    held = {"router": full["router"],
            "experts": {k: v[off:off + n]
                        for k, v in full["experts"].items()}}
    x = jnp.asarray(rng.normal(size=(T // 2, 2, H)), jnp.float32)
    return full, held, shared, x


def routed_written_out(full, x, off=0, n=E):
    tokens = np.asarray(x.reshape(-1, H), np.float64)
    logits = tokens @ np.asarray(full["router"]["gate_weight"], np.float64)
    _, w = written_out(logits, full["router"]["e_score_correction_bias"])
    out = np.zeros_like(tokens)
    for e in range(off, off + n):
        up = np.maximum(tokens @ np.asarray(full["experts"]["w1"][e]), 0) ** 2
        out += w[:, e:e + 1] * (up @ np.asarray(full["experts"]["w2"][e]))
    return out


def shared_written_out(shared, x):
    tokens = np.asarray(x.reshape(-1, H), np.float64)
    up = np.maximum(tokens @ np.asarray(shared["shared_up"]["weight"]),
                    0) ** 2
    return up @ np.asarray(shared["shared_down"]["weight"])


def _switch(**kw):
    return SwitchMLP(hidden_size=H, ffn_hidden_size=F, num_experts=E,
                     top_k=K, activation="relu2", compute_dtype=jnp.float32,
                     dispatch_mode="ragged", router_score="sigmoid_bias",
                     routed_scaling_factor=SCALE,
                     warn_on_dropped_losses=False, **kw)


@pytest.mark.parametrize("off,n", [(0, E), (0, 2), (4, 4), (7, 1)])
def test_relu2_experts_under_the_sigmoid_router(off, n):
    full, held, _, x = _params(1, n, off)
    kw = {} if n == E else dict(local_experts=n, expert_offset=off,
                                capacity_factor=float(E / n))
    got, sown = _switch(**kw).apply({"params": held}, x,
                                    mutable=["moe_losses"])
    np.testing.assert_allclose(got.reshape(-1, H),
                               routed_written_out(full, x, off, n),
                               atol=2e-5)
    if n < E:
        (dropped,) = sown["moe_losses"]["held_dropped_fraction"]
        assert float(dropped) == 0


@pytest.mark.parametrize("held", ["all", "half"])
def test_the_shared_expert_is_added_unweighted(held):
    n, off = (E, 0) if held == "all" else (4, 4)
    full, part, shared, x = _params(2, n, off)
    layer = SharedExpertMoE(
        hidden_size=H, ffn_hidden_size=F, shared_expert_size=FS,
        num_experts=E, top_k=K, activation="relu2",
        shared_expert_gated=False, compute_dtype=jnp.float32,
        dispatch_mode="ragged", router_score="sigmoid_bias",
        routed_scaling_factor=SCALE, warn_on_dropped_losses=False,
        **({} if n == E else dict(local_experts=n, expert_offset=off,
                                  capacity_factor=float(E / n))))
    params = dict(shared, routed=part)
    shapes = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
    assert jax.tree_util.tree_map(lambda a: a.shape, shapes["params"]) == \
        jax.tree_util.tree_map(lambda a: a.shape, params)
    got = layer.apply({"params": params}, x)
    want = routed_written_out(full, x, off, n) + shared_written_out(shared, x)
    np.testing.assert_allclose(got.reshape(-1, H), want, atol=3e-5)


def test_the_gated_swiglu_block_is_still_built():
    """The Qwen2-MoE shape keeps its parameters' names."""
    layer = SharedExpertMoE(hidden_size=H, ffn_hidden_size=F,
                            shared_expert_size=FS, num_experts=E, top_k=2,
                            compute_dtype=jnp.float32,
                            warn_on_dropped_losses=False)
    x = jnp.zeros((4, 2, H))
    names = set(jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), x))["params"])
    assert names == {"routed", "shared_gate_up", "shared_down",
                     "shared_expert_gate"}


def test_the_dense_one_hot_format_refuses_the_sigmoid_router():
    layer = _switch().clone(dispatch_mode="einsum")
    _, held, _, x = _params(3)
    with pytest.raises(ValueError, match="sorted"):
        layer.apply({"params": held}, x)


def test_the_router_counts_itself():
    from apex_tpu.telemetry.registry import get_registry

    reg = get_registry()
    was = reg.enabled
    reg.enable()
    try:
        before = reg.counter("moe/router/sigmoid_bias").value
        _, held, _, x = _params(4)
        _switch().apply({"params": held}, x)
        assert reg.counter("moe/router/sigmoid_bias").value == before + 1
    finally:
        if not was:
            reg.disable()
