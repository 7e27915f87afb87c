"""``SwitchMLP``'s held-share mode (``local_experts``): what is dropped,
what is sown, the static row budget, and the router's losses, which are
the uncut layer's."""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.moe import SwitchMLP

H, F, E, K = 32, 16, 8, 2


def _layer(**kw):
    return SwitchMLP(hidden_size=H, ffn_hidden_size=F, num_experts=E,
                     top_k=K, activation="swiglu",
                     compute_dtype=jnp.float32,
                     warn_on_dropped_losses=False, **kw)


def _inputs(tokens=40, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(tokens // 2, 2, H)), jnp.float32)
    params = {
        "router": {"gate_weight": jnp.asarray(
            rng.normal(size=(H, E)) * 0.5, jnp.float32)},
        "experts": {
            "w1": jnp.asarray(rng.normal(size=(E, H, 2 * F)) * 0.1,
                              jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(E, F, H)) * 0.1,
                              jnp.float32)}}
    return x, params


def _share(params, off, n):
    return {"router": params["router"],
            "experts": {k: v[off:off + n]
                        for k, v in params["experts"].items()}}


@pytest.mark.parametrize("off,n", [(0, 2), (2, 2), (4, 4), (0, 8), (7, 1)])
def test_a_share_is_the_dense_sum_over_its_experts(off, n):
    x, params = _inputs()
    got = _layer(local_experts=n, expert_offset=off,
                 capacity_factor=8.0).apply(
        {"params": _share(params, off, n)}, x)
    tokens = x.reshape(-1, H)
    probs = jax.nn.softmax(tokens @ params["router"]["gate_weight"], -1)
    top, idx = jax.lax.top_k(probs, K)
    gates = top / top.sum(-1, keepdims=True)
    want = jnp.zeros_like(tokens)
    for e in range(off, off + n):
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)
        gate, up = jnp.split(tokens @ params["experts"]["w1"][e], 2, -1)
        want = want + g[:, None] * (
            (jax.nn.silu(gate) * up) @ params["experts"]["w2"][e])
    np.testing.assert_allclose(got.reshape(-1, H), want, atol=2e-6)


def test_what_is_sown_beside_the_losses():
    x, params = _inputs()
    _, sown = _layer(local_experts=4, expert_offset=4,
                     capacity_factor=8.0).apply(
        {"params": _share(params, 4, 4)}, x, mutable=["moe_losses"])
    sown = {k[-1]: float(v[0]) for k, v in
            flax.traverse_util.flatten_dict(sown["moe_losses"]).items()}
    tokens = x.reshape(-1, H)
    _, idx = jax.lax.top_k(tokens @ params["router"]["gate_weight"], K)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
    held = counts[4:]
    assert sown["held_assignments"] == pytest.approx(
        held.sum() / counts.sum())
    assert sown["held_load_max_over_mean"] == pytest.approx(
        held.max() / held.mean())
    assert sown["held_dropped_fraction"] == 0.0
    assert sown["aux_loss"] > 0 and "z_loss" in sown


def test_the_row_budget_drops_the_overflow_and_says_so():
    """With a budget under the held assignments the layer still runs,
    drops the last rows of the run and reports the share dropped."""
    x, params = _inputs(tokens=64, seed=1)
    out, sown = _layer(local_experts=4, expert_offset=0,
                       capacity_factor=0.5).apply(
        {"params": _share(params, 0, 4)}, x, mutable=["moe_losses"])
    dropped = float(flax.traverse_util.flatten_dict(
        sown["moe_losses"])[("held_dropped_fraction",)][0])
    assert 0.0 < dropped < 1.0
    assert bool(jnp.isfinite(out).all())


def test_held_share_refuses_other_paths():
    x, params = _inputs()
    with pytest.raises(ValueError, match="held-share"):
        _layer(local_experts=4, dispatch_mode="scatter").apply(
            {"params": _share(params, 0, 4)}, x)


@pytest.mark.parametrize("off,n", [(0, 4), (4, 4), (6, 2)])
def test_router_losses_are_the_uncut_layer_s(off, n):
    """Whichever experts are held, the load-balancing and z losses are
    over all of them and over all the batch's tokens: what the uncut
    ragged layer sows."""
    x, params = _inputs()

    def losses(layer, p):
        _, sown = layer.apply({"params": p}, x, mutable=["moe_losses"])
        flat = flax.traverse_util.flatten_dict(sown["moe_losses"])
        return float(flat[("aux_loss",)][0]), float(flat[("z_loss",)][0])

    whole = losses(_layer(dispatch_mode="ragged"), params)
    share = losses(_layer(local_experts=n, expert_offset=off,
                          capacity_factor=8.0), _share(params, off, n))
    assert share == pytest.approx(whole, rel=1e-6)
    tokens = x.reshape(-1, H)
    probs = jax.nn.softmax(tokens @ params["router"]["gate_weight"], -1)
    _, idx = jax.lax.top_k(probs, K)
    f = np.bincount(np.asarray(idx).ravel(), minlength=E) / idx.size
    assert whole[0] == pytest.approx(
        E * float(np.sum(f * np.asarray(probs.mean(0)))), rel=1e-5)


def test_held_share_gradients_reach_router_and_held_experts():
    x, params = _inputs()
    share = _share(params, 2, 3)
    grads = jax.grad(lambda p: jnp.sum(_layer(
        local_experts=3, expert_offset=2, capacity_factor=8.0).apply(
            {"params": p}, x) ** 2))(share)
    for leaf in jax.tree_util.tree_leaves(grads):
        assert float(jnp.abs(leaf).max()) > 0
