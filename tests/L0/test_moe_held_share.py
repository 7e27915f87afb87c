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


# -- the grouped matmul under the held share (PR 34): the rows past the
# held run are in no group -------------------------------------------------

from apex_tpu.kernels import registry as kreg  # noqa: E402
from apex_tpu.telemetry.registry import (  # noqa: E402
    MetricsRegistry,
    use_registry,
)
from apex_tpu.transformer.moe import layer as layer_mod  # noqa: E402

KREG = kreg.get_kernel_registry()
# wide enough for the kernel's tiles: 512 gathered rows of 128
HW, FW, TW = 128, 128, 256


def _wide_layer(activation, **kw):
    return SwitchMLP(hidden_size=HW, ffn_hidden_size=FW, num_experts=E,
                     top_k=K, activation=activation,
                     compute_dtype=jnp.float32,
                     warn_on_dropped_losses=False, **kw)


def _wide_inputs(activation, seed=0):
    rng = np.random.default_rng(seed)
    cols = 2 * FW if activation == "swiglu" else FW
    x = jnp.asarray(rng.normal(size=(TW // 2, 2, HW)), jnp.float32)
    return x, {
        "router": {"gate_weight": jnp.asarray(
            rng.normal(size=(HW, E)) * 0.2, jnp.float32)},
        "experts": {
            "w1": jnp.asarray(rng.normal(size=(E, HW, cols)) * 0.1,
                              jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(E, FW, HW)) * 0.1,
                              jnp.float32)}}


def _tail_in_the_last_group(lhs, rhs, group_sizes):
    """The parent's formulation: every row past the held run joins the
    last expert's group and is multiplied by its matrices."""
    tail = lhs.shape[0] - jnp.sum(group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.at[-1].add(tail),
                              preferred_element_type=jnp.float32)


@pytest.fixture(params=["oracle", "interpret"])
def path(request):
    KREG.force_interpret(request.param == "interpret", ["grouped_matmul"])
    yield request.param
    KREG.force_interpret(False, ["grouped_matmul"])


@pytest.mark.parametrize("off,n,factor", [(0, 4, 2.0), (2, 2, 4.0),
                                          (4, 4, 1.0)])
def test_counts_are_the_kept_rows_and_leave_the_tail_out(
        monkeypatch, off, n, factor):
    x, params = _wide_inputs("swiglu")
    seen = []

    def spy(lhs, rhs, group_sizes):
        seen.append((lhs.shape[0], np.asarray(group_sizes)))
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=jnp.float32)

    monkeypatch.setattr(layer_mod, "grouped_matmul", spy)
    _, sown = _wide_layer("swiglu", local_experts=n, expert_offset=off,
                          capacity_factor=factor).apply(
        {"params": _share(params, off, n)}, x, mutable=["moe_losses"])
    tokens = x.reshape(-1, HW)
    _, idx = jax.lax.top_k(tokens @ params["router"]["gate_weight"], K)
    held = np.bincount(np.asarray(idx).ravel(), minlength=E)[off:off + n]
    assert len(seen) == 2 and seen[0][0] == seen[1][0]
    rows, counts = seen[0]
    assert (counts == seen[1][1]).all() and counts.shape == (n,)
    kept = min(int(held.sum()), rows)
    assert counts.sum() == kept
    if held.sum() < rows:       # assignments fewer than the static rows
        assert counts.sum() < rows and (counts == held).all()
    else:                       # the overflow is cut off the last groups
        assert (counts <= held).all()
    dropped = float(flax.traverse_util.flatten_dict(
        sown["moe_losses"])[("held_dropped_fraction",)][0])
    assert dropped == pytest.approx(1.0 - kept / max(held.sum(), 1))


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
def test_the_layer_is_the_parent_s_with_the_tail_in_the_last_group(
        monkeypatch, path, activation):
    """Output, input gradient and every parameter gradient of the
    held-share layer against the same layer with the tail multiplied, as
    before PR 34: on the oracle path and through the kernels."""
    x, params = _wide_inputs(activation)
    share = _share(params, 2, 4)
    layer = _wide_layer(activation, local_experts=4, expert_offset=2,
                        capacity_factor=2.0)

    def out_and_grads():
        def loss(p, inp):
            out = layer.apply({"params": p}, inp)
            return jnp.sum(out * jnp.cos(out)), out

        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            share, x)
        return out, grads

    with use_registry(MetricsRegistry(enabled=True)) as reg:
        got = out_and_grads()
    assert reg.counter_value(
        f"kernels/dispatch/grouped_matmul_{path}") == 2
    assert reg.snapshot()["gauges"]["moe/held_rows"] == 512
    monkeypatch.setattr(layer_mod, "grouped_matmul", _tail_in_the_last_group)
    want = out_and_grads()
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == 5       # out, router, w1, w2, input
    for (where, a), b in zip(flat_got, flat_want):
        assert float(jnp.abs(b).max()) > 0, where
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6,
                                   err_msg=str(where))


@pytest.mark.parametrize("activation", ["swiglu", "relu2", "gelu"])
def test_the_dropless_ragged_mode_is_unchanged(monkeypatch, path,
                                               activation):
    """``dispatch_mode="ragged"`` (no tail: the counts sum to every row)
    gives what ``lax.ragged_dot`` in the expert layer gave."""
    x, params = _wide_inputs(activation)
    if activation == "gelu":
        rng = np.random.default_rng(5)
        params["experts"]["b1"] = jnp.asarray(
            rng.normal(size=(E, FW)) * 0.1, jnp.float32)
        params["experts"]["b2"] = jnp.asarray(
            rng.normal(size=(E, HW)) * 0.1, jnp.float32)
    layer = _wide_layer(activation, dispatch_mode="ragged")
    got = layer.apply({"params": params}, x)
    monkeypatch.setattr(
        layer_mod, "grouped_matmul",
        lambda a, b, s: jax.lax.ragged_dot(
            a, b, s, preferred_element_type=jnp.float32))
    want = layer.apply({"params": params}, x)
    if path == "oracle":
        assert (np.asarray(got) == np.asarray(want)).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# -- the rows' passes follow the count (PR 36): dispatch, combine and the
# window of ``_held_share`` against the parent's formulation ---------------

from apex_tpu.kernels import row_gather  # noqa: E402


def _parent_held_share(self, routing, num_tokens):
    """``SwitchMLP._held_share`` as it was before PR 36: the window of
    the sorted order by three gathers through a clipped ``source``."""
    n, off, E_ = self.local_experts, self.expert_offset, self.num_experts
    N = self.top_k * num_tokens
    rows = min(N, -(-int(N * n / E_ * self.capacity_factor) // 8) * 8)
    ends = jnp.cumsum(routing.counts)
    start = ends[off] - routing.counts[off]
    held = routing.counts[off:off + n]
    local_ends = jnp.minimum(ends[off:off + n] - start, rows)
    counts = jnp.diff(local_ends, prepend=0)
    kept = local_ends[-1]
    row = jnp.arange(rows, dtype=jnp.int32)
    source = jnp.minimum(start + row, N - 1)
    valid = row < kept
    total = jnp.sum(held)
    self.sow("moe_losses", "held_assignments", total / N)
    self.sow("moe_losses", "held_load_max_over_mean",
             jnp.max(held) * n / jnp.maximum(total, 1))
    self.sow("moe_losses", "held_dropped_fraction",
             jax.lax.stop_gradient(1.0 - kept / jnp.maximum(total, 1)))
    return (routing.token_idx[source],
            jnp.clip(routing.expert_idx[source] - off, 0, n - 1),
            jnp.where(valid, routing.gate[source], 0.0), counts, kept)


def _parent_dispatch(x, token_idx, kept):
    """Every row gathered, the tail zeroed (the parent's mask was ``gate
    > 0``, and the gate is 0 from ``kept`` on)."""
    real = jnp.arange(token_idx.shape[0]) < kept
    return x[token_idx] * real[:, None].astype(x.dtype)


def _parent_combine(y, token_idx, kept, num_tokens, weights):
    """Every row multiplied by its gate, 0 in the tail, and added."""
    del kept
    contrib = y.astype(jnp.float32) * weights[:, None]
    return jnp.zeros((num_tokens, y.shape[1]), jnp.float32).at[
        token_idx].add(contrib)


def _as_the_parent(monkeypatch):
    monkeypatch.setattr(SwitchMLP, "_held_share", _parent_held_share)
    monkeypatch.setattr(layer_mod, "gather_rows", _parent_dispatch)
    monkeypatch.setattr(layer_mod, "scatter_add_rows", _parent_combine)


def _steered(params, x, experts, strength=40.0):
    """The router sends every token to ``experts`` (its top-2 among
    them): one input channel held constant and a router row on it."""
    x = x.at[..., 0].set(1.0)
    row = jnp.zeros((E,), jnp.float32).at[jnp.asarray(experts)].set(
        strength) + jnp.arange(E) * 0.1
    params = dict(params, router={"gate_weight": params["router"][
        "gate_weight"].at[0].set(row)})
    return params, x


def _with_biases(params, seed=5):
    rng = np.random.default_rng(seed)
    experts = dict(params["experts"],
                   b1=jnp.asarray(rng.normal(size=(E, FW)) * 0.1,
                                  jnp.float32),
                   b2=jnp.asarray(rng.normal(size=(E, HW)) * 0.5,
                                  jnp.float32))
    return dict(params, experts=experts)


def _out_grads_sown(layer, share, x):
    def loss(p, inp):
        out, sown = layer.apply({"params": p}, inp, mutable=["moe_losses"])
        return jnp.sum(out * jnp.cos(out)), (out, sown["moe_losses"])

    (_, (out, sown)), grads = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(share, x)
    return out, grads, sown


# (expert_offset, local_experts, capacity_factor, the experts the router
# is steered to or None): rows == N where the factor is large (the window
# runs off the end of the sorted order for every offset but 0)
SHARES = {
    "rows_are_every_assignment": (2, 4, 8.0, None),
    "offset_share": (5, 3, 2.0, None),
    "first_share": (0, 2, 3.0, None),
    "collapsed_onto_the_share": (2, 4, 8.0, (3, 4)),
    "holds_none": (2, 4, 8.0, (0, 7)),
    "overflow_cut": (0, 4, 0.5, (1, 2)),
}


@pytest.fixture(params=["walk", "one_tile"])
def tiles(request, monkeypatch):
    """``walk``: row tiles of 64 (128 for the scatter-add where there are
    more rows than that), so the layer's 128-512 rows take a loop of
    several trips; ``one_tile``: the module's 2048, no loop."""
    if request.param == "walk":
        monkeypatch.setattr(row_gather, "ROW_TILE", 64)
        monkeypatch.setattr(row_gather, "SCATTER_TILE", 128)
    return request.param


@pytest.mark.parametrize("activation", ["swiglu", "relu2", "gelu"])
@pytest.mark.parametrize("share_name", sorted(SHARES))
def test_the_rows_passes_follow_the_count_and_change_nothing(
        monkeypatch, tiles, activation, share_name):
    """Output, input gradient, every parameter's gradient (the router's
    among them, through the gate) and everything sown: the parent's
    formulation written out above. Biased gelu experts leave a bias in
    every tail row, which must not reach the output."""
    off, n, factor, steer = SHARES[share_name]
    x, params = _wide_inputs(activation)
    if activation == "gelu":
        params = _with_biases(params)
    if steer is not None:
        params, x = _steered(params, x, steer)
    share = _share(params, off, n)
    layer = _wide_layer(activation, local_experts=n, expert_offset=off,
                        capacity_factor=factor)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        out, grads, sown = _out_grads_sown(layer, share, x)
    gauges = reg.snapshot()["gauges"]
    rows = gauges["moe/held_rows"]
    tile = gauges["moe/row_tile"]
    assert tile == (64 if tiles == "walk" else rows)
    sown = {k[-1]: v[0] for k, v in
            flax.traverse_util.flatten_dict(sown).items()}
    held = float(sown["held_assignments"]) * 2 * TW
    kept = min(round(held), rows)
    assert float(sown["held_row_tiles"]) == pytest.approx(
        -(-kept // tile) / -(-rows // tile))
    if share_name == "rows_are_every_assignment":
        assert rows == 2 * TW
    if share_name == "collapsed_onto_the_share":
        assert round(held) == 2 * TW
    if share_name == "holds_none":
        assert held == 0 and float(jnp.abs(out).max()) == 0
        assert float(sown["held_row_tiles"]) == 0

    _as_the_parent(monkeypatch)
    want_out, want_grads, want_sown = _out_grads_sown(layer, share, x)
    want_sown = {k[-1]: v[0] for k, v in
                 flax.traverse_util.flatten_dict(want_sown).items()}
    assert set(sown) == set(want_sown) | {"held_row_tiles"}
    for key, value in want_sown.items():
        assert float(sown[key]) == float(value), key
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-6)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == (6 if activation == "gelu" else 4)
    for (where, a), b in zip(flat, flat_want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6,
                                   err_msg=str(where))


@pytest.mark.parametrize("off", [0, 3, 6])
def test_the_window_is_a_slice_of_the_sorted_order(monkeypatch, off):
    """``_held_share`` hands out the parent's rows below ``kept`` to the
    bit: token, local expert and gate; from ``kept`` on the gate is 0 and
    the window is padded, not wrapped into other experts' rows."""
    x, params = _wide_inputs("swiglu")
    seen = {}

    def spy(name, fn):
        def wrapped(*args, **kw):
            seen.setdefault(name, []).append(
                [np.asarray(a) for a in args if hasattr(a, "shape")]
                + [np.asarray(w) for w in kw.values()])
            return fn(*args, **kw)
        return wrapped

    def run():
        seen.clear()
        monkeypatch.setattr(layer_mod, "gather_rows",
                            spy("dispatch", _parent_dispatch))
        monkeypatch.setattr(layer_mod, "scatter_add_rows",
                            spy("combine", _parent_combine))
        _wide_layer("swiglu", local_experts=2, expert_offset=off,
                    capacity_factor=8.0).apply(
            {"params": _share(params, off, 2)}, x)
        (_, idx, kept), = seen["dispatch"]
        (_, idx2, kept2, gate), = seen["combine"]
        assert (idx == idx2).all() and kept == kept2
        return idx, int(kept), gate

    idx, kept, gate = run()
    monkeypatch.setattr(SwitchMLP, "_held_share", _parent_held_share)
    want_idx, want_kept, want_gate = run()
    assert kept == want_kept and 0 < kept < idx.shape[0] == 2 * TW
    assert (idx[:kept] == want_idx[:kept]).all()
    assert (gate == want_gate).all() and (gate[kept:] == 0).all()
    if off == 6:    # the window ran off the end: the padding, not a wrap
        assert (idx[-(2 * TW - kept) // 2:] == 0).all()


def test_the_dropless_ragged_mode_makes_no_walk(monkeypatch):
    """``dispatch_mode="ragged"`` has every row real: it keeps XLA's
    whole-array gather and scatter-add and calls neither primitive."""
    x, params = _wide_inputs("swiglu")
    monkeypatch.setattr(row_gather, "ROW_TILE", 64)
    layer = _wide_layer("swiglu", dispatch_mode="ragged")

    def jaxpr():
        return str(jax.make_jaxpr(
            lambda p, inp: layer.apply({"params": p}, inp))(params, x))

    before = jaxpr()
    assert "while[" not in before and "scatter-add" in before

    def refuse(*args, **kw):
        raise AssertionError("the dropless path walks no tiles")

    monkeypatch.setattr(layer_mod, "gather_rows", refuse)
    monkeypatch.setattr(layer_mod, "scatter_add_rows", refuse)
    assert jaxpr() == before
    held = _wide_layer("swiglu", local_experts=4, capacity_factor=8.0)
    with pytest.raises(AssertionError, match="walks no tiles"):
        held.apply({"params": _share(params, 0, 4)}, x)
