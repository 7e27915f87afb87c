"""The Mamba-2 mixer (``apex_tpu/transformer/ssm.py``) at a small size,
seeded: the chunked scan against the recurrence taken a step at a time
(values and the gradient of every input, float32), the whole mixer
against the equations written out, causality, rows that do not see each
other, and bfloat16 against float32 within a stated band."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import TransformerConfig
from apex_tpu.transformer.ssm import (Mamba2Mixer, causal_depthwise_conv,
                                      ssd_chunked)

B_, S, H, P, G, N = 2, 24, 4, 8, 2, 16
INPUTS = ["x", "B", "C", "dt", "A", "D"]


def _inputs(seed=0, s=S):
    rng = np.random.default_rng(seed)
    return {
        "x": jnp.asarray(rng.normal(size=(B_, s, H, P)), jnp.float32),
        "B": jnp.asarray(rng.normal(size=(B_, s, G, N)), jnp.float32),
        "C": jnp.asarray(rng.normal(size=(B_, s, G, N)), jnp.float32),
        # steps between 0.01 and 1: decays a step from 0.0001 to 0.99
        "dt": jnp.asarray(np.exp(rng.uniform(np.log(0.01), 0.0,
                                             (B_, s, H))), jnp.float32),
        "A": -jnp.asarray(rng.uniform(1.0, 8.0, (H,)), jnp.float32),
        "D": jnp.asarray(rng.normal(size=(H,)), jnp.float32),
    }


def step_by_step(x, B, C, dt, A, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t +
    D x_t``, a step at a time and a row at a time."""
    rep = H // G

    def row(x, B, C, dt):
        def step(state, t):
            xt, Bt, Ct, dtt = t
            Bh, Ch = jnp.repeat(Bt, rep, 0), jnp.repeat(Ct, rep, 0)
            state = (jnp.exp(dtt * A)[:, None, None] * state
                     + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :])
            return state, jnp.einsum("hpn,hn->hp", state, Ch)

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, B, C, dt))
        return y + D[:, None] * x

    return jax.vmap(row)(x, B, C, dt)


def chunked(chunk):
    def f(x, B, C, dt, A, D):
        return ssd_chunked(x, dt, A, B, C, chunk) + D[:, None] * x
    return f


@pytest.mark.parametrize("chunk", [4, 8])
@pytest.mark.parametrize("s", [S, 21])
def test_chunked_scan_is_the_recurrence(chunk, s):
    """24 steps are 6 or 3 whole chunks; 21 end in a padded one."""
    v = _inputs(1, s)
    want = step_by_step(**v)
    got = chunked(chunk)(**v)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.abs(want).max()))


@pytest.mark.parametrize("chunk", [4, 8])
@pytest.mark.parametrize("wrt", INPUTS)
def test_chunked_scan_s_gradients_are_the_recurrence_s(chunk, wrt):
    v = _inputs(2)
    w = jnp.asarray(np.random.default_rng(3).normal(size=(B_, S, H, P)),
                    jnp.float32)

    def scalar(f):
        return lambda t: jnp.sum(w * f(**dict(v, **{wrt: t})))

    want = jax.grad(scalar(step_by_step))(v[wrt])
    got = jax.grad(scalar(chunked(chunk)))(v[wrt])
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, atol=5e-5 * float(
        jnp.abs(want).max()))


def test_a_chunk_longer_than_the_sequence_is_one_chunk():
    v = _inputs(4, 8)
    np.testing.assert_allclose(chunked(128)(**v), step_by_step(**v),
                               atol=2e-5)


# ------------------------------------------------------------ the mixer

HIDDEN = 24


def _config(**kw):
    return TransformerConfig(
        hidden_size=HIDDEN, num_layers=1, num_attention_heads=2,
        vocab_size=32, compute_dtype=jnp.float32, layer_pattern="M",
        normalization="rmsnorm", mamba_num_heads=H, mamba_head_dim=P,
        mamba_n_groups=G, mamba_state_size=N, mamba_conv_kernel=4,
        mamba_chunk_size=8, **kw)


def _mixer(seed=0, cfg=None):
    cfg = cfg or _config()
    mixer = Mamba2Mixer(cfg)
    u = jnp.asarray(np.random.default_rng(seed).normal(
        size=(S, B_, HIDDEN)), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(seed), u)["params"]
    return mixer, params, u


def written_out(p, u, eps):
    """The module's docstring, a row at a time."""
    inner, bc = H * P, G * N

    def row(u):
        proj = u @ p["in_proj"]
        z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * bc],
                      proj[:, 2 * inner + 2 * bc:])
        padded = jnp.concatenate([jnp.zeros((3, xbc.shape[1])), xbc])
        conv = p["conv_bias"] + sum(p["conv_weight"][k] * padded[k:k + S]
                                    for k in range(4))
        xbc = jax.nn.silu(conv)
        x = xbc[:, :inner].reshape(S, H, P)
        Bm = xbc[:, inner:inner + bc].reshape(S, G, N)
        Cm = xbc[:, inner + bc:].reshape(S, G, N)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = step_by_step(x[None], Bm[None], Cm[None], dt[None],
                         -jnp.exp(p["A_log"]), p["D"])[0]
        y = (y.reshape(S, inner) * jax.nn.silu(z)).reshape(S, G, -1)
        y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return (y.reshape(S, inner) * p["norm_weight"]) @ p["out_proj"]

    return jax.vmap(row, in_axes=1, out_axes=1)(u)


def test_published_init():
    _, p, _ = _mixer()
    assert p["in_proj"].shape == (HIDDEN, 2 * H * P + 2 * G * N + H)
    assert p["conv_weight"].shape == (4, H * P + 2 * G * N)
    a = np.exp(np.asarray(p["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.log1p(np.exp(np.asarray(p["dt_bias"])))     # softplus
    assert (dt >= 0.001 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert (np.asarray(p["D"]) == 1).all()
    assert (np.asarray(p["norm_weight"]) == 1).all()
    assert np.abs(np.asarray(p["conv_weight"])).max() <= 0.5


def test_mixer_is_its_equations_written_out():
    mixer, p, u = _mixer(1)
    p = dict(p, D=p["D"] * 0.5, norm_weight=p["norm_weight"] * 1.5)
    got = mixer.apply({"params": p}, u)
    want = written_out(p, u, 1e-5)
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.abs(want).max()))


def test_the_conv_is_causal():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    base = causal_depthwise_conv(x, w, b)
    moved = causal_depthwise_conv(x.at[:, 7].add(1.0), w, b)
    assert np.array_equal(base[:, :7], moved[:, :7])
    # the four taps reach three steps on, the last tap is the step itself
    np.testing.assert_allclose(moved[:, 7] - base[:, 7], w[3][None],
                               rtol=1e-5)
    np.testing.assert_allclose(moved[:, 10] - base[:, 10], w[0][None],
                               rtol=1e-5)
    assert np.array_equal(base[:, 11], moved[:, 11])
    np.testing.assert_allclose(base[:, 0], b + w[3] * x[:, 0], rtol=1e-5)


def test_the_mixer_is_causal():
    mixer, p, u = _mixer(2)
    base = mixer.apply({"params": p}, u)
    moved = mixer.apply({"params": p}, u.at[13].add(1.0))
    assert np.array_equal(base[:13], moved[:13])
    assert float(jnp.abs(base[13:] - moved[13:]).min(axis=(1, 2)).min()) > 0


def test_a_row_s_state_does_not_leak_into_the_next_row():
    mixer, p, u = _mixer(3)
    both = mixer.apply({"params": p}, u)
    alone = mixer.apply({"params": p}, u[:, 1:])
    np.testing.assert_allclose(both[:, 1:], alone, atol=1e-6)
    other = mixer.apply({"params": p}, u.at[:, 0].multiply(-2.0))
    assert np.array_equal(both[:, 1], other[:, 1])
    assert not np.allclose(both[:, 0], other[:, 0])


def test_bfloat16_against_float32_within_a_band():
    """bfloat16 operands under float32 decays, sums and state: the gap to
    float32 read 0.4-0.6% of the output's largest entry over five seeds;
    a bfloat16 state or cumulative sum would read percents."""
    for seed in range(3):
        mixer, p, u = _mixer(seed)
        want = mixer.apply({"params": p}, u)
        low = Mamba2Mixer(dataclasses.replace(mixer.config,
                                              compute_dtype=jnp.bfloat16))
        got = jax.jit(low.apply)({"params": p}, u)
        assert got.dtype == jnp.bfloat16
        gap = float(jnp.abs(got.astype(jnp.float32) - want).max()
                    / jnp.abs(want).max())
        assert 1e-4 < gap < 0.02, gap


def test_the_mixer_counts_itself():
    from apex_tpu.telemetry.registry import get_registry

    reg = get_registry()
    was = reg.enabled
    reg.enable()
    try:
        before = reg.counter("ssm/layers").value
        _mixer(0)
        assert reg.counter("ssm/layers").value > before
    finally:
        if not was:
            reg.disable()
