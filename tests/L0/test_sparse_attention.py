"""The selection operand of the flash kernels (``contrib/fmha.py``) against
its oracle in interpret mode, the head-summed probabilities, the indexer's
threshold selection against an exact top-k, and the promise that GPT-2's
three calls are what they were before the operand existed."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib import fmha
from apex_tpu.kernels import registry as kreg
from apex_tpu.models.transformer_lm import topk_selection

B, N, S, D = 2, 4, 256, 64


def _selection(topk, empty_tile):
    """Each query's ``topk`` best causal keys by a seeded score (rows
    shorter than ``topk`` keep all they have); optionally one 128 x 128
    tile with nothing selected."""
    score = jax.random.normal(jax.random.PRNGKey(5), (B, S, S))
    sel = topk_selection(score, topk).astype(bool)
    if empty_tile:
        sel = sel.at[:, 128:, :128].set(False) | jnp.eye(S, dtype=bool)
    return sel.astype(jnp.int8)


def _qkv():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (B, N, S, D), jnp.float32)
                 for k in keys)


@pytest.fixture
def interpret():
    reg = kreg.get_kernel_registry()
    reg.force_interpret(True)
    yield
    reg.force_interpret(False)


CASES = [(40, False, 128), (40, True, 128), (40, True, 256),
         (300, False, 128)]


@pytest.mark.parametrize("topk,empty_tile,block", CASES)
@pytest.mark.parametrize("what", ["fwd", "dq", "dkv", "probs"])
def test_selection_kernels_match_the_oracle(interpret, topk, empty_tile,
                                            block, what):
    q, k, v = _qkv()
    sel = _selection(topk, empty_tile)

    def kernel(q, k, v):
        return fmha.sparse_attention(q, k, v, sel, True, None, block, block)

    def oracle(q, k, v):
        scale = D ** -0.5
        p = jax.nn.softmax(fmha._reference_scores(
            q, k, scale, True, selection=sel), axis=-1)
        return (fmha._attention_reference(q, k, v, scale, True,
                                          selection=sel),
                jnp.sum(jnp.where(sel[:, None] != 0, p, 0.0), axis=1))

    (out, probs), vjp = jax.vjp(kernel, q, k, v)
    (want, want_probs), ref_vjp = jax.vjp(oracle, q, k, v)
    if what == "fwd":
        np.testing.assert_allclose(out, want, atol=2e-6)
    elif what == "probs":
        np.testing.assert_allclose(probs, want_probs, atol=2e-6)
        np.testing.assert_allclose(probs.sum(-1), N, rtol=1e-5)
        assert float(jnp.abs(jnp.where(sel != 0, 0.0, probs)).max()) == 0
    else:
        got = vjp((v, jnp.zeros_like(probs)))
        ref = ref_vjp((v, jnp.zeros_like(probs)))
        pick = {"dq": (0,), "dkv": (1, 2)}[what]
        for i in pick:
            np.testing.assert_allclose(got[i], ref[i], atol=5e-5)


def test_flash_attention_takes_the_selection_too(interpret):
    q, k, v = _qkv()
    sel = _selection(40, True)
    out = fmha.flash_attention(q, k, v, True, None, 128, 128, selection=sel)
    want = fmha._attention_reference(q, k, v, D ** -0.5, True, selection=sel)
    np.testing.assert_allclose(out, want, atol=2e-6)


def test_probs_carry_no_gradient_and_selection_is_checked():
    q, k, v = _qkv()
    sel = _selection(40, False)
    grads = jax.grad(lambda q, k, v: fmha.sparse_attention(
        q, k, v, sel, True)[1].sum(), (0, 1, 2))(q, k, v)
    assert all(float(jnp.abs(g).max()) == 0 for g in grads)
    with pytest.raises(ValueError, match="int8"):
        fmha.flash_attention(q, k, v, True, selection=sel.astype(jnp.int32))
    with pytest.raises(ValueError, match="window"):
        fmha.flash_attention(q, k, v, True, window=8, selection=sel)


@pytest.mark.parametrize("seq,topk", [(32, 8), (64, 64), (48, 100),
                                      (128, 17)])
def test_threshold_selection_is_an_exact_top_k(seq, topk):
    score = jax.random.normal(jax.random.PRNGKey(seq), (3, seq, seq))
    # negative, zero and denormal scores order as floats do
    score = score.at[0, :, 0].set(0.0).at[1, :, 1].set(-1e-40)
    got = np.asarray(topk_selection(score, topk)).astype(bool)
    t = np.arange(seq)
    causal = t[None, :] <= t[:, None]
    masked = np.where(causal, np.asarray(score), -np.inf)
    want = np.zeros_like(got)
    for b in range(3):
        for row in range(seq):
            best = np.argsort(-masked[b, row], kind="stable")[
                :min(row + 1, topk)]
            want[b, row, best] = True
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(t + 1, topk)).all()


# sha256 of GPT-2 345M's three flash calls (16 x 16 heads of 64 at 1024,
# forward + backward) as a jaxpr, source lines and addresses stripped.
# Through PR 37 the hash of the commit before the selection operand
# existed (PR 27); taken anew at PR 38, whose forward and dq run a tile on
# the diagonal in strips (dkv's call is what it was). It holds that an
# optional operand (the selection, the rotary part, alibi, a window)
# leaves GPT-2's program byte for byte alone.
GPT2_FLASH_JAXPR = \
    "cda857fa352bfbcf8459063361d650965e777009002efa47502b41807fb4c518"


def test_without_a_selection_the_kernels_are_what_they_were(monkeypatch):
    monkeypatch.setattr(kreg, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((16, 16, 1024, 64), jnp.bfloat16)

    def step(q, k, v):
        out, vjp = jax.vjp(
            lambda a, b, c: fmha.flash_attention(a, b, c, True), q, k, v)
        return out, vjp(out)

    text = str(jax.make_jaxpr(step)(q, q, q))
    assert text.count("pallas_call") == 3 and "sparse_attention" not in text
    assert sorted(re.findall(r"name=(self_attention_\w+)", text)) == [
        "self_attention_flash_dkv", "self_attention_flash_dq",
        "self_attention_flash_fwd"]
    assert "i8[" not in text
    text = re.sub(r" at [^\s:]+:\d+", "", text)
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest() == GPT2_FLASH_JAXPR
