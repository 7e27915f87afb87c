"""Latent attention on the normal path (``ParallelAttention`` with
``kv_lora_rank``, ``models/transformer_lm.py`` ``latent_attention``,
``contrib/fmha.py`` ``mla_flash_attention``) at small sizes, seeded:
against the plain reference's attention, the kernels in the interpreter
against their oracle at 128 + 64 beside 128, the shared rotary key's
gradient, and ``models/mla.py``'s training branch through the same
function."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib import fmha
from apex_tpu.kernels.registry import get_kernel_registry
from apex_tpu.models import TransformerConfig, transformer_lm
from apex_tpu.models import mla as mla_model
from apex_tpu.models.transformer_lm import ParallelAttention
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry
from benchmark.reference import deepseek_v3 as R
from benchmark.reference import transformer as T

HID, N, DN, DR, DV, LAT, S, B = 48, 4, 16, 8, 16, 24, 32, 2
ARCH = {"heads": N, "nope_dim": DN, "rope_dim": DR, "v_dim": DV,
        "kv_rank": LAT, "theta": 50000.0, "eps": 1e-5}


def config(**kw):
    return TransformerConfig(**dict(dict(
        hidden_size=HID, num_layers=1, num_attention_heads=N,
        vocab_size=64, compute_dtype=jnp.float32, normalization="rmsnorm",
        activation="swiglu", attention_bias=False,
        position_embedding_type="rope", rotary_base=50000.0,
        rotary_interleaved=True, kv_lora_rank=LAT, qk_nope_head_dim=DN,
        qk_rope_head_dim=DR, v_head_dim=DV, use_flash_attention=False), **kw))


def published_weights(seed=0):
    rng = np.random.default_rng(seed)
    shapes = {"wq": (HID, N * (DN + DR)), "wdkv": (HID, LAT + DR),
              "kvn_g": (LAT,), "wukv": (LAT, N * (DN + DV)),
              "wo": (N * DV, HID)}
    lp = {k: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
          for k, s in shapes.items()}
    lp["kvn_g"] = 1.0 + lp["kvn_g"]
    return lp


def program_params(lp):
    """The published columns (a head's [nope | rope], [key | value]) in
    the program's layout (benchmark/families/deepseek_v3.py)."""
    from benchmark.families import deepseek_v3 as family

    return {"q_proj": {"weight": family._heads_apart(lp["wq"], ARCH, DR)},
            "kv_down": {"kernel": lp["wdkv"]},
            "kv_norm": {"weight": lp["kvn_g"]},
            "kv_up": {"weight": family._heads_apart(lp["wukv"], ARCH, DV)},
            "dense": {"weight": lp["wo"]}}


def inputs(seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(S, B, HID)),
                       jnp.float32)


def reference_attention(lp, x):
    """``[s, b, hidden]`` through the plain reference, a sequence at a
    time."""
    return jax.vmap(lambda u: R.attention(u, lp, ARCH, T.identity),
                    in_axes=1, out_axes=1)(x)


def test_the_latent_path_is_the_reference_s_attention():
    lp, x = published_weights(), inputs()
    got = ParallelAttention(config()).apply(
        {"params": program_params(lp)}, x)
    want = reference_attention(lp, x)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("tensor", ["wq", "wdkv", "kvn_g", "wukv", "wo", "x"])
def test_the_latent_path_s_gradients_are_the_reference_s(tensor):
    lp, x = published_weights(2), inputs(3)
    g = jnp.asarray(np.random.default_rng(4).normal(size=(S, B, HID)),
                    jnp.float32)

    def mine(lp, x):
        return jnp.sum(g * ParallelAttention(config()).apply(
            {"params": program_params(lp)}, x))

    def plain(lp, x):
        return jnp.sum(g * reference_attention(lp, x))

    got = jax.grad(mine, argnums=(0, 1))(lp, x)
    want = jax.grad(plain, argnums=(0, 1))(lp, x)
    a, b = (got[1], want[1]) if tensor == "x" else (got[0][tensor],
                                                    want[0][tensor])
    assert float(jnp.abs(b).max()) > 0
    np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


def test_the_program_s_columns_round_trip():
    from benchmark.families import deepseek_v3 as family

    w = published_weights()["wq"]
    apart = family._heads_apart(w, ARCH, DR)
    assert not np.array_equal(apart, w)
    np.testing.assert_array_equal(family._heads_together(apart, ARCH, DR), w)


# ---- the kernels, in the interpreter, at the cell's widths

def kernel_operands(seed=0, b=2, s=256, n=4, dn=128, dr=64, dv=128,
                    dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    widths = (n * dn, n * dr, n * dn, dr, n * dv, n * dv)
    return [jax.random.normal(k, (b, s, w), jnp.float32).astype(dtype)
            for k, w in zip(ks, widths)]


@pytest.fixture
def interpreted():
    reg = get_kernel_registry()
    reg.force_interpret(True, ["flash_attention"])
    yield
    reg.force_interpret(False, ["flash_attention"])


def _kernel_and_oracle(operands, g, heads=4, block=128):
    out, vjp = jax.vjp(lambda *a: fmha.mla_flash_attention(
        *a, heads, True, block, block), *operands)
    want, want_vjp = jax.vjp(lambda *a: fmha.mla_attention_reference(
        *a, heads, True), *operands)
    return (out,) + vjp(g), (want,) + want_vjp(g)


PARTS = ["out", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"]


@pytest.mark.parametrize("part", PARTS)
def test_kernels_at_192_beside_128_are_the_oracle(interpreted, part):
    """Forward, dq, dk and dv with 128 + 64 wide queries and keys beside
    128-wide values, two heads a cell, two q blocks by two kv blocks."""
    *operands, g = kernel_operands()
    got, want = _kernel_and_oracle(operands, g)
    i = PARTS.index(part)
    assert got[i].shape == want[i].shape
    scale = float(jnp.abs(want[i]).max())
    assert scale > 0.1
    np.testing.assert_allclose(got[i], want[i], atol=5e-6 * scale)


def test_the_kernel_path_was_taken_and_counted(interpreted):
    *operands, g = kernel_operands(s=128)
    reg = MetricsRegistry(enabled=True)
    with use_registry(reg):
        text = str(jax.make_jaxpr(lambda *a: _kernel_and_oracle(a, g)[0])(
            *operands))
    for name in ("mla_attention_flash_fwd", "mla_attention_flash_dq",
                 "mla_attention_flash_dkv"):
        assert name in text
    counters = reg.snapshot()["counters"]
    assert counters["kernels/dispatch/flash_attention_mla_interpret"] >= 1
    assert counters["kernels/dispatch/flash_attention_interpret"] >= 1


def test_the_log_sum_exp_is_the_oracle_s(interpreted):
    qn, qr, kn, kr, v, _ = kernel_operands(s=256)
    _, lse = fmha._mla_fwd_pallas(
        qn, qr, kn, kr, v, heads=4, scale=192 ** -0.5, causal=True,
        block_q=128, block_k=128, interpret=True)
    b, s = qn.shape[:2]
    q = jnp.concatenate([qn.reshape(b, s, 4, 128),
                         qr.reshape(b, s, 4, 64)], -1)
    k = jnp.concatenate([kn.reshape(b, s, 4, 128), jnp.broadcast_to(
        kr[:, :, None], (b, s, 4, 64))], -1)
    scores = fmha._reference_scores(q.transpose(0, 2, 1, 3),
                                    k.transpose(0, 2, 1, 3), 192 ** -0.5,
                                    True)
    want = jax.scipy.special.logsumexp(scores, axis=-1)     # [b, n, s]
    assert lse.shape == (b, 2, 2, s)       # two cells of two heads
    np.testing.assert_allclose(lse.reshape(b, 4, s), want, atol=1e-5)


def test_the_shared_rotary_key_s_gradient_is_summed_over_heads(interpreted):
    """``dk_rope`` of the shared key is the sum over heads of what each
    head alone gives: run a head at a time (the other heads' values
    zeroed, so that they carry no gradient to the key)."""
    qn, qr, kn, kr, v, g = kernel_operands(seed=3, n=4)
    whole = _kernel_and_oracle([qn, qr, kn, kr, v], g)[0][4]
    parts = []
    for h in range(4):
        mask = jnp.repeat(jnp.arange(4) == h, 128).astype(g.dtype)
        parts.append(_kernel_and_oracle([qn, qr, kn, kr, v], g * mask)[0][4])
    assert all(float(jnp.abs(p).max()) > 0.1 for p in parts)
    np.testing.assert_allclose(sum(parts), whole,
                               atol=1e-5 * float(jnp.abs(whole).max()))
    assert whole.shape == kr.shape


@pytest.mark.parametrize("heads,widths,want", [
    (16, (128, 64, 128), 2), (4, (128, 64, 128), 2), (3, (128, 64, 128), None),
    (8, (128, 128, 128), 1), (16, (64, 32, 64), 4), (4, (16, 8, 16), None)])
def test_heads_a_cell(heads, widths, want):
    assert fmha._mla_heads_per_cell(heads, widths) == want


def test_a_shape_the_kernels_do_not_take_runs_the_oracle(interpreted):
    """Heads of 16 + 8 fill no 128-lane column: the oracle, counted."""
    operands = kernel_operands(s=128, n=4, dn=16, dr=8, dv=16)[:5]
    reg = MetricsRegistry(enabled=True)
    with use_registry(reg):
        out = fmha.mla_flash_attention(*operands, 4, True)
    np.testing.assert_allclose(
        out, fmha.mla_attention_reference(*operands, 4, True), atol=1e-6)
    assert reg.snapshot()["counters"][
        "kernels/dispatch/flash_attention_mla_oracle"] == 1


def test_the_latent_path_runs_the_kernels_under_recomputation(interpreted):
    """A checkpointed layer at the kernels' widths keeps ``out`` and
    ``lse`` (``FLASH_RESIDUAL_NAMES``): the backward holds one forward
    kernel and the two backward ones, not a second forward."""
    from apex_tpu.models.transformer_lm import ParallelTransformer

    cfg = config(hidden_size=64, num_attention_heads=2, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=32,
                 ffn_hidden_size=64, use_flash_attention=True,
                 activation_checkpointing=True)
    model = ParallelTransformer(cfg)
    x = jnp.ones((128, 1, 64), jnp.float32)
    reg = MetricsRegistry(enabled=True)
    with use_registry(reg):
        params = model.init(jax.random.PRNGKey(0), x)
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(model.apply(p, x))))(params))
    assert text.count("mla_attention_flash_fwd") == 1
    assert text.count("mla_attention_flash_dq") == 1
    assert text.count("mla_attention_flash_dkv") == 1
    assert reg.snapshot()["counters"]["mla/layers"] >= 1


# ---- models/mla.py trains through the same function

def test_one_spelling_of_latent_attention_for_training():
    train = inspect.getsource(mla_model.MLAAttention.__call__)
    latent = inspect.getsource(ParallelAttention._latent_attention)
    assert "latent_attention(" in train and "latent_attention(" in latent
    for text in (train, latent, inspect.getsource(
            transformer_lm.latent_attention)):
        assert "bnqk" not in text and "softmax" not in text


@pytest.mark.parametrize("q_lora_rank", [None, 12])
def test_mla_model_s_training_output_is_what_it_was(q_lora_rank):
    """``MLAAttention``'s training branch through the shared function
    against its former two einsums, written out here."""
    cfg = mla_model.MLAConfig(
        vocab_size=64, hidden_size=HID, num_layers=1, num_heads=N,
        q_lora_rank=q_lora_rank, kv_lora_rank=LAT, qk_nope_head_dim=DN,
        qk_rope_head_dim=DR, v_head_dim=DV, ffn_hidden_size=64,
        rotary_base=10000.0, compute_dtype=jnp.float32)
    attn = mla_model.MLAAttention(cfg)
    x = inputs(5)
    params = attn.init(jax.random.PRNGKey(1), x)
    got = attn.apply(params, x)

    p = params["params"]
    q_in = x
    if q_lora_rank:
        qa = x @ p["q_a"]["kernel"]
        q_in = qa * jax.lax.rsqrt(jnp.mean(qa * qa, -1, keepdims=True)
                                  + cfg.rms_eps) * p["q_a_norm"]["weight"]
    q = (q_in @ p["q_b"]["weight"]).reshape(S, B, N, DN + DR)
    ckv = x @ p["kv_a"]["kernel"]
    c = ckv[..., :LAT]
    c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True)
                          + cfg.rms_eps) * p["kv_a_norm"]["weight"]
    kv = (c @ p["kv_b"]["weight"]).reshape(S, B, N, DN + DV)
    rope = transformer_lm._rope_core
    q_pe = rope(q[..., DN:], cfg.rotary_base, None, DR, interleaved=True)
    k_pe = rope(ckv[:, :, None, LAT:], cfg.rotary_base, None, DR,
                interleaved=True)
    qf = jnp.concatenate([q[..., :DN], q_pe], -1)
    kf = jnp.concatenate([kv[..., :DN],
                          jnp.broadcast_to(k_pe, (S, B, N, DR))], -1)
    scores = jnp.einsum("qbnd,kbnd->bnqk", qf, kf) * (DN + DR) ** -0.5
    scores = jnp.where(jnp.arange(S)[None, :] > jnp.arange(S)[:, None],
                       -1e9, scores)
    ctx = jnp.einsum("bnqk,kbnd->qbnd", jax.nn.softmax(scores, -1),
                     kv[..., DN:]).reshape(S, B, N * DV)
    want = ctx @ p["o"]["weight"]
    np.testing.assert_allclose(got, want,
                               atol=2e-5 * float(jnp.abs(want).max()))


# ---- the configuration's fields

@pytest.mark.parametrize("bad", [
    dict(qk_rope_head_dim=7), dict(kv_lora_rank=0), dict(q_lora_rank=0),
    dict(q_lora_rank=12),
    dict(attention_bias=True), dict(position_embedding_type="learned"),
    dict(num_query_groups=2), dict(sliding_window=8), dict(qk_norm="head"),
    dict(indexer_heads=2), dict(rotary_percent=0.5),
    dict(sequence_parallel=True), dict(kv_lora_rank=None, q_lora_rank=8)])
def test_fields_are_validated(bad):
    with pytest.raises(ValueError):
        config(**bad)


def test_absent_fields_leave_attention_as_it_was():
    plain = TransformerConfig(hidden_size=64, num_layers=1,
                              num_attention_heads=4, vocab_size=64)
    assert plain.kv_lora_rank is None and plain.q_lora_rank is None
    shapes = jax.eval_shape(lambda: ParallelAttention(plain).init(
        jax.random.PRNGKey(0), jnp.ones((8, 1, 64))))
    assert set(shapes["params"]) == {"query_key_value", "dense"}
    latent = jax.eval_shape(lambda: ParallelAttention(config()).init(
        jax.random.PRNGKey(0), jnp.ones((8, 1, HID))))
    assert set(latent["params"]) == {"q_proj", "kv_down", "kv_norm",
                                     "kv_up", "dense"}
    assert latent["params"]["q_proj"]["weight"].shape == (
        HID, N * (DN + DR))


def test_the_latent_path_refuses_a_mask_and_decoding():
    x = jnp.ones((8, 1, HID))
    with pytest.raises(ValueError, match="attention_mask"):
        ParallelAttention(config()).init(jax.random.PRNGKey(0), x,
                                         jnp.zeros((1, 1, 8, 8), bool))
    with pytest.raises(ValueError, match="cache row"):
        ParallelAttention(config(), decode=True).init(
            jax.random.PRNGKey(0), x)
