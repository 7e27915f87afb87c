"""contrib/gqa_decode: the streaming KV-cache decode kernel must be
token-exact against the einsum decode path — interpreter mode runs the
REAL kernel dataflow (tile index clamping, online softmax, scalar
prefetch) on the CPU mesh, and the end-to-end tests drive it through
``generate()`` so the model-integration gate (s == 1, no alibi) is what
is actually tested."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib import gqa_decode
from apex_tpu.models import GPTModel, TransformerConfig, generate
from apex_tpu.transformer import parallel_state


@pytest.fixture(autouse=True)
def _interpret():
    parallel_state.destroy_model_parallel()
    gqa_decode.force_interpret(True)
    yield
    gqa_decode.force_interpret(False)


@pytest.mark.parametrize("g,rep", [(2, 2), (4, 1), (1, 4)])
@pytest.mark.parametrize("window,softcap", [(None, None), (7, None),
                                            (None, 30.0), (6, 25.0)])
def test_kernel_matches_reference(g, rep, window, softcap):
    """GQA/MHA/MQA head layouts x {window, softcap}: kernel == einsum
    oracle at several live lengths incl. tile-boundary cases."""
    rng = np.random.RandomState(g * 10 + rep)
    b, d, T = 2, 16, 64
    q = jnp.asarray(rng.randn(b, g, rep, d).astype(np.float32))
    k = jnp.asarray(rng.randn(T, b, g, d).astype(np.float32))
    v = jnp.asarray(rng.randn(T, b, g, d).astype(np.float32))
    for length in (1, 5, 32, 33, 64):
        want = gqa_decode.gqa_decode_reference(
            q, k, v, length, 0.25, window=window, softcap=softcap)
        got = gqa_decode.gqa_flash_decode(
            q, k, v, length, 0.25, window=window, softcap=softcap,
            block_t=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def _gen_cfg(**kw):
    return TransformerConfig(
        hidden_size=48, num_layers=2, num_attention_heads=4,
        vocab_size=96, max_position_embeddings=32,
        compute_dtype=jnp.float32, use_flash_attention=False,
        normalization="rmsnorm", position_embedding_type="rope",
        activation="swiglu", num_query_groups=2, **kw)


@pytest.mark.parametrize("case", ["plain", "window", "gemma2"])
def test_generate_token_exact_kernel_vs_einsum(case, monkeypatch):
    """End-to-end greedy decode: the kernel path (forced interpret) must
    emit exactly the tokens the einsum path emits — through the real
    model gate (single-token steps only; the prefill chunk stays on
    the chunked einsum)."""
    kw = {}
    if case == "window":
        kw = dict(sliding_window=5)
    elif case == "gemma2":
        kw = dict(sliding_window=5, sliding_window_pattern=2,
                  sandwich_norm=True, attn_logit_softcapping=30.0,
                  query_pre_attn_scalar=20.0)
    cfg = _gen_cfg(**kw)
    model = GPTModel(cfg, decode=True)
    prompt = jnp.asarray(
        np.random.RandomState(1).randint(0, 96, size=(2, 9)))
    params = model.init(jax.random.PRNGKey(2), prompt)["params"]

    out_kernel = generate(model, params, prompt, 10)

    gqa_decode.force_interpret(False)
    # fresh jit cache entries: the flag is read at trace time
    from apex_tpu.models import generation as gen_mod

    gen_mod._compiled.cache_clear()
    out_einsum = generate(model, params, prompt, 10)
    np.testing.assert_array_equal(np.asarray(out_kernel),
                                  np.asarray(out_einsum))


def test_alibi_stays_on_einsum(monkeypatch):
    """ALiBi decode must NOT take the kernel (it carries no position
    bias): with the kernel gate ON (interpret), tokens must equal the
    flag-off einsum run — if a future edit dropped the alibi exclusion
    from the gate, the slope bias would vanish and tokens diverge."""
    cfg = TransformerConfig(
        hidden_size=48, num_layers=2, num_attention_heads=4,
        vocab_size=96, max_position_embeddings=32,
        compute_dtype=jnp.float32, use_flash_attention=False,
        position_embedding_type="alibi")
    model = GPTModel(cfg, decode=True)
    prompt = jnp.asarray(
        np.random.RandomState(3).randint(0, 96, size=(1, 6)))
    params = model.init(jax.random.PRNGKey(4), prompt)["params"]
    out_gated = generate(model, params, prompt, 6)

    from apex_tpu.models import generation as gen_mod

    gqa_decode.force_interpret(False)
    gen_mod._compiled.cache_clear()
    out_einsum = generate(model, params, prompt, 6)
    np.testing.assert_array_equal(np.asarray(out_gated),
                                  np.asarray(out_einsum))


def test_block_ladder_nondivisible_buffers():
    """A 1280-long buffer is not a 512-multiple but IS a 256-multiple:
    the ladder must pick 256 and keep the kernel (review finding) —
    parity at a length crossing several 256-tiles."""
    from apex_tpu.kernels.registry import choose_block

    assert choose_block(1280, 512) == 256
    assert choose_block(1536, 512) == 512
    assert choose_block(100, 512) == 100
    assert choose_block(1283, 512) is None

    rng = np.random.RandomState(0)
    b, g, rep, d, T = 1, 2, 2, 8, 1280
    q = jnp.asarray(rng.randn(b, g, rep, d).astype(np.float32))
    k = jnp.asarray(rng.randn(T, b, g, d).astype(np.float32))
    v = jnp.asarray(rng.randn(T, b, g, d).astype(np.float32))
    assert gqa_decode.use_flash(T)
    want = gqa_decode.gqa_decode_reference(q, k, v, 700, 0.3)
    got = gqa_decode.gqa_flash_decode(q, k, v, 700, 0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
