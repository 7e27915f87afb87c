"""Attention kinds by layer (``TransformerConfig.attention_kinds`` /
``layer_attention``), YaRN (``RopeScaling(rope_type="yarn")``), the
per-head output gate (``attention_output_gate``) and the window band's own
kernel names and dispatch record (``contrib/fmha.py``): a tiny
``[full, window, window, window, full]`` stack against a plain per-layer
einsum, forward and gradients, on the softmax path and through the
interpreted kernels; YaRN against the HF formulas; the gate; the names,
records, counters and scopes; the paths that refuse the description."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.kernels.registry as kreg
from apex_tpu.contrib import fmha
from apex_tpu.models import AttentionKind, RopeScaling, TransformerConfig
from apex_tpu.models.transformer_lm import (
    ParallelAttention,
    ParallelTransformer,
    _scale_rope_freqs,
    apply_rotary_emb,
    rope_attention_factor,
    yarn_correction_range,
)
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry
from apex_tpu.telemetry.scopes import classify

HID, D, GROUPS, SEQ, WINDOW = 64, 64, 2, 256, 100
LAGUNA_YARN = RopeScaling(rope_type="yarn", factor=64.0,
                          original_max_position_embeddings=4096,
                          beta_fast=64.0, beta_slow=1.0,
                          attention_factor=1.4158883083359672)
TINY_YARN = RopeScaling(rope_type="yarn", factor=4.0,
                        original_max_position_embeddings=64,
                        beta_fast=4.0, beta_slow=1.0)
KINDS = (AttentionKind("full_attention", heads=4, rotary_base=50000.0,
                       rotary_percent=0.5, rope_scaling=TINY_YARN),
         AttentionKind("sliding_attention", window=WINDOW, heads=8,
                       rotary_base=10000.0))
LAYERS = ("full_attention", "sliding_attention", "sliding_attention",
          "sliding_attention", "full_attention")


def _config(**kw):
    base = dict(
        hidden_size=HID, num_layers=len(LAYERS), num_attention_heads=4,
        head_dim=D, num_query_groups=GROUPS, ffn_hidden_size=96,
        vocab_size=128, max_position_embeddings=SEQ,
        compute_dtype=jnp.float32, normalization="rmsnorm",
        activation="swiglu", attention_bias=False,
        position_embedding_type="rope", attention_kinds=KINDS,
        layer_attention=LAYERS, attention_output_gate=True,
        activation_checkpointing=False)
    return TransformerConfig(**dict(base, **kw))


# ---------------------------------------------------- HF's YaRN, verbatim

def _hf_yarn(base, dim, scaling):
    """transformers' ``_compute_yarn_parameters`` (truncate on), in
    numpy: -> (inverse frequencies, attention factor, low, high)."""
    factor = scaling.factor
    attention_factor = scaling.attention_factor
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0

    def find_correction_dim(num_rotations):
        return (dim * math.log(scaling.original_max_position_embeddings
                               / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(find_correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(scaling.beta_slow)), dim - 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    hi = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (hi - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv = (interpolation * (1 - extrapolation_factor)
           + extrapolation * extrapolation_factor)
    return inv, attention_factor, low, high


@pytest.mark.parametrize("base,dim,scaling,low,high", [
    (500000.0, 64, LAGUNA_YARN, 5, 16),
    (50000.0, 32, TINY_YARN, 1, 4)])
def test_yarn_is_hf_s(base, dim, scaling, low, high):
    want, factor, want_low, want_high = _hf_yarn(base, dim, scaling)
    assert (want_low, want_high) == (low, high)
    assert yarn_correction_range(scaling, base, dim) == (low, high)
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    got = _scale_rope_freqs(inv, scaling, base, dim)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    assert rope_attention_factor(scaling) == pytest.approx(factor)
    # below the range the extrapolated frequencies, above it divided
    np.testing.assert_allclose(np.asarray(got)[:low],
                               np.asarray(inv)[:low], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got)[high:],
                               np.asarray(inv)[high:] / scaling.factor,
                               rtol=1e-6)


def test_laguna_s_attention_factor_is_the_default_one():
    assert rope_attention_factor(LAGUNA_YARN) == pytest.approx(
        rope_attention_factor(dataclasses.replace(LAGUNA_YARN,
                                                  attention_factor=None)),
        rel=1e-9)
    assert rope_attention_factor(None) == 1.0
    assert rope_attention_factor(RopeScaling("linear", 4.0)) == 1.0


def test_yarn_on_half_a_head_leaves_the_other_half_alone():
    """At position 0 the rotated half is scaled by the attention factor
    and turned by nothing; the pass-through half is as it was at every
    position."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 1, 2, 128))
    out = apply_rotary_emb(x, 500000.0, None, 0.5, False, LAGUNA_YARN)
    np.testing.assert_array_equal(np.asarray(out[..., 64:]),
                                  np.asarray(x[..., 64:]))
    np.testing.assert_allclose(np.asarray(out[0, ..., :64]),
                               np.asarray(x[0, ..., :64]) * 1.4158883,
                               rtol=1e-6)
    plain = apply_rotary_emb(x, 500000.0, None, 0.5, False, None)
    assert not np.allclose(np.asarray(out[5:, ..., :64]),
                           np.asarray(plain[5:, ..., :64]))


# ------------------------------------------- the stack against plain einsums

def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * g


def _rotary(x, kind):
    """``x [s, heads, d]``: rotate-half on the kind's leading channels."""
    s, r = x.shape[0], int(x.shape[-1] * (kind.rotary_percent or 1.0))
    inv = kind.rotary_base ** (-np.arange(0, r, 2, dtype=np.float32) / r)
    scale = 1.0
    if kind.rope_scaling is not None:
        inv, scale, _, _ = _hf_yarn(kind.rotary_base, r, kind.rope_scaling)
    angle = np.arange(s, dtype=np.float32)[:, None, None] * inv
    cos, sin = np.cos(angle) * scale, np.sin(angle) * scale
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _plain_layer(x, lp, kind):
    """One layer over ``x [s, b, hidden]``, from the published equations."""
    n = kind.heads
    s = x.shape[0]
    att = lp["self_attention"]
    u = _rms(x, lp["input_layernorm"]["weight"])
    w = att["query_key_value"]["weight"]
    q = jnp.einsum("sbh,hk->bsk", u, w[:, :n * D]).reshape(-1, s, n, D)
    kv = jnp.einsum("sbh,hk->bsk", u, w[:, n * D:]).reshape(
        -1, s, GROUPS, 2, D)
    k, v = kv[..., 0, :], kv[..., 1, :]
    q = jax.vmap(lambda t: _rotary(t, kind))(q)
    k = jax.vmap(lambda t: _rotary(t, kind))(k)
    q = q.reshape(-1, s, GROUPS, n // GROUPS, D)
    scores = jnp.einsum("bsgrd,btgd->bgrst", q, k) / math.sqrt(D)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (j <= i) & ((i - j < kind.window) if kind.window else True)
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    o = jnp.einsum("bgrst,btgd->bsgrd", p, v).reshape(-1, s, n, D)
    g = jax.nn.sigmoid(jnp.einsum("sbh,hn->bsn", u,
                                  att["gate"]["weight"]))
    o = (o * g[..., None]).reshape(-1, s, n * D)
    x = x + jnp.einsum("bsk,kh->sbh", o, att["dense"]["weight"])
    u = _rms(x, lp["post_attention_layernorm"]["weight"])
    gate_up = jnp.einsum("sbh,hf->sbf", u,
                         lp["mlp"]["dense_h_to_4h"]["weight"])
    gate, up = jnp.split(gate_up, 2, -1)
    return x + jnp.einsum("sbf,fh->sbh", jax.nn.silu(gate) * up,
                          lp["mlp"]["dense_4h_to_h"]["weight"])


def _plain_stack(params, x):
    kinds = {k.name: k for k in KINDS}
    for i, name in enumerate(LAYERS):
        x = _plain_layer(x, params["params"][f"layer_{i}"], kinds[name])
    return x


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(fmha.GATE, "interpret", True)


def _stack_and_params(flash):
    stack = ParallelTransformer(_config(use_flash_attention=flash))
    hidden = jax.random.normal(jax.random.PRNGKey(1), (SEQ, 2, HID))
    params = stack.init(jax.random.PRNGKey(0), hidden)
    # a gate that is not a half everywhere
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 40 if "gate" in jax.tree_util.keystr(path)
        else w, params)
    return stack, params, hidden


@pytest.mark.parametrize("flash", [False, True], ids=["softmax", "kernels"])
def test_the_stack_is_the_plain_einsum(request, flash):
    if flash:
        request.getfixturevalue("interpreted")
    stack, params, hidden = _stack_and_params(flash)
    ps = params["params"]
    assert [ps[f"layer_{i}"]["self_attention"]["gate"]["weight"].shape
            for i in range(5)] == [(HID, 4), (HID, 8), (HID, 8), (HID, 8),
                                   (HID, 4)]
    assert ps["layer_1"]["self_attention"]["query_key_value"][
        "weight"].shape == (HID, (8 + 2 * GROUPS) * D)

    def loss(f):
        return lambda p, x: jnp.sum(jnp.sin(f(p, x)))

    got = stack.apply(params, hidden)
    want = _plain_stack(params, hidden)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    g_got = jax.grad(loss(stack.apply), argnums=(0, 1))(params, hidden)
    g_want = jax.grad(loss(_plain_stack), argnums=(0, 1))(params, hidden)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        scale = float(jnp.abs(b).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_a_zero_gate_halves_the_context():
    cfg = _config(num_layers=1, layer_attention=LAYERS[:1])
    x = jax.random.normal(jax.random.PRNGKey(2), (SEQ, 2, HID))
    gated = ParallelAttention(cfg)
    params = gated.init(jax.random.PRNGKey(0), x)
    zero = jax.tree_util.tree_map(jnp.zeros_like,
                                  params["params"]["gate"])
    params = {"params": dict(params["params"], gate=zero)}
    plain = ParallelAttention(dataclasses.replace(
        cfg, attention_output_gate=False))
    without = {"params": {k: v for k, v in params["params"].items()
                          if k != "gate"}}
    np.testing.assert_allclose(
        np.asarray(gated.apply(params, x)),
        0.5 * np.asarray(plain.apply(without, x)), rtol=1e-5, atol=1e-6)


def test_the_stack_counts_its_kinds_and_gates():
    stack, params, hidden = _stack_and_params(False)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        jax.eval_shape(stack.apply, params, hidden)
        assert reg.counter_value("attention/window_layers") == 3
        assert reg.counter_value("attention/full_layers") == 2
        assert reg.counter_value("attention/gate_layers") == 5
        gauges = reg.snapshot()["gauges"]
    assert gauges["attention/query_heads/full_attention"] == 4
    assert gauges["attention/query_heads/sliding_attention"] == 8


def test_a_stack_without_kinds_counts_nothing_of_them():
    cfg = _config(attention_kinds=None, layer_attention=None,
                  attention_output_gate=False, num_attention_heads=4)
    stack = ParallelTransformer(cfg)
    hidden = jnp.zeros((SEQ, 1, HID))
    params = jax.eval_shape(stack.init, jax.random.PRNGKey(0), hidden)
    with use_registry(MetricsRegistry(enabled=True)) as reg:
        jax.eval_shape(stack.apply, params, hidden)
        counters = reg.snapshot()["counters"]
    assert not [k for k in counters if k.startswith("attention/")]


# ------------------------------------------------- names and the record

def _records(f, *operands):
    reg = MetricsRegistry(enabled=True)
    seen = {}
    reg.add_event_tap(lambda rec: rec["kind"] == "kernel"
                      and seen.update({rec["kernel"]: rec}))
    with use_registry(reg):
        jax.eval_shape(f, *operands)
    return seen, reg.snapshot()


def test_the_band_s_record_at_the_cell_s_shape():
    """8192 positions, a window of 512, tiles of 512: a head runs 31
    tiles (the diagonal's 16 and the 15 below it) whole and skips 225,
    and each kernel's walk takes those 31 grid steps; they compute twice
    the pairs a query sees."""
    q = jax.ShapeDtypeStruct((2, 8192, 8 * 128), jnp.bfloat16)
    seen, summary = _records(lambda q, k, v: fmha.flash_attention_bsnd(
        q, k, v, 8, True, window=512), q, q, q)
    rec = seen[fmha.WINDOW_ENTRY]
    assert (rec["tiles_whole"], rec["tiles_skipped"],
            rec["tiles_diagonal"], rec["grid_steps"]) == (31, 225, 0, 31)
    assert (rec["pairs_computed"], rec["pairs_visible"]) == (
        8_126_464, 4_063_488)
    assert summary["gauges"]["kernels/flash_attention_window/tiles_whole"] \
        == 31
    # the entry's own record is kept beside it
    assert seen["flash_attention_bsnd"]["tiles_whole"] == 31
    path = rec["path"]
    assert summary["counters"][
        f"kernels/dispatch/flash_attention_window_{path}"] == 1


def test_a_call_without_a_window_has_no_band_record():
    q = jax.ShapeDtypeStruct((2, 1024, 8 * 128), jnp.bfloat16)
    seen, _ = _records(lambda q, k, v: fmha.flash_attention_bsnd(
        q, k, v, 8, True), q, q, q)
    assert fmha.WINDOW_ENTRY not in seen
    seen, _ = _records(lambda q, k, v: fmha.flash_attention(
        q, k, v, True, window=100), *(3 * [jax.ShapeDtypeStruct(
            (1, 2, 256, 64), jnp.float32)]))
    assert seen[fmha.WINDOW_ENTRY]["tiles_whole"] == 1


@pytest.mark.parametrize("window,prefix", [(None, "self_attention"),
                                           (128, "window_attention")])
@pytest.mark.parametrize("batch_major", [False, True])
def test_the_kernels_are_named_by_their_band(monkeypatch, window, prefix,
                                             batch_major):
    monkeypatch.setattr(kreg, "_on_tpu", lambda: True)
    if batch_major:
        q = jax.ShapeDtypeStruct((2, 1024, 2 * 128), jnp.bfloat16)

        def attend(a, b, c):
            return fmha.flash_attention_bsnd(a, b, c, 2, True,
                                             window=window)
    else:
        q = jax.ShapeDtypeStruct((2, 2, 1024, 128), jnp.bfloat16)

        def attend(a, b, c):
            return fmha.flash_attention(a, b, c, True, window=window)

    def step(q, k, v):
        out, vjp = jax.vjp(attend, q, k, v)
        return out, vjp(out)

    text = str(jax.make_jaxpr(step)(q, q, q))
    assert sorted(re.findall(r"name=(\w+_attention_flash_\w+)", text)) == [
        f"{prefix}_flash_{w}" for w in ("dkv", "dq", "fwd")]


# -------------------------------------------------------------- scopes

@pytest.mark.parametrize("scope,want", [
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/self_attention/attention/"
     "window/query_key_value/dot_general", ("attention/window/qkv",
                                            "forward")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/layer_1/self_attention/"
     "attention/window/jit(_bsnd_bwd_pallas)/window_attention_flash_dkv/"
     "pallas_call", ("attention/window/kernel", "backward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/self_attention/attention/"
     "window/attention/gate/gate/dot_general", ("attention/window/gate",
                                                "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/self_attention/attention/"
     "window/dense/dot_general", ("attention/window/dense", "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/self_attention/attention/"
     "window/transpose", ("attention/window", "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/self_attention/attention/"
     "gate/logistic", ("attention/gate", "forward")),
])
def test_classify_reads_the_window_and_the_gate(scope, want):
    assert classify(scope) == want


def test_a_compiled_step_gives_the_window_its_parts():
    import collections

    from apex_tpu.telemetry.scopes import scope_table

    stack, params, hidden = _stack_and_params(False)

    def objective(p, x):
        return jnp.sum(stack.apply(p, x) ** 2)

    compiled = jax.jit(jax.grad(objective)).lower(params, hidden).compile()
    blocks = collections.Counter(
        classify(s)[0] for s in scope_table(compiled).values())
    for part in ("attention/window/qkv", "attention/window/gate",
                 "attention/window/dense", "attention/qkv",
                 "attention/gate", "attention/dense"):
        assert blocks[part] > 0, sorted(blocks, key=str)


# ---------------------------------------------------- what is refused

@pytest.mark.parametrize("change", [
    dict(layer_attention=None),
    dict(layer_attention=LAYERS[:4]),
    dict(layer_attention=LAYERS[:4] + ("global",)),
    dict(attention_kinds=KINDS + (KINDS[0],)),
    dict(attention_kinds=(dataclasses.replace(KINDS[0], heads=5),
                          KINDS[1])),
    dict(attention_kinds=(KINDS[0], dataclasses.replace(KINDS[1],
                                                        window=0))),
    dict(sliding_window=64),
    dict(scan_layers=True),
    dict(context_parallel=True),
    dict(sequence_parallel=True),
    dict(position_embedding_type="alibi"),
    dict(rotary_base_local=10000.0),
    dict(no_rope_layer_interval=4),
])
def test_what_the_kinds_refuse(change):
    with pytest.raises(ValueError):
        _config(**change)


def test_the_gate_refuses_what_it_does_not_take():
    with pytest.raises(ValueError, match="attention_output_gate"):
        TransformerConfig(hidden_size=64, num_attention_heads=4,
                          attention_output_gate=True, context_parallel=True)


def test_decode_refuses_the_kinds():
    attention = ParallelAttention(_config(), decode=True)
    with pytest.raises(ValueError, match="no decode path"):
        attention.init(jax.random.PRNGKey(0), jnp.zeros((4, 1, HID)))


def test_a_pipeline_stage_refuses_the_kinds():
    from apex_tpu.models.gpt_stage import GPTStage

    stage = GPTStage(_config(), layers_per_stage=5)
    with pytest.raises(ValueError, match="layer_attention"):
        stage.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                   jnp.zeros((8, 1, HID)), True)
