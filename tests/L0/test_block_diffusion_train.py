"""Training by diffusion over blocks on the normal path (``GPTModel`` under
``AttnMaskType.block_diffusion`` / ``diffusion_block_length``,
``models/gpt.py`` ``block_diffusion_loss_fn``) at a small size on the CPU,
seeded random weights, against the plain reference
``benchmark/reference/sdar_moe.py``: loss, the noisy half's logits, every
tensor's gradient; the head sees ``L`` rows; what the configuration
refuses; and the share tied to the model: the eight shares' expert sums,
with attention and router counted once, add up to the uncut reference's
layer."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.transformer_lm import ParallelTransformerLayer
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry
from apex_tpu.transformer.enums import AttnMaskType
from benchmark import families, weights
from benchmark.reference import sdar_moe as R
from benchmark.reference import transformer as T

# hidden 64, 4 query / 2 key-value heads of 16, 16 experts of 32 top-2 with
# 2 held (an eighth, as the cell's 16 of 128), 2 layers, blocks of 4
CONFIG = dict(
    family="sdar_moe", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    num_experts=2, num_experts_per_tok=2, num_hidden_layers=2,
    vocab_size=96, max_position_embeddings=64, rope_theta=10000,
    rms_norm_eps=1e-6, expert_offset=0,
    assumed={"routed_experts": 16, "padded_vocab_size": 128,
             "held_rows_factor": 8.0, "block_length": 4,
             "router_aux_loss_coef": 0.01})
MIX = {"batch": 2, "seq": 32, "block_length": 4, "t_min": 0.25,
       "flash_attention": True, "recompute": False}
FAMILY = families.of("sdar_moe")
ARCH = FAMILY.arch(CONFIG)
TENSORS = sorted(FAMILY.shapes(ARCH))


def _model(**replace):
    model = FAMILY.build_model(ARCH, MIX)
    return model.clone(config=dataclasses.replace(
        model.config, compute_dtype=jnp.float32, **replace))


def _state(seed=3):
    canon = weights.make(weights.seed_key(seed), ARCH)
    batch = next(FAMILY.TASKS["block_diffusion"](MIX, ARCH, seed))
    return canon, batch


@functools.lru_cache(maxsize=None)
def _both_sides():
    """(loss, canonical gradients) of the program in float32 and of the
    reference on one seeded batch."""
    canon, batch = _state()
    loss, grads = jax.value_and_grad(FAMILY.loss(_model()))(
        FAMILY.to_program(canon, ARCH), batch)
    block = {k: jnp.asarray(v) for k, v in batch.items()}
    want = jax.value_and_grad(lambda p: R.loss_part(
        p, ARCH, block, R.totals(batch)))(canon)
    return (loss, FAMILY.from_program(grads, ARCH)), want


def test_loss_matches_in_float32():
    # float32 on both sides, the same equations: summation order alone
    got, want = _both_sides()
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)


def test_the_noisy_half_s_logits_match_in_float32():
    canon, batch = _state()
    rows = jnp.concatenate([batch["tokens"], batch["noisy"]], axis=1)
    got = _model().apply({"params": FAMILY.to_program(canon, ARCH)}, rows)
    want = R.logits(canon, ARCH, {k: jnp.asarray(v)
                                  for k, v in batch.items()})
    assert got.shape == want.shape == (2, 32, 128)
    # logits are O(0.1) at this init; float32 round-off through two layers
    np.testing.assert_allclose(got, want,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("tensor", TENSORS)
def test_gradient_matches_in_float32(tensor):
    got, want = _both_sides()
    a, b = np.asarray(got[1][tensor]), np.asarray(want[1][tensor])
    assert np.abs(b).max() > 0, "a tensor with no gradient tests nothing"
    # relative to the tensor's largest entry: float32 round-off of sums
    # taken in another order on the two sides
    np.testing.assert_allclose(a, b, atol=2e-5 * np.abs(b).max())


def test_the_loss_weighs_the_masked_positions_alone():
    """Changing a data token where nothing was masked moves the loss only
    through the clean copy's keys; changing the weights moves it
    directly: the loss is ``sum(w * CE) / (B L)`` with no shift."""
    from apex_tpu.models.gpt import block_diffusion_loss_fn

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 16, (2, 8)), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 4, (2, 8)) * (rng.random((2, 8)) < 0.5),
                    jnp.float32)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                               labels[..., None], -1)[..., 0]
    want = float(jnp.sum(w * nll) / 16)
    assert float(block_diffusion_loss_fn(logits, labels, w)) == \
        pytest.approx(want, rel=1e-6)
    assert float(block_diffusion_loss_fn(logits, labels,
                                         jnp.zeros_like(w))) == 0.0


def test_the_head_sees_the_noisy_half_alone():
    canon, batch = _state()
    rows = jnp.concatenate([batch["tokens"], batch["noisy"]], axis=1)
    reg = MetricsRegistry(enabled=True)
    with use_registry(reg):
        logits = jax.eval_shape(
            lambda p: _model().apply({"params": p}, rows),
            FAMILY.to_program(canon, ARCH))
    snap = reg.snapshot()
    assert logits.shape == (2, 32, 128)
    assert snap["gauges"]["diffusion/head_rows"] == 2 * 32
    assert snap["gauges"]["diffusion/block_length"] == 4
    assert snap["counters"]["diffusion/layers"] == 2
    # off the TPU the rule runs as the boolean mask, counted as the
    # kernels' oracle, once a layer
    assert snap["counters"][
        "kernels/dispatch/flash_attention_blockdiff_oracle"] == 2


def test_the_clean_copy_of_the_last_block_reaches_no_logit():
    """The clean half reaches neither the final norm, the head nor the
    loss, and no noisy row sees the clean copy of its own or a later
    block: a clean token of the last block moves no logit at all."""
    canon, batch = _state()
    params = FAMILY.to_program(canon, ARCH)
    rows = jnp.concatenate([batch["tokens"], batch["noisy"]], axis=1)
    last = 32 - 1
    other = rows.at[:, last].set((rows[:, last] + 1) % 95)
    a = _model().apply({"params": params}, rows)
    b = _model().apply({"params": params}, other)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # while a clean token of the first block moves the later blocks' logits
    moved = _model().apply({"params": params},
                           rows.at[:, 0].set((rows[:, 0] + 1) % 95))
    assert not np.array_equal(np.asarray(a)[:, 4:], np.asarray(moved)[:, 4:])
    np.testing.assert_array_equal(np.asarray(a)[:, :4],
                                  np.asarray(moved)[:, :4])


def _base(**kw):
    return dict(dict(hidden_size=64, num_layers=1, num_attention_heads=4,
                     vocab_size=128, position_embedding_type="rope",
                     attn_mask_type=AttnMaskType.block_diffusion,
                     diffusion_block_length=4), **kw)


@pytest.mark.parametrize("kw", [
    {"sliding_window": 8},
    {"indexer_heads": 4},
    {"diffusion_block_length": None},
    {"diffusion_block_length": 0},
    {"attn_mask_type": AttnMaskType.causal},
    {"context_parallel": True},
    {"position_embedding_type": "alibi"},
])
def test_the_configuration_refuses(kw):
    TransformerConfig(**_base())      # the rule alone is accepted
    with pytest.raises(ValueError):
        TransformerConfig(**_base(**kw))


def test_there_is_no_decode_path_and_rows_come_in_pairs_of_blocks():
    cfg = TransformerConfig(**_base(compute_dtype=jnp.float32))
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="no decode path"):
        jax.eval_shape(lambda: GPTModel(cfg, decode=True).init(
            jax.random.PRNGKey(0), tokens))
    with pytest.raises(ValueError, match="noised copies"):
        jax.eval_shape(lambda: GPTModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 12), jnp.int32)))


# ---- the share tied to the model: eight shares of two experts each

def _layer_tree(canon, arch, layer=0):
    return FAMILY.to_program(canon, arch)["transformer"][f"layer_{layer}"]


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One layer on one row ``[2L, hidden]`` under the rule: what every
    chip computes alike (attention, router, norms: ``x'``) counted once,
    plus the eight shares' expert sums, is the uncut reference's layer."""
    full_cfg = dict(CONFIG, num_experts=16, num_hidden_layers=1)
    full = FAMILY.arch(full_cfg)
    canon = weights.make(weights.seed_key(5), full)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)   # 2L = 64
    lp = {k[len("layers."):]: v[0] for k, v in canon.items()
          if k.startswith("layers.")}
    want = R.block(x, lp, full, T.identity)[0]

    positions = jnp.tile(jnp.arange(32), 2)[:, None]

    def share(rank, experts_off=False):
        arch = dict(full, experts_held=2, expert_offset=2 * rank)
        held = {k: (v[:, 2 * rank:2 * rank + 2]
                    if k in ("layers.egate", "layers.eup", "layers.edown")
                    else v) for k, v in canon.items()}
        if experts_off:
            held["layers.edown"] = jnp.zeros_like(held["layers.edown"])
        model = FAMILY.build_model(arch, MIX)
        cfg = dataclasses.replace(model.config, compute_dtype=jnp.float32)
        out = ParallelTransformerLayer(cfg, layer_number=0).apply(
            {"params": _layer_tree(held, arch)}, x[:, None, :], None,
            positions, mutable=["moe_losses"])[0]
        return out[:, 0, :]

    alike = share(0, experts_off=True)        # x': no expert adds to it
    total = alike + sum(share(r) - alike for r in range(8))
    assert float(jnp.abs(want - alike).max()) > 1e-3
    np.testing.assert_allclose(total, want,
                               atol=2e-5 * float(jnp.abs(want).max()))
