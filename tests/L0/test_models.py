"""Model-zoo shape and numerics smoke tests.

Regression coverage for the example models (the reference ships its models
inside examples/: dcgan main_amp.py, imagenet main_amp.py). The DCGAN
generator must emit exactly 64x64 so D(G(z)) is non-empty — a shape
mismatch here produced empty logits whose mean was silently NaN.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def gan():
    from apex_tpu.models import Discriminator, Generator

    return Generator(), Discriminator()


def test_generator_emits_64x64(gan):
    netG, _ = gan
    z = jnp.zeros((2, 1, 1, 100))
    v = netG.init(jax.random.PRNGKey(0), z, train=True)
    fake, _ = netG.apply(v, z, train=True, mutable=["batch_stats"])
    assert fake.shape == (2, 64, 64, 3)
    assert fake.dtype == jnp.float32  # tanh output is fp32
    assert bool(jnp.isfinite(fake).all())


def test_discriminator_on_generator_output(gan):
    netG, netD = gan
    z = jnp.zeros((2, 1, 1, 100))
    vG = netG.init(jax.random.PRNGKey(0), z, train=True)
    fake, _ = netG.apply(vG, z, train=True, mutable=["batch_stats"])
    vD = netD.init(jax.random.PRNGKey(1), fake, train=True)
    out, _ = netD.apply(vD, fake, train=True, mutable=["batch_stats"])
    assert out.shape == (2, 1)  # non-empty: mean() of it must be finite
    assert bool(jnp.isfinite(out).all())


@pytest.mark.slow  # duplicate coverage: the dcgan/resnet amp-step tests
# compile the same conv stacks (tier-1 budget, 10s)
def test_resnet18_forward_shape():
    from apex_tpu.models import ResNet18

    model = ResNet18(num_classes=10, dtype=jnp.bfloat16)
    x = jnp.zeros((2, 64, 64, 3))
    v = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(v, x, train=False)
    assert logits.shape == (2, 10)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.slow
def test_dcgan_one_amp_step_finite(rng):
    """One O2 train step of the example's D loss stays finite."""
    from apex_tpu import amp
    from apex_tpu.models import Discriminator, Generator
    from apex_tpu.optimizers import FusedAdam

    netG, netD = Generator(ngf=8), Discriminator(ndf=8)
    z = jnp.asarray(rng.randn(2, 1, 1, 16).astype(np.float32))
    real = jnp.asarray(rng.randn(2, 64, 64, 3).astype(np.float32))
    vG = netG.init(jax.random.PRNGKey(0), z, train=True)
    vD = netD.init(jax.random.PRNGKey(1), real, train=True)
    pG, bsG = vG["params"], vG["batch_stats"]
    pD, bsD = vD["params"], vD["batch_stats"]
    (pD, pG), (optD, _) = amp.initialize(
        [pD, pG], [FusedAdam(lr=2e-4), FusedAdam(lr=2e-4)],
        opt_level="O2", num_losses=3, verbosity=0)
    sD = optD.init(pD)

    def bce(logits, t):
        x = logits.astype(jnp.float32)
        return jnp.mean(jnp.maximum(x, 0) - x * t +
                        jnp.log1p(jnp.exp(-jnp.abs(x))))

    def d_loss(pd):
        out_real, nbsD = netD.apply(
            {"params": pd, "batch_stats": bsD}, real, train=True,
            mutable=["batch_stats"])
        fake, _ = netG.apply({"params": pG, "batch_stats": bsG}, z,
                             train=True, mutable=["batch_stats"])
        out_fake, _ = netD.apply(
            {"params": pd, "batch_stats": nbsD["batch_stats"]},
            jax.lax.stop_gradient(fake), train=True,
            mutable=["batch_stats"])
        return bce(out_real, 1.0) + bce(out_fake, 0.0)

    scale = sD["scaler"].loss_scale
    loss, grads = jax.value_and_grad(lambda p: d_loss(p) * scale)(pD)
    assert bool(jnp.isfinite(loss))
    pD2, sD2 = optD.step(grads, sD, pD)
    gmax = max(float(jnp.abs(x).max())
               for x in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gmax)
    for leaf in jax.tree_util.tree_leaves(pD2):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


def _any_width(fmha_mod):
    """``fmha.dense_layout`` without its rule of sequence and head size:
    the model takes the flash kernels at widths it would refuse on a
    chip, in the layout the heads allow."""
    return lambda seq, heads, head_dim: (
        "bnsd" if fmha_mod._heads_per_cell(heads, head_dim) is None
        else "bsnd")


def test_gpt_flash_attention_path_jits(monkeypatch, rng):
    """The model-level flash path must survive jit+grad (regression: the
    attention layer once passed a traced jnp scale into the flash
    custom_vjp's static nondiff argument, blowing up only when
    use_flash_attention was actually enabled on TPU)."""
    import apex_tpu.contrib.fmha as fmha_mod

    monkeypatch.setattr(fmha_mod.GATE, "interpret", True)
    monkeypatch.setattr(fmha_mod, "dense_layout", _any_width(fmha_mod))

    from apex_tpu.models import GPTModel, TransformerConfig

    cfg = TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=1,
        vocab_size=128, max_position_embeddings=128,
        compute_dtype=jnp.float32, use_flash_attention=True)
    model = GPTModel(cfg)
    tokens = jnp.asarray(rng.randint(0, 128, (1, 128)))
    params = model.init(jax.random.PRNGKey(0), tokens)

    @jax.jit
    def loss_and_grad(p):
        def loss_fn(p):
            logits = model.apply(p, tokens).astype(jnp.float32)
            return jnp.mean(logits ** 2)
        return jax.value_and_grad(loss_fn)(p)

    loss, grads = loss_and_grad(params)
    assert bool(jnp.isfinite(loss))
    gmax = max(float(jnp.abs(x).max())
               for x in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gmax) and gmax > 0


def test_gpt_sliding_window_flash_matches_masked_path(monkeypatch, rng):
    """Model-level SWA through the flash kernel (window band block-skip)
    must match the masked-softmax fold of the same config."""
    import apex_tpu.contrib.fmha as fmha_mod

    from apex_tpu.models import GPTModel, TransformerConfig

    tokens = jnp.asarray(rng.randint(0, 128, (1, 128)))

    def logits(use_flash):
        cfg = TransformerConfig(
            hidden_size=64, num_layers=2, num_attention_heads=1,
            vocab_size=128, max_position_embeddings=128,
            compute_dtype=jnp.float32, use_flash_attention=use_flash,
            sliding_window=40)
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0), tokens)
        return np.asarray(model.apply(params, tokens))

    masked = logits(use_flash=False)
    monkeypatch.setattr(fmha_mod.GATE, "interpret", True)
    monkeypatch.setattr(fmha_mod, "dense_layout", _any_width(fmha_mod))
    flash = logits(use_flash=True)
    np.testing.assert_allclose(flash, masked, rtol=2e-4, atol=2e-4)


def test_gpt_alibi_flash_matches_masked_path(monkeypatch, rng):
    """Model-level ALiBi through the flash kernel (in-kernel key-position
    bias) must match the masked-softmax score-bias path."""
    import apex_tpu.contrib.fmha as fmha_mod

    from apex_tpu.models import GPTModel, TransformerConfig

    tokens = jnp.asarray(rng.randint(0, 128, (1, 128)))

    def logits(use_flash):
        cfg = TransformerConfig(
            hidden_size=64, num_layers=2, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=128,
            compute_dtype=jnp.float32, use_flash_attention=use_flash,
            position_embedding_type="alibi")
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0), tokens)
        return np.asarray(model.apply(params, tokens))

    masked = logits(use_flash=False)
    monkeypatch.setattr(fmha_mod.GATE, "interpret", True)
    monkeypatch.setattr(fmha_mod, "dense_layout", _any_width(fmha_mod))
    flash = logits(use_flash=True)
    np.testing.assert_allclose(flash, masked, rtol=2e-4, atol=2e-4)


# --- recomputation keeps the flash forward's output and log-sum-exp -------

_REMAT_LAYERS = 2
_REMAT_STACKS = [
    pytest.param(scan, window,
                 id=f"{'scan' if scan else 'unrolled'}-"
                    f"{'windowed' if window else 'causal'}")
    for scan in (False, True) for window in (None, 40)]


@pytest.fixture
def flash_interpreted(monkeypatch):
    """The flash kernels in interpret mode, at widths the gate of the
    model would refuse on a chip."""
    import apex_tpu.contrib.fmha as fmha_mod

    monkeypatch.setattr(fmha_mod.GATE, "interpret", True)
    monkeypatch.setattr(fmha_mod, "dense_layout", _any_width(fmha_mod))
    return monkeypatch


def _remat_stack(scan, window, *, checkpointing=True, flash=True):
    """A float32 two-layer ``ParallelTransformer``, its parameters, its
    input and the gradient of a scalar of its output by both."""
    from apex_tpu.models import TransformerConfig
    from apex_tpu.models.transformer_lm import ParallelTransformer

    stack = ParallelTransformer(TransformerConfig(
        hidden_size=64, num_layers=_REMAT_LAYERS, num_attention_heads=2,
        vocab_size=128, max_position_embeddings=128,
        compute_dtype=jnp.float32, use_flash_attention=flash,
        sliding_window=window, scan_layers=scan,
        activation_checkpointing=checkpointing))
    hidden = jax.random.normal(jax.random.PRNGKey(1), (128, 2, 64))
    params = stack.init(jax.random.PRNGKey(0), hidden)

    def loss(params, hidden):
        return jnp.sum(stack.apply(params, hidden) ** 2)

    return params, hidden, loss, jax.grad(loss, argnums=(0, 1))


def _bare_remat(monkeypatch):
    """The parent's checkpointing: ``nn.remat`` with no policy."""
    import flax.linen as nn

    import apex_tpu.models.transformer_lm as tlm

    monkeypatch.setattr(
        tlm, "_remat_keeping_flash_residuals",
        lambda block, wrapped, **kw: nn.remat(block, static_argnums=(),
                                              **kw))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    from apex_tpu.analysis.rules import _iter_subjaxprs

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _iter_subjaxprs(eqn):
            yield from _equations(sub)


def _flash_forwards(grad, params, hidden):
    return sum(eqn.primitive.name == "pallas_call"
               and eqn.params["name"] == "self_attention_flash_fwd"
               for eqn in _equations(
                   jax.make_jaxpr(grad)(params, hidden).jaxpr))


@pytest.mark.parametrize("scan,window", _REMAT_STACKS)
def test_remat_keeping_flash_residuals_leaves_gradients_as_they_were(
        flash_interpreted, scan, window):
    """The kept ``out`` and ``lse`` are what the second run of the kernel
    on the same q, k, v gave: float32 gradients are the bare
    ``nn.remat``'s to the bit, and checkpointing off's to rounding."""
    params, hidden, _, grad = _remat_stack(scan, window)
    kept = jax.jit(grad)(params, hidden)
    *_, grad_off = _remat_stack(scan, window, checkpointing=False)
    off = jax.jit(grad_off)(params, hidden)
    _bare_remat(flash_interpreted)
    *_, grad_bare = _remat_stack(scan, window)
    bare = jax.jit(grad_bare)(params, hidden)
    jax.tree_util.tree_map(np.testing.assert_array_equal, kept, bare)
    scale = max(float(jnp.abs(g).max())
                for g in jax.tree_util.tree_leaves(off))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6,
                                                atol=1e-6 * scale),
        kept, off)


@pytest.mark.parametrize("scan,window", _REMAT_STACKS)
def test_remat_runs_the_flash_forward_once_a_layer(flash_interpreted, scan,
                                                   window):
    """One forward kernel a layer in the gradient's jaxpr (a scanned
    stack holds its one block once), where the bare ``nn.remat`` holds
    the recomputed one as well."""
    blocks = 1 if scan else _REMAT_LAYERS
    params, hidden, _, grad = _remat_stack(scan, window)
    assert _flash_forwards(grad, params, hidden) == blocks
    _bare_remat(flash_interpreted)
    *_, grad_bare = _remat_stack(scan, window)
    assert _flash_forwards(grad_bare, params, hidden) == 2 * blocks


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
def test_remat_without_flash_names_and_saves_nothing(monkeypatch, capsys,
                                                     scan):
    """Without the kernel no residual is named, so the policy keeps what
    the bare ``nn.remat`` keeps: the arguments and each layer's input."""
    def residuals():
        params, hidden, loss, grad = _remat_stack(scan, None, flash=False)
        names = [eqn for eqn in _equations(
            jax.make_jaxpr(grad)(params, hidden).jaxpr)
            if eqn.primitive.name == "name"]
        jax.ad_checkpoint.print_saved_residuals(loss, params, hidden)
        return names, capsys.readouterr().out.splitlines()

    names, kept = residuals()
    assert names == []
    assert not any("named" in line for line in kept)
    # the parameters, the stack's input, the later layers' inputs (one
    # stacked array under scan) and the loss's own square
    inner = [line for line in kept if "from the argument" not in line]
    assert len(inner) == (2 if scan else _REMAT_LAYERS), inner
    assert all(line.startswith("f32[") and "128,2,64]" in line
               for line in inner), inner
    _bare_remat(monkeypatch)
    assert residuals() == (names, kept)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
def test_remat_counts_the_layers_it_wraps(flash_interpreted, scan):
    """``remat/save_flash_residuals`` reads one per checkpointed layer of
    a trace (one for a scanned block), beside the kernel path's own
    counter, and nothing with checkpointing off."""
    from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

    def counts(checkpointing):
        params, hidden, _, grad = _remat_stack(
            scan, None, checkpointing=checkpointing)
        with use_registry(MetricsRegistry(enabled=True)) as reg:
            jax.make_jaxpr(grad)(params, hidden)
            return (reg.counter_value("remat/save_flash_residuals"),
                    reg.counter_value(
                        "kernels/dispatch/flash_attention_interpret"))

    # (the kernel's counter reads every trace of the body: scan and
    # differentiation trace it more than once)
    wrapped, dispatched = counts(True)
    assert wrapped == (1 if scan else _REMAT_LAYERS)
    assert dispatched >= wrapped
    wrapped, dispatched = counts(False)
    assert wrapped == 0 and dispatched >= 1
