"""On-chip L1 convergence traces: real ResNet-50 + BERT-large at every
opt level, per-iteration loss/grad-norm dumped to committed JSON.

Parity: reference tests/L1/common/main_amp.py (trace dump per opt level)
+ compare.py (closeness vs the O0 baseline), run on the real chip with
the real models (BASELINE functional configs 1/2/4), not the CPU-mesh
stand-ins in tests/L1.

One config per invocation (fresh process per point — OOM containment,
same policy as tools/mfu_sweep.py; one process per chip, so never two
at once):

    python tools/l1_onchip.py resnet_O0        # ... resnet_O1 _O2 _O3
    python tools/l1_onchip.py bert_O0          # ... bert_O2
    python tools/l1_onchip.py all              # print the run plan
    python tools/l1_onchip.py compare          # verdicts vs O0, from JSON

Traces land in tests/L1/traces_onchip/<config>.json. Budget ~2-6 min
per config (first compile dominates); run with the host CPU dedicated.
"""

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "L1", "traces_onchip")

# APEX_TPU_L1_TINY=1: CPU-smoke geometry for script-logic verification
# (traces land in a separate dir so real captures are never overwritten)
TINY = os.environ.get("APEX_TPU_L1_TINY") == "1"
if TINY:
    TRACE_DIR = os.path.join(os.path.dirname(TRACE_DIR), "traces_tiny")

ITERS = 6 if TINY else 12

# bf16-vs-fp32 per-iteration closeness (tests/L1/test_cross_product.py
# rationale; real models at real scale get the same headroom)
LOSS_RTOL = {"O1": 0.05, "O2": 0.08, "O3": 0.10}
GNORM_RTOL = {"O1": 0.15, "O2": 0.20, "O3": 0.25}


def _global_norm(grads, scale):
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(grads)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves)) / scale


def run_resnet(opt_level, optimizer_name):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models import ResNet50
    from apex_tpu.optimizers import FusedAdam, FusedSGD

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    dtype = jnp.float32 if opt_level == "O0" else jnp.bfloat16
    if TINY:
        from apex_tpu.models import ResNet18 as ResNetCls
        batch, side, classes = 4, 64, 10
    else:
        ResNetCls, batch, side, classes = ResNet50, 64, 224, 1000
    model = ResNetCls(num_classes=classes, dtype=dtype)
    rng = np.random.RandomState(0)
    images = jnp.asarray(
        rng.randn(batch, side, side, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, classes, size=(batch,)))
    variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    base = (FusedSGD(lr=0.05, momentum=0.9) if optimizer_name == "sgd"
            else FusedAdam(lr=1e-3))
    params, opt = amp.initialize(params, base, opt_level=opt_level,
                                 verbosity=0)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, batch_stats, opt_state):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.mean(
                jnp.take_along_axis(logp, labels[:, None], axis=-1))
            return loss, updates["batch_stats"]

        scale = opt_state["scaler"].loss_scale
        (loss, new_bs), grads = jax.value_and_grad(
            lambda p: (lambda l, b: (l * scale, b))(*loss_fn(p)),
            has_aux=True)(params)
        gnorm = _global_norm(grads, scale)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_bs, new_opt_state, loss / scale, gnorm

    losses, gnorms = [], []
    state = (params, batch_stats, opt_state)
    for _ in range(ITERS):
        *state, loss, gnorm = train_step(*state)
        losses.append(float(loss))
        gnorms.append(float(gnorm))
    return losses, gnorms


def run_bert(opt_level):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models import BertModel, TransformerConfig, bert_loss_fn
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.enums import AttnMaskType

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    parallel_state.destroy_model_parallel()
    batch, seq = (2, 32) if TINY else (16, 128)
    cfg = TransformerConfig(
        hidden_size=128 if TINY else 1024,
        num_layers=2 if TINY else 24,
        num_attention_heads=4 if TINY else 16,
        vocab_size=512 if TINY else 30528,
        max_position_embeddings=512,
        compute_dtype=jnp.float32 if opt_level == "O0" else jnp.bfloat16,
        use_flash_attention=False, attn_mask_type=AttnMaskType.padding,
        activation_checkpointing=False)
    model = BertModel(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    padding_mask = jnp.ones((batch, seq), jnp.int32)
    tokentype = jnp.zeros((batch, seq), jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    loss_mask = jnp.asarray(
        (rng.rand(batch, seq) < 0.15).astype(np.float32))
    nsp_labels = jnp.asarray(rng.randint(0, 2, (batch,)))

    variables = model.init(jax.random.PRNGKey(0), tokens, padding_mask,
                           tokentype)
    params, opt = amp.initialize(
        variables, FusedLAMB(lr=1e-3, weight_decay=0.01),
        opt_level=opt_level, verbosity=0)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state):
        def loss_fn(p):
            mlm, nsp = model.apply(p, tokens, padding_mask, tokentype)
            return bert_loss_fn(mlm, nsp, labels, loss_mask, nsp_labels)

        scale = opt_state["scaler"].loss_scale
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p) * scale)(params)
        gnorm = _global_norm(grads, scale)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_opt_state, loss / scale, gnorm

    losses, gnorms = [], []
    state = (params, opt_state)
    for _ in range(ITERS):
        *state, loss, gnorm = train_step(*state)
        losses.append(float(loss))
        gnorms.append(float(gnorm))
    return losses, gnorms


def run_dcgan(opt_level):
    """BASELINE functional config 2: DCGAN multi-loss amp (reference
    examples/dcgan/main_amp.py — two models, three loss ids, per-loss
    scalers). Trace = lossD + lossG per iter; grad norm from the D step.
    Fixed data per iter index so runs are comparable."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models import Discriminator, Generator
    from apex_tpu.optimizers import FusedAdam

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    batch = 4 if TINY else 64
    nz = 16 if TINY else 100
    dt = jnp.float32 if opt_level == "O0" else jnp.bfloat16
    netG, netD = Generator(dtype=dt), Discriminator(dtype=dt)
    rng = np.random.RandomState(0)
    z0 = jnp.asarray(rng.randn(batch, 1, 1, nz).astype(np.float32))
    img0 = jnp.asarray(rng.randn(batch, 64, 64, 3).astype(np.float32))
    vG = netG.init(jax.random.PRNGKey(0), z0, train=True)
    vD = netD.init(jax.random.PRNGKey(1), img0, train=True)
    pG, bsG = vG["params"], vG.get("batch_stats", {})
    pD, bsD = vD["params"], vD.get("batch_stats", {})
    (pD, pG), (optD, optG) = amp.initialize(
        [pD, pG], [FusedAdam(lr=2e-4, betas=(0.5, 0.999)),
                   FusedAdam(lr=2e-4, betas=(0.5, 0.999))],
        opt_level=opt_level, num_losses=3, verbosity=0)
    sD, sG = optD.init(pD), optG.init(pG)

    def bce(logits, target):
        x = logits.astype(jnp.float32)
        return jnp.mean(jnp.maximum(x, 0) - x * target +
                        jnp.log1p(jnp.exp(-jnp.abs(x))))

    @jax.jit
    def train_step(pD, bsD, sD, pG, bsG, sG, real, z):
        def d_loss(pd):
            out_real, nbsD = netD.apply(
                {"params": pd, "batch_stats": bsD}, real, train=True,
                mutable=["batch_stats"])
            fake, nbsG = netG.apply(
                {"params": pG, "batch_stats": bsG}, z, train=True,
                mutable=["batch_stats"])
            out_fake, nbsD2 = netD.apply(
                {"params": pd, "batch_stats": nbsD["batch_stats"]},
                jax.lax.stop_gradient(fake), train=True,
                mutable=["batch_stats"])
            return (bce(out_real, 1.0) + bce(out_fake, 0.0),
                    (nbsD2["batch_stats"], nbsG["batch_stats"]))

        scaleD = sD["scaler"].loss_scale
        (lossD, (bsD2, bsG2)), gD = jax.value_and_grad(
            lambda p: (lambda l, a: (l * scaleD, a))(*d_loss(p)),
            has_aux=True)(pD)
        gnorm = _global_norm(gD, scaleD)
        pD2, sD2 = optD.step(gD, sD, pD)

        def g_loss(pg):
            fake, nbsG = netG.apply(
                {"params": pg, "batch_stats": bsG2}, z, train=True,
                mutable=["batch_stats"])
            out, _ = netD.apply({"params": pD2, "batch_stats": bsD2},
                                fake, train=True, mutable=["batch_stats"])
            return bce(out, 1.0), nbsG["batch_stats"]

        scaleG = sG["scaler"].loss_scale
        (lossG, bsG3), gG = jax.value_and_grad(
            lambda p: (lambda l, a: (l * scaleG, a))(*g_loss(p)),
            has_aux=True)(pG)
        pG2, sG2 = optG.step(gG, sG, pG)
        return (pD2, bsD2, sD2, pG2, bsG3, sG2,
                lossD / scaleD + lossG / scaleG, gnorm)

    losses, gnorms = [], []
    state = (pD, bsD, sD, pG, bsG, sG)
    for i in range(ITERS):
        data = np.random.RandomState(100 + i)
        real = jnp.asarray(
            data.randn(batch, 64, 64, 3).astype(np.float32))
        z = jnp.asarray(data.randn(batch, 1, 1, nz).astype(np.float32))
        *state, loss, gnorm = train_step(*state, real, z)
        losses.append(float(loss))
        gnorms.append(float(gnorm))
    return losses, gnorms


CONFIGS = {
    "resnet_O0": functools.partial(run_resnet, "O0", "sgd"),
    "resnet_O0_adam": functools.partial(run_resnet, "O0", "adam"),
    "resnet_O1": functools.partial(run_resnet, "O1", "sgd"),
    "resnet_O2": functools.partial(run_resnet, "O2", "adam"),
    "resnet_O3": functools.partial(run_resnet, "O3", "adam"),
    "bert_O0": functools.partial(run_bert, "O0"),
    "bert_O2": functools.partial(run_bert, "O2"),
    "dcgan_O0": functools.partial(run_dcgan, "O0"),
    "dcgan_O2": functools.partial(run_dcgan, "O2"),
}

# which baseline each candidate compares against (optimizer must match).
# require_trains=False for the GAN: adversarial losses are not monotone,
# so the bar is trace closeness + finiteness only (the reference's DCGAN
# functional config asserts completion, not loss decrease).
PAIRS = [
    ("resnet_O1", "resnet_O0", "O1", True),
    ("resnet_O2", "resnet_O0_adam", "O2", True),
    ("resnet_O3", "resnet_O0_adam", "O3", True),
    ("bert_O2", "bert_O0", "O2", True),
    ("dcgan_O2", "dcgan_O0", "O2", False),
]


def capture(name):
    import time

    import jax

    if not TINY:
        from apex_tpu._compile_cache import enable_compile_cache

        enable_compile_cache()
    t0 = time.perf_counter()
    losses, gnorms = CONFIGS[name]()
    os.makedirs(TRACE_DIR, exist_ok=True)
    rec = {
        "config": name,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "iters": ITERS,
        "losses": losses,
        "grad_norms": gnorms,
        "total_incl_compile_s": round(time.perf_counter() - t0, 1),
    }
    path = os.path.join(TRACE_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps({"config": name, "wrote": path,
                      "final_loss": losses[-1],
                      "platform": rec["platform"],
                      "s": rec["total_incl_compile_s"]}), flush=True)


def compare():
    import numpy as np

    failures = []
    for cand, base, level, require_trains in PAIRS:
        try:
            with open(os.path.join(TRACE_DIR, f"{base}.json")) as f:
                b = json.load(f)
            with open(os.path.join(TRACE_DIR, f"{cand}.json")) as f:
                c = json.load(f)
        except FileNotFoundError as e:
            print(json.dumps({"pair": f"{cand} vs {base}",
                              "verdict": "MISSING", "detail": str(e)}))
            failures.append(cand)
            continue
        bl, cl = np.asarray(b["losses"]), np.asarray(c["losses"])
        bg, cg = np.asarray(b["grad_norms"]), np.asarray(c["grad_norms"])
        rel = (np.abs(bl - cl) / np.maximum(np.abs(bl), 1e-6)).max()
        # grad norms compare on the trailing half of the trace only: the
        # first adam/LAMB updates are sign(g) (m-hat/sqrt(v-hat) = g/|g|
        # at step 1), so precision rounding flips tiny-grad signs and the
        # early gnorm trajectory diverges transiently by design — both
        # runs must have re-converged by the back half
        half = len(bg) // 2
        relg = (np.abs(bg[half:] - cg[half:])
                / np.maximum(np.abs(bg[half:]), 1e-6)).max()
        trains = bool(cl[-1] < cl[0]) if require_trains else None
        ok = (rel < LOSS_RTOL[level] and relg < GNORM_RTOL[level]
              and np.isfinite(cl).all()
              and (trains is None or trains))
        print(json.dumps({
            "pair": f"{cand} vs {base}",
            "max_loss_rel": round(float(rel), 4),
            "max_gnorm_rel": round(float(relg), 4),
            "tol": [LOSS_RTOL[level], GNORM_RTOL[level]],
            "trains": trains,
            "verdict": "PASS" if ok else "FAIL",
        }), flush=True)
        if not ok:
            failures.append(cand)
    sys.exit(1 if failures else 0)


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "all"
    if name == "all":
        for n in CONFIGS:
            print(f"python tools/l1_onchip.py {n}")
        print("python tools/l1_onchip.py compare")
        return
    if name == "compare":
        return compare()
    if name not in CONFIGS:
        raise SystemExit(
            f"unknown config {name!r}; one of {list(CONFIGS)} / compare")
    capture(name)


if __name__ == "__main__":
    main()
