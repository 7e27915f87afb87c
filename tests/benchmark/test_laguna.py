"""The ``laguna`` family (Laguna-XS.2) and its cell ``laguna_xs2_train_8k``:
at a small size on the CPU (hidden 64, full layers of 4 query heads and
window layers of 8 over 2 K/V heads of 16, a window of 8, YaRN on half of
a full layer's head, the per-head gate, one dense layer of 96 and four
expert layers of 4 held experts of 8 top-3 of width 16 with a shared
expert, sequence 32, seeded weights) the program against
``benchmark/reference/laguna.py`` on loss and every tensor's gradient, and
three Adam steps through the cell; the planted faults; the shares of an
expert layer adding up to the uncut layer; the configuration file's
arithmetic; the counts; the new readers; the cell's files."""

import dataclasses
import functools
import json
import math
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import REPO, _rewrite, build_tiny_root, run_cell
from benchmark import families, harness, weights
from benchmark.reference import laguna as R
from benchmark.reference import transformer as T
from benchmark.xplane import Op, Trace

CELL = "laguna_xs2_train_8k"
CONFIG = REPO / "benchmark" / "configs" / "laguna-xs.2.json"
_FULL = json.loads(CONFIG.read_text())["rope_parameters"]["full_attention"]
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_attention_heads_per_layer=[4, 8, 8, 8, 4],
    sliding_window=8, intermediate_size=96, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts=4,
    num_experts_per_tok=3, vocab_size=128, max_position_embeddings=64,
    published={"num_experts": 8}, assumed={"held_rows_factor": 2.0},
    rope_parameters={"full_attention": dict(
        _FULL, original_max_position_embeddings=8, factor=4.0,
        beta_fast=4.0, attention_factor=1.2)})
# the program in bfloat16 against the float32 reference at this size, six
# seeds read on the CPU: loss_gap 2.1e-5 to 6.5e-5, grad_norm_gap
# 0.0017-0.036, grad_gap_p97 0.0012-0.013, change_norm_gap 0.0021-0.0043;
# the reference in fp8 on three seeds 1.6e-4 to 5.8e-4, 0.021-0.044,
# 0.013-0.023, 0.011-0.015; the window layers under full causal attention
# 1.7e-3 to 3.3e-3, 0.12-0.36, 0.077-0.18, 0.027-0.030. YaRN left off
# reads as the program does here (1.8e-5 to 6.5e-5, 0.027-0.042,
# 0.014-0.019, 0.0023-0.0088): at init the scores are too small for the
# frequencies or the attention factor to move a softmax by much
TINY_LIMITS = {"loss_gap": 1.2e-4, "grad_norm_gap": 0.08,
               "grad_gap_p97": 0.04, "change_norm_gap": 0.008}


@pytest.fixture(scope="module")
def laguna_root(tmp_path_factory):
    root = build_tiny_root(tmp_path_factory.mktemp("tiny_laguna"))
    data = root / "benchmark"
    _rewrite(data / "configs" / CONFIG.name, **TINY)
    _rewrite(data / "traffic" / "lm_seq8192_b2.json", batch=2, seq=32,
             flash_attention=False)
    (data / "limits" / f"{CELL}.json").write_text(json.dumps(TINY_LIMITS))
    return root


def real_arch():
    config = json.loads(CONFIG.read_text())
    return config, families.of("laguna").arch(config)


@functools.lru_cache(maxsize=None)
def _both_sides(root):
    """Loss and gradients of the program (compute type float32) and of
    the reference on one seeded batch: -> (cell, got, want), each side a
    (loss, canonical gradient dict)."""
    cell = harness.load_cell(CELL, root)
    arch, mix = cell.arch, cell.mix
    mine = families.of(arch)
    canon = weights.make(weights.seed_key(3), arch)
    batch = dict(next(families.batches(arch, mix, 3)))
    model = mine.build_model(arch, mix)
    model = model.clone(config=dataclasses.replace(
        model.config, compute_dtype=jnp.float32))
    loss, grads = jax.value_and_grad(mine.loss(model))(
        mine.to_program(canon, arch), batch)
    block = {k: jnp.asarray(v) for k, v in batch.items()}
    want = jax.value_and_grad(lambda p: R.loss_part(
        p, arch, block, R.totals(batch)))(canon)
    return cell, (loss, mine.from_program(grads, arch)), want


TENSORS = sorted(families.of("laguna").shapes(real_arch()[1]))


def test_loss_matches_in_float32(laguna_root):
    _, got, want = _both_sides(laguna_root)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)


@pytest.mark.parametrize("tensor", TENSORS)
def test_gradient_matches_in_float32(laguna_root, tensor):
    """Per-kind heads and rotary, the band, the gate, the fused QKV and
    SwiGLU layouts, the held experts and the shared one against the
    reference's plain layout."""
    _, got, want = _both_sides(laguna_root)
    a, b = np.asarray(got[1][tensor]), np.asarray(want[1][tensor])
    assert np.abs(b).max() > 0, "a tensor with no gradient tests nothing"
    np.testing.assert_allclose(a, b, atol=1e-5 * np.abs(b).max())


def test_tree_mapping_round_trips_and_fits_the_model(laguna_root):
    cell = harness.load_cell(CELL, laguna_root)
    arch = cell.arch
    mine = families.of(arch)
    canon = weights.make(weights.seed_key(1), arch)
    tree = mine.to_program(canon, arch)
    back = mine.from_program(tree, arch)
    assert set(back) == set(canon) == set(mine.shapes(arch))
    for k in canon:
        assert np.array_equal(np.asarray(back[k]), np.asarray(canon[k])), k
    batch = next(families.batches(arch, cell.mix, 0))
    shapes = jax.eval_shape(lambda: mine.build_model(arch, cell.mix).init(
        jax.random.PRNGKey(0), batch["tokens"]))
    want = jax.tree_util.tree_map(lambda x: x.shape, shapes["params"])
    assert jax.tree_util.tree_map(lambda x: x.shape, tree) == want
    layers = tree["transformer"]
    assert [layers[f"layer_{i}"]["self_attention"]["gate"]["weight"].shape
            for i in range(5)] == [(64, 4), (64, 8), (64, 8), (64, 8),
                                   (64, 4)]
    assert "dense_h_to_4h" in layers["layer_0"]["mlp"]
    assert "routed" in layers["layer_1"]["mlp"]


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_three_adam_steps_through_the_cell(laguna_root, seed):
    """amp O2 + FusedAdam on the benchmark's own step against the
    reference's three steps, through ``train_cell.run``."""
    result, compared = run_cell(laguna_root, CELL, seed=seed, seconds=0.2)
    assert result["correct"] is True, compared
    assert set(compared) == set(TINY_LIMITS) | {"compiles_in_window"}
    # the tables and the final norm; the dense layer's 10; the expert
    # layers' 14 each
    assert result["notes"]["tensors"] == 3 + 10 + 4 * 14


def _planted(change):
    """A fault on the program's side: its configuration changed before
    the step is first traced."""
    def plant(stepper):
        cfg = stepper.prog.model.config
        for key, value in change(cfg).items():
            object.__setattr__(cfg, key, value)
    return plant


def _without_windows(cfg):
    return {"attention_kinds": tuple(
        dataclasses.replace(k, window=None) for k in cfg.attention_kinds)}


def test_the_window_layers_under_full_causal_attention_are_not_correct(
        laguna_root):
    """The fault of the mechanism's own: every window layer attends over
    all its causal keys."""
    result, compared = run_cell(laguna_root, CELL, seed=5, seconds=0.2,
                                fault=_planted(_without_windows))
    assert result["correct"] is False, compared
    assert compared["grad_norm_gap"][0] > 1.4 * TINY_LIMITS["grad_norm_gap"]


def test_half_a_batch_is_not_correct(laguna_root):
    def half_batch(stepper):
        real = stepper.next_batch
        stepper.next_batch = lambda: {k: v[:v.shape[0] // 2]
                                      for k, v in real().items()}

    result, compared = run_cell(laguna_root, CELL, seed=5, seconds=0.2,
                                fault=half_batch)
    assert result["correct"] is False, compared


def test_a_rounding_to_fp8_fails_a_limit(laguna_root):
    from benchmark import compare, train_cell
    from benchmark.reference import lowp
    from benchmark.reference import train as ref_train

    cell = harness.load_cell(CELL, laguna_root)
    want = train_cell.reference_readings(cell, 7)
    control = train_cell.reference_readings(cell, 7, ref_train.Reference(
        cell.arch, cell.mix["optimizer"], cell.mix["hp"], quant=lowp.fp8))
    numbers, _ = compare.train_numbers(control, want, cell.limits)
    correct, compared = harness.compare(numbers, cell.limits)
    assert correct is False, compared


# ---- the reference's own band: its blocks of queries against the keys
# that hold their band are a plain masked softmax over all of them

def test_the_reference_s_band_is_a_plain_masked_softmax():
    _, real = real_arch()
    arch = dict(real, hidden=32, head_dim=16, kv_heads=2,
                layer_heads=(4, 4), layer_types=(R.FULL, R.WINDOW),
                window=100, gate=True)
    s, rng = 512, np.random.default_rng(0)
    lp = {k: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
          for k, shape in (("wq", (32, 64)), ("wk", (32, 32)),
                           ("wv", (32, 32)), ("wo", (64, 32)),
                           ("wg", (32, 4)))}
    u = jnp.asarray(rng.normal(size=(s, 32)), jnp.float32)
    got = R.attention(u, lp, arch, 1, T.identity)

    inv, scale = R.layer_rotary(arch, 1, 16)
    q = R.rotary((u @ lp["wq"]).reshape(s, 4, 16), inv, scale)
    k = R.rotary((u @ lp["wk"]).reshape(s, 2, 16), inv, scale)
    v = (u @ lp["wv"]).reshape(s, 2, 16)
    k, v = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
    scores = jnp.einsum("snd,tnd->nst", q, k,
                        precision=T.HIGHEST) / math.sqrt(16)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where((j <= i) & (i - j < 100), scores, -1e30),
                       -1)
    o = jnp.einsum("nst,tnd->snd", p, v, precision=T.HIGHEST)
    o = o * jax.nn.sigmoid(u @ lp["wg"])[..., None]
    want = o.reshape(s, 64) @ lp["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_the_reference_s_yarn_is_the_published_range():
    """Frequencies 0-4 of a full layer's 32 keep theta 500000's, 16-31 are
    divided by 64, and the ramp lies between (HF's correction range with
    beta_fast 64 and beta_slow 1 over 4096 positions)."""
    _, arch = real_arch()
    inv, scale = R.yarn_inverse_frequencies(arch, 64)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(np.asarray(inv)[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv)[16:], plain[16:] / 64,
                               rtol=1e-6)
    assert np.all(np.asarray(inv)[6:16] < plain[6:16])
    assert scale == pytest.approx(0.1 * math.log(64) + 1)


# ---- the shares add up: at a small size the four shares' routed parts of
# one layer (four pairs of experts) plus what every chip computes alike,
# attention and its gate, the router and the shared expert, counted once,
# are the uncut layer's output

HID, S_ = 48, 24
WHOLE = {"hidden": HID, "eps": 1e-6, "experts": 8, "experts_held": 8,
         "expert_offset": 0, "top_k": 3, "routed_scale": 2.5, "ffn": 16,
         "shared_ffn": 16, "head_dim": 16, "kv_heads": 2,
         "layer_heads": (4,), "layer_types": (R.WINDOW,), "window": 8,
         "window_theta": 10000.0, "window_percent": 1.0, "gate": True}


def _layer_weights(seed=0):
    rng = np.random.default_rng(seed)
    shapes = {"e_router": (HID, 8), "e_gate": (8, HID, 16),
              "e_up": (8, HID, 16), "e_down": (8, 16, HID),
              "s_gate": (HID, 16), "s_up": (HID, 16), "s_down": (16, HID),
              "wq": (HID, 64), "wk": (HID, 32), "wv": (HID, 32),
              "wo": (64, HID), "wg": (HID, 4)}
    return {k: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
            for k, s in shapes.items()}


def _held(lp, off, n):
    return dict(lp, **{k: lp[k][off:off + n]
                       for k in ("e_gate", "e_up", "e_down")})


def _reference_layer(lp, x, off, n):
    """One layer's output on the chip that holds experts ``off .. off +
    n``: ``h + E_held(h) + S(h)``, ``h = x + attention(x)`` (no norms:
    they are every chip's alike)."""
    arch = dict(WHOLE, expert_offset=off, experts_held=n)
    h = x + R.attention(x, lp, arch, 0, T.identity)
    return h + R.expert_ffn(h, _held(lp, off, n), arch, T.identity)


def _program_layer(lp, x, off, n):
    from apex_tpu.models import AttentionKind, TransformerConfig
    from apex_tpu.models.transformer_lm import ParallelAttention, _make_mlp

    cfg = TransformerConfig(
        hidden_size=HID, num_layers=1, num_attention_heads=4, head_dim=16,
        num_query_groups=2, ffn_hidden_size=80, moe_ffn_hidden_size=16,
        vocab_size=64, compute_dtype=jnp.float32, normalization="rmsnorm",
        activation="swiglu", attention_bias=False,
        position_embedding_type="rope", use_flash_attention=False,
        attention_kinds=(AttentionKind(R.WINDOW, window=8, heads=4),),
        layer_attention=(R.WINDOW,), attention_output_gate=True,
        num_moe_experts=8, moe_top_k=3, moe_router_score="sigmoid_bias",
        moe_routed_scaling_factor=2.5, moe_dispatch_mode="ragged",
        moe_shared_expert_size=16, moe_shared_expert_gated=False,
        **({} if n == 8 else dict(moe_local_experts=n,
                                  moe_expert_offset=off,
                                  moe_capacity_factor=8.0 / n)))
    lp = _held(lp, off, n)
    g, d = 2, 16
    kv = jnp.concatenate([lp["wk"].reshape(HID, g, d),
                          lp["wv"].reshape(HID, g, d)], -1)
    attention = {"query_key_value": {"weight": jnp.concatenate(
        [lp["wq"], kv.reshape(HID, 2 * g * d)], -1)},
        "gate": {"weight": lp["wg"]}, "dense": {"weight": lp["wo"]}}
    mlp = {
        "routed": {"router": {"gate_weight": lp["e_router"],
                              "e_score_correction_bias": jnp.zeros((8,))},
                   "experts": {"w1": jnp.concatenate(
                       [lp["e_gate"], lp["e_up"]], -1), "w2": lp["e_down"]}},
        "shared_gate_up": {"weight": jnp.concatenate(
            [lp["s_gate"], lp["s_up"]], -1)},
        "shared_down": {"weight": lp["s_down"]}}
    h = x[:, None, :]
    h = h + ParallelAttention(cfg).apply({"params": attention}, h)
    layer = _make_mlp(cfg, True).clone(warn_on_dropped_losses=False)
    return (h + layer.apply({"params": mlp}, h))[:, 0]


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up(side):
    lp = _layer_weights(seed=5)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(S_, HID)),
                    jnp.float32)
    share = {"program": _program_layer, "reference": _reference_layer}[side]
    whole = _reference_layer(lp, x, 0, 8)
    # every share computes attention, the router and the shared expert
    # alike: count them once
    common = _reference_layer(lp, x, 0, 0)
    assert float(jnp.abs(whole - common).max()) > 0.1
    parts = [share(lp, x, off, 2) - common for off in range(0, 8, 2)]
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    atol = 2e-5 * float(jnp.abs(whole).max())
    np.testing.assert_allclose(sum(parts) + common, whole, atol=atol)
    np.testing.assert_allclose(share(lp, x, 0, 8), whole, atol=atol)


# ---- the configuration file and the counts

def test_published_widths_are_kept_and_the_cut_is_listed():
    config, arch = real_arch()
    pub = config["published"]
    for key, value in pub.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "num_attention_heads_per_layer"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert config[key] == pub[key][:5], key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 12544)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"] if c["name"] == "laguna-xs.2"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert (arch["hidden"], arch["layer_heads"], arch["kv_heads"],
            arch["head_dim"], arch["window"], arch["dense_ffn"], arch["ffn"],
            arch["shared_ffn"], arch["top_k"], arch["routed_scale"]) == (
                2048, (48, 64, 64, 64, 48), 8, 128, 512, 8192, 512, 512, 8,
                2.5)
    assert arch["layer_types"] == ("full_attention",) + 3 * (
        "sliding_attention",) + ("full_attention",)
    assert arch["mlp_types"] == ("dense",) + 4 * ("sparse",)
    assert (arch["full_theta"], arch["full_percent"], arch["yarn_factor"],
            arch["yarn_original"], arch["window_theta"],
            arch["window_percent"]) == (500000.0, 0.5, 64.0, 4096, 10000.0,
                                        1.0)
    assert (arch["experts"], arch["experts_held"], arch["layers"]) == (
        256, 16, 5)
    assert arch["vocab"] == arch["vocab_real"] == 100352 // 8
    assert arch["vocab"] % 128 == 0 and len(config["deployment"]) > 40
    # room for every assignment: the held share drops nothing
    assert arch["held_rows_factor"] == arch["experts"] / arch["experts_held"]
    for key in ("gating", "router", "router_bias", "aux_loss", "qk_norm",
                "rotary", "held_rows_why", "init", "precision",
                "optimizer", "depth_why"):
        assert len(config["assumed"][key]) > 15, key


def test_published_is_the_catalog_s_row():
    # MODEL_CATALOG names a JSONL catalog of published architectures, one
    # {"source_url", "config"} row per model
    catalog = pathlib.Path(os.environ.get("MODEL_CATALOG", ""))
    if not catalog.is_file():
        pytest.skip("MODEL_CATALOG names no catalog file")
    config, _ = real_arch()
    (row,) = [r for r in map(json.loads, catalog.read_text().splitlines())
              if r["source_url"] == config["source"]]
    assert config["published"] == row["config"]


def test_parameters_and_bytes_by_hand():
    _, arch = real_arch()
    h = 2048

    def attention(n):
        return 2 * h * n * 128 + 2 * h * 8 * 128 + h * n + 2 * h

    expert = h * 256 + 3 * h * 512 + 16 * 3 * h * 512
    total = (attention(48) + 3 * h * 8192 + 3 * (attention(64) + expert)
             + attention(48) + expert + 2 * 12544 * h + h)
    assert weights.n_params(arch) == total == 490_297_344
    norms = 5 * 2 * h + h
    assert total - norms == 490_274_816
    assert round(total * 14 / 1e9, 2) == 6.86      # amp O2 + Adam
    assert round(total * 20 / 1e9, 2) == 9.81      # the reference's five


def test_flops_per_token_by_hand():
    from benchmark import flops

    _, arch = real_arch()
    fam = families.of(arch)
    h = 2048

    def attention(n):
        return 2 * h * n * 128 + 2 * h * 8 * 128 + h * n

    expert = h * 256 + 3 * h * 512 + 0.5 * 3 * h * 512
    want = (2 * attention(48) + 3 * attention(64) + 3 * h * 8192
            + 4 * expert + h * 12544)
    assert flops.matmul_params(arch) == int(want)
    band = 512 * 513 / 2 + (8192 - 512) * 512
    causal = 8192 * 8193 / 2
    fwd = 2 * int(want) + 4 * 128 * (2 * 48 * causal + 3 * 64 * band) / 8192
    assert flops.fwd_flops_per_token(arch, 8192) == pytest.approx(fwd)
    assert fam.window_attention_train_flops_per_step(arch, 2, 8192) == \
        pytest.approx(12 * 128 * band * 2 * 3 * 64)
    ins = 2 * 128 * (64 + 16)
    fwd_bytes = ins + 2 * 64 * 128 + 4 * 64
    assert fam.window_attention_train_bytes_per_step(arch, 2, 8192) == \
        3 * 2 * 8192 * (2 * fwd_bytes + 2 * 64 * 128 + ins)


# ---- the readers this family brings, on a synthetic trace

OPS = [
    Op(0, "fusion.1", 0.0, 1.0, "fusion", "kOutput"),
    Op(0, "window_attention_flash_fwd.2", 1.0, 2.0, "custom-call"),
    Op(0, "fusion.3", 2.0, 2.5, "fusion", "kLoop"),
    Op(0, "window_attention_flash_dq.4", 3.0, 4.0, "custom-call"),
    Op(0, "window_attention_flash_dkv.5", 4.0, 5.0, "custom-call"),
    Op(0, "self_attention_flash_fwd.6", 5.0, 6.0, "custom-call"),
    Op(0, "fusion.7", 6.0, 7.0, "fusion", "kOutput"),
]
BLOCKS = {"fusion.1": ("attention/window/qkv", "forward"),
          "window_attention_flash_fwd.2": ("attention/window/kernel",
                                           "forward"),
          "fusion.3": ("attention/window/gate", "recompute"),
          "window_attention_flash_dq.4": ("attention/window/kernel",
                                          "backward"),
          "window_attention_flash_dkv.5": ("attention/window/kernel",
                                           "backward"),
          "self_attention_flash_fwd.6": ("attention/kernel", "forward"),
          "fusion.7": ("moe", "forward")}


def _ctx(ops=OPS, blocks=BLOCKS, arch=None):
    return {"trace": Trace(ops, []) if ops is not None else None,
            "window": {"steps": 2, "elapsed_s": 10.0},
            "scope_blocks": blocks, "arch": arch or real_arch()[1],
            "mix": {"batch": 2, "seq": 8192},
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _read(name, context):
    return harness.load_reader(name, REPO)(context)


def test_window_attention_ms_per_step_is_the_union_of_the_scope():
    # the trace spans 7 s of the two steps' 10 s: 1.4 steps traced; the
    # window layers' operations cover 4.5 s of it
    assert _read("window_attention_ms_per_step", _ctx()) == \
        pytest.approx(4.5e3 / 1.4)
    assert _read("window_attention_ms_per_step", _ctx(ops=None)) is None
    assert _read("window_attention_ms_per_step",
                 _ctx(blocks=None)) is None        # a program with no table
    assert _read("window_attention_ms_per_step",
                 _ctx(blocks={"fusion.1": ("attention/qkv",
                                           "forward")})) is None


def test_window_attention_roofline_by_hand():
    arch = real_arch()[1]
    fam = families.of(arch)
    got = _read("window_attention_roofline", _ctx())
    flop_s = fam.window_attention_train_flops_per_step(arch, 2, 8192) \
        / 197e12
    byte_s = fam.window_attention_train_bytes_per_step(arch, 2, 8192) \
        / 819e9
    assert flop_s > byte_s       # the FLOPs bound it at 8192 positions
    # 3 s in the three kernels named window_attention_* in 1.4 steps; the
    # full layers' kernel is not this metric's
    assert got == pytest.approx(100 * flop_s * 1.4 / 3.0)
    assert _read("window_attention_roofline", _ctx(ops=None)) is None
    assert _read("window_attention_roofline", _ctx(ops=OPS[5:])) is None
    assert _read("window_attention_roofline",
                 dict(_ctx(), peaks=None)) is None
    moonlight = families.of("deepseek_v3").arch(json.loads(
        (REPO / "benchmark" / "configs" / "moonlight-16b-a3b.json")
        .read_text()))
    assert _read("window_attention_roofline", _ctx(arch=moonlight)) is None


def test_the_cell_s_files_are_found_by_name():
    cell = harness.load_cell(CELL, REPO)
    assert cell.chips == 1 and cell.mix["kind"] == "train"
    assert cell.config_name == "laguna-xs.2"
    assert cell.traffic_name == "lm_seq8192_b2"
    assert (cell.mix["batch"], cell.mix["seq"]) == (2, 8192)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s_per_chip", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"window_attention_roofline", "window_attention_ms_per_step",
            "moe_ms_per_step", "train_mfu_pct", "unscoped_time_share_pct",
            "attention_kernel_fwd_ms_per_step",
            "attention_kernel_bwd_ms_per_step",
            "attention_copy_ms_per_step", "recompute_time_share_pct",
            "setup_compile_s", "setup_unattributed_s"} <= names
    assert not names & {"attention_roofline", "indexer_ms_per_step",
                        "sparse_attention_roofline", "ssm_ms_per_step",
                        "mla_ms_per_step", "blockdiff_attention_roofline"}
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"], REPO))
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
    assert set(cell.limits) == {"loss_gap", "grad_norm_gap", "grad_gap_p97",
                                "change_norm_gap"}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(w["why"]) <= 200
    # membership, not position: a later configuration's PR appends its own
    assert w["config"] in [c["name"] for c in bench["configs"]]
    for name in ("window_attention_roofline",
                 "window_attention_ms_per_step"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s_per_chip"
    (rate,) = [m for m in bench["end_to_end"]
               if m["name"] == "train_tokens_per_s_per_chip"]
    assert CELL in rate["workloads"]


# ---- the committed limits against the chip's readings

# measured on one v5e chip (benchmark/limits/<cell>.json "readings"; the
# program's highest of 20 seeds by number)
PROGRAM_HIGHEST = {"loss_gap": 4.44e-5, "grad_norm_gap": 0.001894,
                   "grad_gap_p97": 0.001394, "change_norm_gap": 0.000678}
CONTROL_FP8 = {
    3000: {"loss_gap": 5.238e-5, "grad_norm_gap": 0.006195,
           "grad_gap_p97": 0.005655, "change_norm_gap": 0.001988},
    10919: {"loss_gap": 1.688e-4, "grad_norm_gap": 0.005279,
            "grad_gap_p97": 0.004921, "change_norm_gap": 0.000734},
    500003: {"loss_gap": 9.461e-5, "grad_norm_gap": 0.008373,
             "grad_gap_p97": 0.006141, "change_norm_gap": 0.001447}}
PLANTED = {
    "half_batch 3000": {"loss_gap": 1.128e-3, "grad_norm_gap": 0.4617,
                        "grad_gap_p97": 0.4352, "change_norm_gap": 0.1251},
    "half_batch 500003": {"loss_gap": 5.712e-4, "grad_norm_gap": 0.4582,
                          "grad_gap_p97": 0.4359, "change_norm_gap": 0.1232},
    "window_causal 3000": {"loss_gap": 4.036e-4, "grad_norm_gap": 0.3953,
                           "grad_gap_p97": 0.3736,
                           "change_norm_gap": 0.005322},
    "window_causal 10919": {"loss_gap": 2.558e-4, "grad_norm_gap": 0.3911,
                            "grad_gap_p97": 0.3709,
                            "change_norm_gap": 0.006102},
    "yarn_off 3000": {"loss_gap": 6.05e-4, "grad_norm_gap": 0.4466,
                      "grad_gap_p97": 0.4304, "change_norm_gap": 0.005376},
    "yarn_off 10919": {"loss_gap": 7.776e-4, "grad_norm_gap": 0.4439,
                       "grad_gap_p97": 0.4188, "change_norm_gap": 0.006739}}


@pytest.mark.parametrize("what,numbers,want", [
    ("program", PROGRAM_HIGHEST, True),
    *[(f"control_fp8 {seed}", numbers, False)
      for seed, numbers in CONTROL_FP8.items()],
    *[(what, numbers, False) for what, numbers in PLANTED.items()],
    ("state unchanged", {"loss_gap": 0.0, "grad_norm_gap": 1.0,
                         "grad_gap_p97": 1.0, "change_norm_gap": 1.0}, False),
])
def test_the_committed_limits_hold_the_chip_s_readings(what, numbers, want):
    cell = harness.load_cell(CELL, REPO)
    correct, compared = harness.compare(numbers, cell.limits)
    assert correct is want, compared
    if what.startswith("control"):
        # the numbers that hold the control, with room
        assert numbers["grad_gap_p97"] > 2 * cell.limits["grad_gap_p97"]
        assert numbers["grad_norm_gap"] > 1.7 * cell.limits["grad_norm_gap"]
    if what.startswith(("half_batch", "window_causal", "yarn_off")):
        # every number sees a planted fault, the loss with twice the room
        assert all(numbers[k] > (2 if k == "loss_gap" else 1.7)
                   * cell.limits[k] for k in numbers)
    if what == "program":
        for name, value in numbers.items():
            assert value * 1.5 < cell.limits[name], name
