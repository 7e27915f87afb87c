"""The ``deepseek_v3`` family (Moonlight-16B-A3B) at a small size on the
CPU (hidden 48, 4 heads of 16 + 8 beside values of 16 over a latent of 24,
one dense layer of 80 and two expert layers of 16 experts top-3 of width
24 with 4 held and 2 shared, sequence 32, seeded weights): the program
against ``benchmark/reference/deepseek_v3.py`` on loss, every tensor's
gradient and three Adam steps; the shares of an expert layer adding up to
the uncut layer; the configuration file's arithmetic; the counts; the new
readers."""

import dataclasses
import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import REPO, _rewrite, build_tiny_root, run_cell
from benchmark import families, harness, weights
from benchmark.reference import deepseek_v3 as R
from benchmark.reference import transformer as T
from benchmark.xplane import Op, Trace

CELL = "moonlight_16b_a3b_train_8k"
CONFIG = REPO / "benchmark" / "configs" / "moonlight-16b-a3b.json"
TINY = dict(hidden_size=48, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=24, intermediate_size=80,
            moe_intermediate_size=24, n_routed_experts=4,
            num_experts_per_tok=3, vocab_size=128,
            max_position_embeddings=64, published={"n_routed_experts": 16},
            assumed={"held_rows_factor": 4.0, "aux_loss_alpha": 0.01})
# the program in bfloat16 against the float32 reference at this size, four
# seeds read on the CPU: loss_gap up to 3e-5, grad_norm_gap 0.0008-0.0055,
# grad_gap_p97 0.0006-0.0025, change_norm_gap 0.0017-0.0036; the reference
# in fp8 on three seeds reads 0.017-0.064, 0.011-0.026 and 0.009-0.011
TINY_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 0.012,
               "grad_gap_p97": 0.006, "change_norm_gap": 0.08}


@pytest.fixture(scope="module")
def moon_root(tmp_path_factory):
    root = build_tiny_root(tmp_path_factory.mktemp("tiny_moonlight"))
    data = root / "benchmark"
    _rewrite(data / "configs" / CONFIG.name, **TINY)
    _rewrite(data / "traffic" / "lm_seq8192_b2.json", batch=4, seq=32,
             flash_attention=False)
    (data / "limits" / f"{CELL}.json").write_text(json.dumps(TINY_LIMITS))
    return root


def real_arch():
    config = json.loads(CONFIG.read_text())
    return config, families.of("deepseek_v3").arch(config)


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


TENSORS = sorted(families.of("deepseek_v3").shapes(real_arch()[1]))
# the tiny root has three layers: its tensors are a subset of the cell's
TINY_TENSORS = [t for t in TENSORS if t[:2] not in ("l3", "l4")]


def test_loss_matches_in_float32(moon_root):
    _, got, want = _both_sides(moon_root)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)


def test_the_balance_loss_is_in_the_loss(moon_root):
    """At alpha 0.01 the three-layer model's two expert layers add about
    0.02 to a cross-entropy of log(128): left out, the losses differ."""
    cell, got, _ = _both_sides(moon_root)
    batch = dict(next(families.batches(cell.arch, cell.mix, 3)))
    block = {k: jnp.asarray(v) for k, v in batch.items()}
    without = R.loss_part(weights.make(weights.seed_key(3), cell.arch),
                          dict(cell.arch, aux_alpha=0.0), block,
                          R.totals(batch))
    assert 0.015 < float(got[0]) - float(without) < 0.03


@pytest.mark.parametrize("tensor", TINY_TENSORS)
def test_gradient_matches_in_float32(moon_root, tensor):
    """The latent attention's separate projections, the ragged gated
    experts and the fused SwiGLUs against the published layout, the loop
    over experts and the plain ones."""
    _, got, want = _both_sides(moon_root)
    a, b = np.asarray(got[1][tensor]), np.asarray(want[1][tensor])
    assert np.abs(b).max() > 0, "a tensor with no gradient tests nothing"
    np.testing.assert_allclose(a, b, atol=1e-5 * np.abs(b).max())


def test_tree_mapping_round_trips_and_fits_the_model(moon_root):
    cell = harness.load_cell(CELL, moon_root)
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
    # the published columns are a head's [nope | rope]; the program's are
    # [every head's nope | every head's rope]
    wq = np.asarray(canon["l0.wq"]).reshape(48, 4, 24)
    mine_q = np.asarray(
        tree["transformer"]["layer_0"]["self_attention"]["q_proj"]["weight"])
    np.testing.assert_array_equal(mine_q[:, 16:32], wq[:, 1, :16])
    np.testing.assert_array_equal(mine_q[:, 64 + 8:64 + 16], wq[:, 1, 16:])
    assert float(canon["l0.kvn_g"].min()) == 1


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_three_adam_steps_through_the_cell(moon_root, seed):
    """amp O2 + FusedAdam on the benchmark's own step against the
    reference's three steps, through ``train_cell.run``."""
    result, compared = run_cell(moon_root, CELL, seed=seed, seconds=0.2)
    assert result["correct"] is True, compared
    assert set(compared) == set(TINY_LIMITS) | {"compiles_in_window"}
    assert result["notes"]["tensors"] == 3 + 10 + 2 * 14


def test_half_a_batch_is_not_correct(moon_root):
    def half_batch(stepper):
        real = stepper.next_batch
        stepper.next_batch = lambda: {k: v[:v.shape[0] // 2]
                                      for k, v in real().items()}

    result, compared = run_cell(moon_root, CELL, seed=5, seconds=0.2,
                                fault=half_batch)
    assert result["correct"] is False, compared


def test_a_rounding_to_fp8_fails_a_limit(moon_root):
    """The reference in fp8 put in the program's place reads a wider gap
    than the bfloat16 program and is not correct by the tiny limits."""
    from benchmark import compare, train_cell
    from benchmark.reference import lowp
    from benchmark.reference import train as ref_train

    cell = harness.load_cell(CELL, moon_root)
    want = train_cell.reference_readings(cell, 7)
    control = train_cell.reference_readings(cell, 7, ref_train.Reference(
        cell.arch, cell.mix["optimizer"], cell.mix["hp"], quant=lowp.fp8))
    numbers, _ = compare.train_numbers(control, want, cell.limits)
    correct, compared = harness.compare(numbers, cell.limits)
    assert correct is False, compared
    assert numbers["grad_gap_p97"] > 1.5 * TINY_LIMITS["grad_gap_p97"]


# ---- the shares add up: at a small size the eight shares' routed parts of
# one expert layer (eight pairs of experts) plus the shared experts and the
# router, which every chip computes alike, counted once, are the uncut
# layer's output

HID, S_ = 48, 24
FULL = {"hidden": HID, "eps": 1e-5, "experts": 16, "experts_held": 16,
        "expert_offset": 0, "top_k": 3, "routed_scale": 2.446, "ffn": 24,
        "shared_ffn": 48}


def _layer_weights(seed=0):
    rng = np.random.default_rng(seed)
    shapes = {"e_router": (HID, 16), "e_gate": (16, HID, 24),
              "e_up": (16, HID, 24), "e_down": (16, 24, HID),
              "s_gate": (HID, 48), "s_up": (HID, 48), "s_down": (48, HID)}
    return {k: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
            for k, s in shapes.items()}


def _held(lp, off, n):
    return dict(lp, **{k: lp[k][off:off + n]
                       for k in ("e_gate", "e_up", "e_down")})


def _reference_share(lp, x, off, n):
    arch = dict(FULL, expert_offset=off, experts_held=n)
    return R.expert_ffn(x, _held(lp, off, n), arch, T.identity)[0]


def _program_share(lp, x, off, n):
    from apex_tpu.models import TransformerConfig
    from apex_tpu.models.transformer_lm import _make_mlp

    cfg = TransformerConfig(
        hidden_size=HID, num_layers=1, num_attention_heads=4,
        ffn_hidden_size=80, moe_ffn_hidden_size=24, vocab_size=64,
        compute_dtype=jnp.float32, normalization="rmsnorm",
        activation="swiglu", num_moe_experts=16, moe_top_k=3,
        moe_router_score="sigmoid_bias", moe_routed_scaling_factor=2.446,
        moe_dispatch_mode="ragged", moe_shared_expert_size=48,
        moe_shared_expert_gated=False,
        **({} if n == 16 else dict(moe_local_experts=n,
                                   moe_expert_offset=off,
                                   moe_capacity_factor=16.0 / n)))
    lp = _held(lp, off, n)
    params = {
        "routed": {"router": {"gate_weight": lp["e_router"],
                              "e_score_correction_bias": jnp.zeros((16,))},
                   "experts": {"w1": jnp.concatenate(
                       [lp["e_gate"], lp["e_up"]], -1), "w2": lp["e_down"]}},
        "shared_gate_up": {"weight": jnp.concatenate(
            [lp["s_gate"], lp["s_up"]], -1)},
        "shared_down": {"weight": lp["s_down"]}}
    layer = _make_mlp(cfg, True).clone(warn_on_dropped_losses=False)
    return layer.apply({"params": params}, x[:, None, :])[:, 0]


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up(side):
    lp = _layer_weights(seed=5)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(S_, HID)),
                    jnp.float32)
    share = {"program": _program_share, "reference": _reference_share}[side]
    whole = _reference_share(lp, x, 0, 16)
    assert float(jnp.abs(whole).max()) > 0.1
    # every share computes the shared experts and the router alike: count
    # them once
    shared = _reference_share(lp, x, 0, 0)
    assert float(jnp.abs(shared).max()) > 0.1
    parts = [share(lp, x, off, 2) - shared for off in range(0, 16, 2)]
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    atol = 2e-5 * float(jnp.abs(whole).max())
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=atol)
    np.testing.assert_allclose(share(lp, x, 0, 16), whole, atol=atol)


# ---- the configuration file and the counts

def test_published_widths_are_kept_and_the_cut_is_listed():
    config, arch = real_arch()
    pub = config["published"]
    for key, value in pub.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 20480)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"]
                if c["name"] == "moonlight-16b-a3b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert (arch["hidden"], arch["heads"], arch["nope_dim"],
            arch["rope_dim"], arch["v_dim"], arch["kv_rank"],
            arch["dense_ffn"], arch["ffn"], arch["shared_ffn"],
            arch["top_k"], arch["routed_scale"], arch["theta"]) == (
                2048, 16, 128, 64, 128, 512, 11264, 1408, 2816, 6, 2.446,
                50000.0)
    assert (arch["experts"], arch["experts_held"], arch["dense_layers"],
            arch["layers"]) == (64, 8, 1, 5)
    assert arch["vocab"] == arch["vocab_real"] == 163840 // 8
    assert arch["vocab"] % 128 == 0 and len(config["deployment"]) > 40
    assert arch["aux_alpha"] == 0.001 and arch["positions"] == 8192
    # room for every assignment: the held share drops nothing
    assert arch["held_rows_factor"] == arch["experts"] / arch["experts_held"]
    for key in ("aux_loss_alpha_why", "e_score_correction_bias",
                "norm_topk_prob", "held_rows_why", "rotary", "init",
                "precision", "optimizer"):
        assert len(config["assumed"][key]) > 40, key
    assert "Muon" in config["assumed"]["optimizer"]


def test_published_is_the_catalog_s_row():
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog beside the guide here")
    config, _ = real_arch()
    (row,) = [r for r in map(json.loads, catalog.read_text().splitlines())
              if r["source_url"] == config["source"]]
    assert config["published"] == row["config"]


def test_parameters_and_bytes_by_hand():
    _, arch = real_arch()
    attention = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 \
        + 2048 * 2048 + 512
    dense = attention + 3 * 2048 * 11264 + 2 * 2048
    expert = attention + 2048 * 64 + 3 * 2048 * 2816 \
        + 8 * 3 * 2048 * 1408 + 2 * 2048
    total = dense + 4 * expert + 2 * 20480 * 2048 + 2048
    assert weights.n_params(arch) == total == 568_484_352
    assert round(total * 14 / 1e9, 2) == 7.96      # amp O2 + Adam
    assert round(total * 20 / 1e9, 2) == 11.37     # the reference's five


def test_flops_per_token_by_hand():
    from benchmark import flops

    _, arch = real_arch()
    fam = families.of(arch)
    attention = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 2048 * 2048
    # an expected 6 x 8/64 of an expert's three matrices a token
    expert = 2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408
    want = 5 * attention + 3 * 2048 * 11264 + 4 * expert + 2048 * 20480
    assert flops.matmul_params(arch) == int(want)
    fwd = 2 * int(want) + 5 * 2 * 8192 * 16 * (192 + 128)
    assert flops.fwd_flops_per_token(arch, 8192) == pytest.approx(fwd)
    pairs = 2 * 16 * 8192 * 8193 / 2
    assert fam.mla_attention_train_flops_per_step(arch, 2, 8192) == \
        pairs * 5 * ((2 * 192 + 2 * 128) + 2 * (3 * 192 + 2 * 128))
    ins = 2 * (16 * 192 + 16 * 128 + 64 + 16 * 128)
    fwd_bytes = ins + 2 * 16 * 128 + 4 * 16
    assert fam.mla_attention_train_bytes_per_step(arch, 2, 8192) == \
        2 * 8192 * 5 * (2 * fwd_bytes + 2 * 16 * 128 + ins)


# ---- the readers this family brings, on a synthetic trace

OPS = [
    Op(0, "fusion.1", 0.0, 1.0, "fusion", "kOutput"),
    Op(0, "mla_attention_flash_fwd.2", 1.0, 2.0, "custom-call"),
    Op(0, "fusion.3", 2.0, 2.5, "fusion", "kLoop"),
    Op(0, "mla_attention_flash_dq.4", 3.0, 4.0, "custom-call"),
    Op(0, "mla_attention_flash_dkv.5", 4.0, 5.0, "custom-call"),
    Op(0, "self_attention_flash_fwd.6", 5.0, 6.0, "custom-call"),
    Op(0, "fusion.7", 6.0, 7.0, "fusion", "kOutput"),
]
BLOCKS = {"fusion.1": ("mla/q_proj", "forward"),
          "mla_attention_flash_fwd.2": ("mla/kernel", "forward"),
          "fusion.3": ("mla/rope", "recompute"),
          "mla_attention_flash_dq.4": ("mla/kernel", "backward"),
          "mla_attention_flash_dkv.5": ("mla/kernel", "backward"),
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


def test_mla_ms_per_step_is_the_union_of_the_block_s_parts():
    # the trace spans 7 s of the two steps' 10 s: 1.4 steps traced; the
    # latent attention's operations cover 4.5 s of it
    assert _read("mla_ms_per_step", _ctx()) == pytest.approx(4.5e3 / 1.4)
    assert _read("mla_ms_per_step", _ctx(ops=None)) is None
    assert _read("mla_ms_per_step", _ctx(blocks=None)) is None   # the parent
    assert _read("mla_ms_per_step",
                 _ctx(blocks={"fusion.1": ("mlp", "forward")})) is None


def test_mla_copy_ms_per_step_is_the_block_s_copies():
    # block ``attention``'s copies are attention_copy_ms_per_step's, a
    # fusion is no copy, and asynchronous copies keep no scope
    ops = OPS + [Op(0, "copy.8", 7.0, 7.25, "copy"),
                 Op(0, "copy.9", 7.25, 7.5, "copy"),
                 Op(0, "copy.10", 7.5, 8.0, "copy"),
                 Op(0, "copy-done.11", 8.0, 8.5, "copy-done")]
    blocks = dict(BLOCKS, **{"copy.8": ("mla/rope", "forward"),
                             "copy.9": ("mla", "backward"),
                             "copy.10": ("attention", "forward")})
    # 8.5 s of the two steps' 10 s traced: 1.7 steps, 0.5 s of copies
    assert _read("mla_copy_ms_per_step", _ctx(ops, blocks)) == \
        pytest.approx(0.5e3 / 1.7)
    assert _read("mla_copy_ms_per_step", _ctx()) is None    # no copy ran
    assert _read("mla_copy_ms_per_step", _ctx(ops=None)) is None
    assert _read("mla_copy_ms_per_step", _ctx(ops, None)) is None  # parent
    assert _read("attention_copy_ms_per_step", _ctx(ops, blocks)) == \
        pytest.approx(0.5e3 / 1.7)


def test_mla_attention_roofline_by_hand():
    arch = real_arch()[1]
    fam = families.of(arch)
    got = _read("mla_attention_roofline", _ctx())
    flop_s = fam.mla_attention_train_flops_per_step(arch, 2, 8192) / 197e12
    byte_s = fam.mla_attention_train_bytes_per_step(arch, 2, 8192) / 819e9
    assert flop_s > 10 * byte_s     # the FLOPs bound it at 8192 positions
    # 3 s in the three kernels named mla_attention_* in 1.4 steps; GPT-2's
    # kernel is not this metric's
    assert got == pytest.approx(100 * flop_s * 1.4 / 3.0)
    assert _read("mla_attention_roofline", _ctx(ops=None)) is None
    assert _read("mla_attention_roofline", _ctx(ops=OPS[5:])) is None
    assert _read("mla_attention_roofline", dict(_ctx(), peaks=None)) is None
    nemotron = families.of("nemotron_h").arch(json.loads(
        (REPO / "benchmark" / "configs" / "nemotron-3-nano-30b-a3b.json")
        .read_text()))
    assert _read("mla_attention_roofline", _ctx(arch=nemotron)) is None


def test_the_cell_s_files_are_found_by_name():
    cell = harness.load_cell(CELL, REPO)
    assert cell.chips == 1 and cell.mix["kind"] == "train"
    assert cell.traffic_name == "lm_seq8192_b2"
    assert (cell.mix["batch"], cell.mix["seq"]) == (2, 8192)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s_per_chip", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"mla_ms_per_step", "mla_attention_roofline",
            "mla_copy_ms_per_step", "moe_ms_per_step",
            "train_mfu_pct", "unscoped_time_share_pct",
            "attention_kernel_fwd_ms_per_step",
            "attention_kernel_bwd_ms_per_step"} <= names
    assert not names & {"attention_roofline", "indexer_ms_per_step",
                        "sparse_attention_roofline", "ssm_ms_per_step",
                        "attention_copy_ms_per_step"}
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"], REPO))
        assert m["moves"] == "train_tokens_per_s_per_chip"
    assert set(cell.limits) == {"loss_gap", "grad_norm_gap", "grad_gap_p97",
                                "change_norm_gap"}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(w["why"]) <= 200
    # (not "the last entries": the next configuration's PR appends its own)
    assert w["config"] in [c["name"] for c in bench["configs"]]
    for name in ("mla_ms_per_step", "mla_attention_roofline",
                 "mla_copy_ms_per_step"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL]


# ---- the committed limits against the chip's readings

# my chip runs 2 and 6, PR 35 (benchmark/limits/<cell>.json "readings"; the
# program's highest of 36 seeds: loss_gap run 3, grad_norm_gap run 6)
PROGRAM_HIGHEST = {"loss_gap": 6.75e-5, "grad_norm_gap": 0.002939,
                   "grad_gap_p97": 0.001288, "change_norm_gap": 0.000304}
CONTROL_FP8 = {
    3000: {"loss_gap": 2.481e-4, "grad_norm_gap": 0.007194,
           "grad_gap_p97": 0.005079, "change_norm_gap": 0.00066},
    10919: {"loss_gap": 1.898e-4, "grad_norm_gap": 0.009975,
            "grad_gap_p97": 0.007848, "change_norm_gap": 0.000745},
    700035: {"loss_gap": 2.202e-4, "grad_norm_gap": 0.008854,
             "grad_gap_p97": 0.007569, "change_norm_gap": 0.000545},
    707954: {"loss_gap": 1.492e-4, "grad_norm_gap": 0.006417,
             "grad_gap_p97": 0.005777, "change_norm_gap": 0.000529}}
HALF_BATCH = {
    3000: {"loss_gap": 8.694e-4, "grad_norm_gap": 0.484058,
           "grad_gap_p97": 0.438407, "change_norm_gap": 0.171623},
    10919: {"loss_gap": 8.244e-4, "grad_norm_gap": 0.436497,
            "grad_gap_p97": 0.421015, "change_norm_gap": 0.172796},
    700035: {"loss_gap": 9.311e-4, "grad_norm_gap": 0.472583,
             "grad_gap_p97": 0.447136, "change_norm_gap": 0.170264},
    707954: {"loss_gap": 1.7971e-3, "grad_norm_gap": 0.452417,
             "grad_gap_p97": 0.449677, "change_norm_gap": 0.171899}}


@pytest.mark.parametrize("what,numbers,want", [
    ("program", PROGRAM_HIGHEST, True),
    *[(f"control_fp8 {seed}", numbers, False)
      for seed, numbers in CONTROL_FP8.items()],
    *[(f"half_batch {seed}", numbers, False)
      for seed, numbers in HALF_BATCH.items()],
    ("state unchanged", {"loss_gap": 0.0, "grad_norm_gap": 1.0,
                         "grad_gap_p97": 1.0, "change_norm_gap": 1.0}, False),
])
def test_the_committed_limits_hold_the_chip_s_readings(what, numbers, want):
    cell = harness.load_cell(CELL, REPO)
    correct, compared = harness.compare(numbers, cell.limits)
    assert correct is want, compared
    if what.startswith("control"):
        # the number that holds the control, with room
        assert numbers["grad_gap_p97"] > 1.5 * cell.limits["grad_gap_p97"]
        assert numbers["grad_norm_gap"] > 1.25 * cell.limits["grad_norm_gap"]
    if what.startswith("half_batch"):
        # the loss sees the fault with 3.6 times of room, the norms with 50
        assert all(numbers[k] > (3.5 if k == "loss_gap" else 10)
                   * cell.limits[k] for k in numbers)
    if what == "program":
        for name, value in numbers.items():
            assert value * 1.4 < cell.limits[name], name


@pytest.mark.parametrize("coefficient,sound", [(1e-3, True), (0.0, False)])
def test_the_committed_loss_limit_sees_a_dropped_balance_loss(
        moon_root, coefficient, sound):
    """What ``loss_gap`` is held for. At the cell's alpha 0.001 an expert
    layer's balance loss adds about 0.001 to the loss (``sum_i f_i P_i`` is
    1 for a uniform router): two layers in log(128) here, 4.1e-4 of the
    loss; four in the cell's 10.34 (the chip's readings), 3.9e-4. A program
    that drops the term reads over the committed limit, the sound one far
    under it."""
    cell, _, _ = _both_sides(moon_root)
    limit = harness.load_cell(CELL, REPO).limits["loss_gap"]
    arch = dict(cell.arch, aux_alpha=1e-3)
    mine = families.of(arch)
    canon = weights.make(weights.seed_key(3), arch)
    batch = dict(next(families.batches(arch, cell.mix, 3)))
    model = mine.build_model(arch, cell.mix)
    model = model.clone(config=dataclasses.replace(
        model.config, compute_dtype=jnp.float32,
        moe_seq_aux_loss_coeff=coefficient))
    got = float(mine.loss(model)(mine.to_program(canon, arch), batch))
    want = float(R.loss_part(
        canon, arch, {k: jnp.asarray(v) for k, v in batch.items()},
        R.totals(batch)))
    gap = abs(got - want) / abs(want)
    assert (gap < limit / 20) if sound else (gap > 1.5 * limit), gap
    _, real = real_arch()
    expert_layers = real["layers"] - real["dense_layers"]
    assert real["aux_alpha"] * expert_layers / 10.36 > 1.6 * limit


def test_the_held_share_drops_nothing(moon_root):
    """The gather holds every assignment of a step (``held_rows_factor`` is
    ``experts / held``): ``held_dropped_fraction`` reads 0."""
    cell = harness.load_cell(CELL, moon_root)
    arch, mix = cell.arch, cell.mix
    mine = families.of(arch)
    model = mine.build_model(arch, mix)
    batch = next(families.batches(arch, mix, 9))
    params = mine.to_program(weights.make(weights.seed_key(9), arch), arch)
    _, sown = model.apply({"params": params}, batch["tokens"],
                          mutable=["moe_losses"])
    layers = sown["moe_losses"]["transformer"]
    assert sorted(layers) == ["layer_1", "layer_2"]
    for layer in layers.values():
        routed = layer["mlp"]["routed"]
        assert float(routed["held_dropped_fraction"][0]) == 0
        assert 0 < float(routed["held_assignments"][0]) < 1
        assert float(routed["seq_aux_loss"][0]) > 0.9
