"""The ``nemotron_h`` family at a small size on the CPU (hidden 48, the
published pattern ``MEMEM*EME``, 4 Mamba-2 heads of 8 in 2 groups with 16
states and chunks of 8, 16 experts top-3 with 4 held, 4 query heads on 1
KV head of 16, sequence 32, seeded weights): the program against
``benchmark/reference/nemotron_h.py`` on loss, every tensor's gradient and
three Adam steps; the shares of each kind of layer adding up to the uncut
layer; the configuration file's arithmetic; the counts; the new readers."""

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
from benchmark.reference import nemotron_h as R
from benchmark.reference import transformer as T
from benchmark.xplane import Op, Trace

CELL = "nemotron3_nano_30b_a3b_train_8k"
CONFIG = REPO / "benchmark" / "configs" / "nemotron-3-nano-30b-a3b.json"
TINY = dict(hidden_size=48, head_dim=16, num_attention_heads=4,
            num_key_value_heads=1, mamba_num_heads=4, mamba_head_dim=8,
            n_groups=2, ssm_state_size=16, chunk_size=8,
            moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=40, n_routed_experts=4,
            num_experts_per_tok=3, vocab_size=128,
            max_position_embeddings=64, published={"n_routed_experts": 16},
            assumed={"held_rows_factor": 4.0})
# the program in bfloat16 against the float32 reference at this size, four
# seeds read on the CPU: loss_gap up to 3.2e-5, grad_norm_gap 0.007-0.024,
# change_norm_gap 0.010-0.013; half a batch reads 4.6e-3, 0.83 and 0.16
TINY_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 0.1,
               "change_norm_gap": 0.08}


@pytest.fixture(scope="module")
def nemo_root(tmp_path_factory):
    root = build_tiny_root(tmp_path_factory.mktemp("tiny_nemotron"))
    data = root / "benchmark"
    _rewrite(data / "configs" / CONFIG.name, **TINY)
    _rewrite(data / "traffic" / "lm_seq8192_b2.json", batch=4, seq=32,
             flash_attention=False)
    (data / "limits" / f"{CELL}.json").write_text(json.dumps(TINY_LIMITS))
    return root


def real_arch():
    config = json.loads(CONFIG.read_text())
    return config, families.of("nemotron_h").arch(config)


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


TENSORS = sorted(families.of("nemotron_h").shapes(real_arch()[1]))


def test_loss_matches_in_float32(nemo_root):
    _, got, want = _both_sides(nemo_root)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)


@pytest.mark.parametrize("tensor", TENSORS)
def test_gradient_matches_in_float32(nemo_root, tensor):
    """The chunked scan, the ragged experts and the fused projections
    against the recurrence, the loop over experts and the plain ones."""
    _, got, want = _both_sides(nemo_root)
    a, b = np.asarray(got[1][tensor]), np.asarray(want[1][tensor])
    assert np.abs(b).max() > 0, "a tensor with no gradient tests nothing"
    np.testing.assert_allclose(a, b, atol=1e-5 * np.abs(b).max())


def test_tree_mapping_round_trips_and_fits_the_model(nemo_root):
    cell = harness.load_cell(CELL, nemo_root)
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
    # the seed's tensors: gains and D are 1, the convolution's bias 0
    assert float(canon["l0.m_d_g"].min()) == 1
    assert float(jnp.abs(canon["l0.m_conv_b"]).max()) == 0
    assert 0.01 < float(canon["l0.m_a_log"].std()) < 0.04


def test_the_published_starts_are_added_on_both_sides(nemo_root):
    """``A_log``, ``dt_bias`` and the convolution start from the published
    distributions, the same draw whatever the seed, on both sides."""
    cell = harness.load_cell(CELL, nemo_root)
    arch = cell.arch
    mine = families.of(arch)
    offsets = R.init_offsets(arch)
    assert set(offsets) == {f"l{i}.{n}" for i in (0, 2, 4, 7) for n in (
        "m_a_log", "m_dt_bias", "m_conv_w", "m_conv_b")}
    for i in (0, 2, 4, 7):
        a = np.exp(offsets[f"l{i}.m_a_log"])
        assert (a >= 1).all() and (a <= 16).all()
        dt = np.log1p(np.exp(offsets[f"l{i}.m_dt_bias"]))
        assert (dt >= 0.000999).all() and (dt <= 0.1001).all()
        assert np.abs(offsets[f"l{i}.m_conv_w"]).max() <= 0.5
    assert not np.array_equal(offsets["l0.m_a_log"], offsets["l2.m_a_log"])
    canon = weights.make(weights.seed_key(2), arch)
    tree = mine.to_program(canon, arch)
    seen = mine.with_init_offsets(tree, arch)["transformer"]
    np.testing.assert_array_equal(
        seen["layer_2"]["mixer"]["A_log"],
        canon["l2.m_a_log"] + offsets["l2.m_a_log"])
    np.testing.assert_array_equal(
        R.layer_params(canon, arch, 2)["m_a_log"],
        canon["l2.m_a_log"] + offsets["l2.m_a_log"])
    # nothing else is touched, and the stepped tree is left as it was
    assert seen["layer_2"]["mixer"]["in_proj"] is \
        tree["transformer"]["layer_2"]["mixer"]["in_proj"]
    assert seen["layer_1"] is tree["transformer"]["layer_1"]
    np.testing.assert_array_equal(tree["transformer"]["layer_2"]["mixer"]
                                  ["A_log"], canon["l2.m_a_log"])


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_three_adam_steps_through_the_cell(nemo_root, seed):
    """amp O2 + FusedAdam on the benchmark's own step against the
    reference's three steps, through ``train_cell.run``."""
    result, compared = run_cell(nemo_root, CELL, seed=seed, seconds=0.2)
    assert result["correct"] is True, compared
    assert set(compared) == set(TINY_LIMITS) | {"compiles_in_window"}
    assert result["notes"]["tensors"] == 3 + 4 * 9 + 4 * 6 + 5


def test_half_a_batch_is_not_correct(nemo_root):
    def half_batch(stepper):
        real = stepper.next_batch
        stepper.next_batch = lambda: {k: v[:v.shape[0] // 2]
                                      for k, v in real().items()}

    result, compared = run_cell(nemo_root, CELL, seed=5, seconds=0.2,
                                fault=half_batch)
    assert result["correct"] is False, compared


def test_the_control_in_fp8_is_further_off_than_the_program(nemo_root):
    """The reference in fp8 put in the program's place reads a wider gap
    than the bfloat16 program: the recurrence's operands are rounded
    too."""
    from benchmark import compare, train_cell
    from benchmark.reference import lowp
    from benchmark.reference import train as ref_train

    cell = harness.load_cell(CELL, nemo_root)
    want = train_cell.reference_readings(cell, 7)
    control = train_cell.reference_readings(cell, 7, ref_train.Reference(
        cell.arch, cell.mix["optimizer"], cell.mix["hp"], quant=lowp.fp8))
    numbers, _ = compare.train_numbers(control, want)
    assert numbers["grad_norm_gap"] > 0.05


# ---- the shares add up: at a small size the 16 shares' parts of one M, one
# E and one * layer (two halves of the heads x eight pairs of experts; the
# shared expert and the router counted once) are the uncut layer's output

H_, HID, S_ = 8, 48, 24
FULL = {"hidden": HID, "eps": 1e-5, "m_heads": H_, "m_head_dim": 8,
        "m_groups": 4, "state": 16, "conv_kernel": 4, "heads": 4,
        "kv_heads": 2, "head_dim": 16, "experts": 16, "experts_held": 16,
        "expert_offset": 0, "top_k": 3, "routed_scale": 2.5, "ffn": 24,
        "shared_ffn": 40}


def _layer_weights(kind, seed=0):
    rng = np.random.default_rng(seed)
    a = FULL
    inner, bc = a["m_heads"] * a["m_head_dim"], a["m_groups"] * a["state"]
    q, kv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    shapes = {
        "M": {"m_in": (HID, 2 * inner + 2 * bc + H_),
              "m_conv_w": (4, inner + 2 * bc), "m_conv_b": (inner + 2 * bc,),
              "m_dt_bias": (H_,), "m_a_log": (H_,), "m_d_g": (H_,),
              "m_norm_g": (inner,), "m_out": (inner, HID)},
        "E": {"e_router": (HID, 16), "e_up": (16, HID, 24),
              "e_down": (16, 24, HID), "e_sup": (HID, 40),
              "e_sdown": (40, HID)},
        "*": {"a_wq": (HID, q), "a_wk": (HID, kv), "a_wv": (HID, kv),
              "a_wo": (q, HID)},
    }[kind]
    lp = {k: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
          for k, s in shapes.items()}
    if kind == "M":
        lp["m_dt_bias"] = lp["m_dt_bias"] - 2.0
    return lp


def _cut(x, sizes, pieces, half, axis=-1):
    """``x`` is ``[.. | a | b | ..]`` along ``axis`` with the given sizes;
    take the ``half``-th of ``pieces`` of each part."""
    out, at = [], 0
    for size in sizes:
        step = size // pieces
        out.append(jax.lax.slice_in_dim(x, at + half * step,
                                        at + (half + 1) * step, axis=axis))
        at += size
    return jnp.concatenate(out, axis)


def _mixer_half(lp, half):
    a = FULL
    inner, bc = a["m_heads"] * a["m_head_dim"], a["m_groups"] * a["state"]
    return {
        "m_in": _cut(lp["m_in"], [inner, inner, bc, bc, H_], 2, half),
        "m_conv_w": _cut(lp["m_conv_w"], [inner, bc, bc], 2, half),
        "m_conv_b": _cut(lp["m_conv_b"], [inner, bc, bc], 2, half),
        "m_dt_bias": _cut(lp["m_dt_bias"], [H_], 2, half),
        "m_a_log": _cut(lp["m_a_log"], [H_], 2, half),
        "m_d_g": _cut(lp["m_d_g"], [H_], 2, half),
        "m_norm_g": _cut(lp["m_norm_g"], [inner], 2, half),
        "m_out": _cut(lp["m_out"], [inner], 2, half, axis=0),
    }


def _attention_half(lp, half):
    return {"a_wq": _cut(lp["a_wq"], [64], 2, half),
            "a_wk": _cut(lp["a_wk"], [32], 2, half),
            "a_wv": _cut(lp["a_wv"], [32], 2, half),
            "a_wo": _cut(lp["a_wo"], [64], 2, half, axis=0)}


def _program_config(**kw):
    from apex_tpu.models import TransformerConfig

    return TransformerConfig(**dict(dict(
        hidden_size=HID, num_layers=1, num_attention_heads=4, head_dim=16,
        num_query_groups=2, ffn_hidden_size=24, vocab_size=64,
        compute_dtype=jnp.float32, use_flash_attention=False,
        normalization="rmsnorm", activation="relu2", attention_bias=False,
        position_embedding_type="none", layer_pattern="M",
        mamba_num_heads=H_, mamba_head_dim=8, mamba_n_groups=4,
        mamba_state_size=16, mamba_chunk_size=8, num_moe_experts=16,
        moe_top_k=3, moe_router_score="sigmoid_bias",
        moe_routed_scaling_factor=2.5, moe_dispatch_mode="ragged"), **kw))


def _reference_share(kind, lp, x, half=None, experts=None):
    """One share's part of a layer's output ``f(x)`` for ``x [s, hidden]``:
    ``half`` of the heads, or the ``experts`` (offset, count) held."""
    arch = dict(FULL)
    if kind == "M":
        if half is not None:
            lp = _mixer_half(lp, half)
            arch.update(m_heads=H_ // 2, m_groups=2)
        return R.mamba(x, lp, arch, T.identity)
    if kind == "*":
        if half is not None:
            lp = _attention_half(lp, half)
            arch.update(heads=2, kv_heads=1)
        return R.attention(x, lp, arch, T.identity)
    off, n = experts
    arch.update(expert_offset=off, experts_held=n)
    lp = dict(lp, e_up=lp["e_up"][off:off + n],
              e_down=lp["e_down"][off:off + n])
    return R.experts(x, lp, arch, T.identity)


def _program_share(kind, lp, x, half=None, experts=None):
    from apex_tpu.models.transformer_lm import ParallelAttention, _make_mlp
    from apex_tpu.transformer.ssm import Mamba2Mixer

    mine = families.of("nemotron_h")
    xs = x[:, None, :]                                   # [s, 1, hidden]
    if kind == "M":
        cfg = _program_config()
        if half is not None:
            lp = _mixer_half(lp, half)
            cfg = _program_config(mamba_num_heads=H_ // 2, mamba_n_groups=2)
        params = {path[-1]: lp[name]
                  for name, path in mine.KIND_LEAVES["M"].items()}
        return Mamba2Mixer(cfg).apply({"params": params}, xs)[:, 0]
    if kind == "*":
        arch, cfg = dict(FULL), _program_config()
        if half is not None:
            lp = _attention_half(lp, half)
            arch.update(heads=2, kv_heads=1)
            cfg = _program_config(num_attention_heads=2, num_query_groups=1)
        params = {"dense": {"weight": lp["a_wo"]},
                  "query_key_value": {"weight": mine._fuse_qkv(
                      lp["a_wq"], lp["a_wk"], lp["a_wv"], arch)}}
        return ParallelAttention(cfg).apply({"params": params}, xs)[:, 0]
    off, n = experts
    cfg = _program_config(
        moe_shared_expert_size=40, moe_shared_expert_gated=False,
        **({} if n == 16 else dict(moe_local_experts=n,
                                   moe_expert_offset=off,
                                   moe_capacity_factor=16.0 / n)))
    params = {"routed": {"router": {"gate_weight": lp["e_router"],
                                    "e_score_correction_bias":
                                        jnp.zeros((16,))},
                         "experts": {"w1": lp["e_up"][off:off + n],
                                     "w2": lp["e_down"][off:off + n]}},
              "shared_up": {"weight": lp["e_sup"]},
              "shared_down": {"weight": lp["e_sdown"]}}
    layer = _make_mlp(cfg, True).clone(warn_on_dropped_losses=False)
    return layer.apply({"params": params}, xs)[:, 0]


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("kind", ["M", "E", "*"])
def test_the_shares_add_up(kind, side):
    lp = _layer_weights(kind, seed=ord(kind))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(S_, HID)),
                    jnp.float32)
    share = {"program": _program_share, "reference": _reference_share}[side]
    whole = _reference_share(kind, lp, x, experts=(0, 16))
    assert float(jnp.abs(whole).max()) > 0.1
    if kind == "E":
        # eight pairs of experts; every share computes the shared expert
        # and the router alike: count them once
        shared = R.experts(x, dict(lp, e_up=lp["e_up"][:0],
                                   e_down=lp["e_down"][:0]),
                           dict(FULL, experts_held=0), T.identity)
        parts = [share(kind, lp, x, experts=(off, 2)) - shared
                 for off in range(0, 16, 2)]
        total = sum(parts) + shared
        assert float(jnp.abs(shared).max()) > 0.1
    else:
        total = share(kind, lp, x, half=0) + share(kind, lp, x, half=1)
    np.testing.assert_allclose(total, whole,
                               atol=2e-5 * float(jnp.abs(whole).max()))
    np.testing.assert_allclose(
        share(kind, lp, x, experts=(0, 16)), whole,
        atol=2e-5 * float(jnp.abs(whole).max()))


# ---- the configuration file and the counts

def test_published_widths_are_kept_and_the_cut_is_listed():
    config, arch = real_arch()
    pub = config["published"]
    for key, value in pub.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads"]
    for key in config["reduced"]:
        assert config[key] != pub[key], key
        assert not key.endswith(("_dim", "_rank", "_size")) \
            or key == "vocab_size"
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"]
                if c["name"] == "nemotron-3-nano-30b-a3b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert (arch["hidden"], arch["head_dim"], arch["m_head_dim"],
            arch["state"], arch["ffn"], arch["shared_ffn"], arch["top_k"],
            arch["conv_kernel"], arch["chunk"]) == (
                2688, 128, 64, 128, 1856, 3712, 6, 4, 128)
    assert (arch["experts"], arch["experts_held"]) == (128, 8)
    assert (arch["m_heads"], arch["m_groups"], arch["heads"],
            arch["kv_heads"]) == (32, 4, 16, 1)
    # the first 9 of the published 52, a whole period of every kind in the
    # published 23 : 23 : 6
    assert pub["hybrid_override_pattern"].startswith(arch["pattern"])
    assert [arch["pattern"].count(k) for k in "ME*"] == [4, 4, 1]
    assert [pub["hybrid_override_pattern"].count(k) for k in "ME*"] == \
        [23, 23, 6]
    assert arch["vocab"] == arch["vocab_real"] == 131072 // 8
    assert arch["vocab"] % 128 == 0 and len(config["deployment"]) > 40
    # room for every assignment: the held share drops nothing
    assert arch["held_rows_factor"] == arch["experts"] / arch["experts_held"]
    for key in ("no_rotary", "e_score_correction_bias", "norm_topk_prob",
                "gate_before_norm", "held_rows_why", "init", "precision",
                "mixers_share_why"):
        assert len(config["assumed"][key]) > 40, key


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
    mixer = 2688 * (2 * 2048 + 2 * 512 + 32) + 2048 * 2688 \
        + 4 * 3072 + 3072 + 3 * 32 + 2048
    attention = 2688 * (16 + 2) * 128 + 16 * 128 * 2688
    expert = 2688 * 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856
    total = 4 * mixer + attention + 4 * expert + 9 * 2688 \
        + 2 * 16384 * 2688 + 2688
    assert weights.n_params(arch) == total == 577_780_352
    assert round(total * 14 / 1e9, 2) == 8.09      # amp O2 + Adam
    assert round(total * 20 / 1e9, 2) == 11.56     # the reference's five


def test_flops_per_token_by_hand():
    from benchmark import flops

    _, arch = real_arch()
    fam = families.of(arch)
    mixer = 2688 * 5152 + 2048 * 2688
    attention = 2688 * 2304 + 2048 * 2688
    # an expected 6 x 8/128 of an expert's two matrices a token
    expert = 2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856
    want = 4 * mixer + attention + 4 * expert + 2688 * 16384
    assert flops.matmul_params(arch) == int(want)
    scan = 5 * 32 * 64 * 128
    assert fam.scan_flops_per_token(arch) == scan
    fwd = 2 * int(want) + 4 * 8192 * 2048 + 4 * (scan + 2 * 4 * 3072)
    assert flops.fwd_flops_per_token(arch, 8192) == pytest.approx(fwd)
    tokens = 2 * 8192 * 4
    assert fam.ssm_scan_train_flops_per_step(arch, 2, 8192) == \
        3 * scan * tokens
    ins = 2 * 3072 + 4 * 32
    assert fam.ssm_scan_train_bytes_per_step(arch, 2, 8192) == \
        tokens * (2 * (ins + 4 * 2048) + ins)


# ---- the readers this family brings, on a synthetic trace

OPS = [
    Op(0, "fusion.1", 0.0, 1.0, "fusion", "kLoop"),
    Op(0, "while.2", 1.0, 3.0, "while"),
    Op(0, "fusion.3", 1.5, 2.5, "fusion", "kOutput"),     # the loop's body
    Op(0, "fusion.4", 3.0, 4.0, "fusion", "kOutput"),
    Op(0, "fusion.5", 4.0, 7.0, "fusion", "kOutput"),
]
BLOCKS = {"fusion.1": ("ssm/conv", "forward"),
          "while.2": ("ssm/scan", "recompute"),
          "fusion.3": ("ssm/scan", "recompute"),
          "fusion.4": ("ssm/scan", "backward"),
          "fusion.5": ("moe", "forward")}


def _ctx(ops=OPS, blocks=BLOCKS, arch=None):
    return {"trace": Trace(ops, []) if ops is not None else None,
            "window": {"steps": 2, "elapsed_s": 10.0},
            "scope_blocks": blocks, "arch": arch or real_arch()[1],
            "mix": {"batch": 2, "seq": 8192},
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _read(name, context):
    return harness.load_reader(name, REPO)(context)


def test_ssm_ms_per_step_is_the_union_of_the_block_s_parts():
    # the trace spans 7 s of the two steps' 10 s: 1.4 steps traced; the
    # mixer's operations cover 4 s of it, the loop's body counted once
    assert _read("ssm_ms_per_step", _ctx()) == pytest.approx(4e3 / 1.4)
    assert _read("ssm_ms_per_step", _ctx(ops=None)) is None
    assert _read("ssm_ms_per_step", _ctx(blocks=None)) is None   # the parent
    assert _read("ssm_ms_per_step",
                 _ctx(blocks={"fusion.1": ("mlp", "forward")})) is None


def test_ssm_scan_roofline_by_hand():
    arch = real_arch()[1]
    fam = families.of(arch)
    got = _read("ssm_scan_roofline", _ctx())
    flop_s = fam.ssm_scan_train_flops_per_step(arch, 2, 8192) / 197e12
    byte_s = fam.ssm_scan_train_bytes_per_step(arch, 2, 8192) / 819e9
    assert byte_s > flop_s          # the bytes bound it on a v5e
    # 3 s of scan operations (the loop's body once) in 1.4 steps
    assert got == pytest.approx(100 * byte_s * 1.4 / 3.0)
    assert 0 < got < 100
    assert _read("ssm_scan_roofline", _ctx(ops=None)) is None
    assert _read("ssm_scan_roofline", _ctx(blocks=None)) is None
    assert _read("ssm_scan_roofline", _ctx(
        blocks={"fusion.1": ("ssm/conv", "forward")})) is None
    assert _read("ssm_scan_roofline", dict(_ctx(), peaks=None)) is None
    keye = families.of("keye_vl2").arch(json.loads(
        (REPO / "benchmark" / "configs" / "keye-vl-2.0-30b-a3b.json")
        .read_text()))
    assert _read("ssm_scan_roofline", _ctx(arch=keye)) is None


def test_the_cell_s_files_are_found_by_name():
    cell = harness.load_cell(CELL, REPO)
    assert cell.chips == 1 and cell.mix["kind"] == "train"
    assert cell.traffic_name == "lm_seq8192_b2"
    assert (cell.mix["batch"], cell.mix["seq"]) == (2, 8192)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s_per_chip", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"ssm_ms_per_step", "ssm_scan_roofline", "moe_ms_per_step",
            "train_mfu_pct", "unscoped_time_share_pct"} <= names
    assert not names & {"attention_roofline", "indexer_ms_per_step",
                        "sparse_attention_roofline"}
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"], REPO))
        assert m["moves"] == "train_tokens_per_s_per_chip"
    assert set(cell.limits) == {"grad_norm_gap", "grad_gap_p97",
                                "change_norm_gap"}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(w["why"]) <= 200
    assert bench["workloads"][-1] == w and bench["configs"][-1]["name"] == \
        w["config"]
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "ssm_ms_per_step", "ssm_scan_roofline"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


# ---- the committed limits against the chip's readings, and the control's
# hand-run

# my chip runs 2, 3 and 6, PR 33 (benchmark/limits/<cell>.json "readings")
PROGRAM_HIGHEST = {"grad_norm_gap": 0.01379, "grad_gap_p97": 0.00563,
                   "change_norm_gap": 0.00596}
CONTROL_FP8 = [{"grad_norm_gap": 0.0323, "grad_gap_p97": 0.0171,
                "change_norm_gap": 0.00441},
               {"grad_norm_gap": 0.02167, "grad_gap_p97": 0.01672,
                "change_norm_gap": 0.0044}]
HALF_BATCH = [{"grad_norm_gap": 0.47321, "grad_gap_p97": 0.42411,
               "change_norm_gap": 0.15452},
              {"grad_norm_gap": 0.43476, "grad_gap_p97": 0.42438,
               "change_norm_gap": 0.15355}]


@pytest.mark.parametrize("what,numbers,want", [
    ("program", PROGRAM_HIGHEST, True),
    ("control_fp8 3000", CONTROL_FP8[0], False),
    ("control_fp8 10919", CONTROL_FP8[1], False),
    ("half_batch 3000", HALF_BATCH[0], False),
    ("half_batch 10919", HALF_BATCH[1], False),
    ("state unchanged", {"grad_norm_gap": 1.0, "grad_gap_p97": 1.0,
                         "change_norm_gap": 1.0}, False),
])
def test_the_committed_limits_hold_the_chip_s_readings(what, numbers, want):
    cell = harness.load_cell(CELL, REPO)
    correct, compared = harness.compare(numbers, cell.limits)
    assert correct is want, compared
    if what.startswith("control"):
        # the number that holds the control, with room
        assert numbers["grad_gap_p97"] > 1.5 * cell.limits["grad_gap_p97"]
    if what == "program":
        for name, value in numbers.items():
            assert value * 1.4 < cell.limits[name], name


def test_the_control_s_hand_run_reads_both_upper_readings(nemo_root, tmp_path,
                                                          monkeypatch):
    """``calibrate_control.py`` at the tiny size: the half-batch fault over
    the whole batch, the fp8 control over the first row of each batch on
    both of its sides, one ``Reference`` at a time."""
    from unittest import mock

    from bench_tiny import any_device
    from benchmark import calibrate_control

    out = tmp_path / "control.jsonl"
    monkeypatch.setattr("sys.argv", [
        "calibrate_control.py", "--workload", CELL, "--seeds", "5,7",
        "--control-rows", "1", "--out", str(out)])
    real = harness.load_cell
    with mock.patch.object(harness, "require_chips", any_device), \
            mock.patch.object(harness, "load_cell",
                              lambda name: real(name, nemo_root)):
        calibrate_control.main()
    records = [json.loads(line) for line in out.read_text().splitlines()]
    assert [(r["what"], r["seed"]) for r in records] == [
        ("fault_half_batch", 5), ("fault_half_batch", 7),
        ("control_fp8", 5), ("control_fp8", 7)]
    for r in records:
        assert r["device"]["platform"] == "cpu"
        assert len(r["vectors"]["names"]) == 68
        # the tiny program reads up to 0.024 (TINY_LIMITS' comment)
        assert r["numbers"]["grad_norm_gap"] > 0.04
    assert [r.get("rows") for r in records] == [None, None, 1, 1]
    assert all(r["correct"] is False and r["numbers"]["grad_norm_gap"] > 0.2
               for r in records[:2])
