"""The ``keye_vl2`` family at a small size on the CPU (hidden 64, 4 query
/ 2 KV heads of 16, 8 experts top-2 with 4 held, 16 indexer heads of 8
choosing 8 keys at sequence 32, 2 layers, seeded weights): the program
against ``benchmark/reference/keye_vl2.py`` on loss, every tensor's
gradient and three Adam steps; the selection; unequal rotary components;
the shares of the expert layer adding up; where the indexer's loss sends
gradient; the counts; the new readers."""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import REPO, _rewrite, build_tiny_root, run_cell
from benchmark import families, harness, weights
from benchmark.reference import keye_vl2 as R
from benchmark.reference import transformer as T
from benchmark.xplane import Op, Trace

CELL = "keye_vl2_30b_a3b_train_8k"
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, moe_intermediate_size=32, num_experts=8,
            num_experts_per_tok=2, num_local_experts=4, num_hidden_layers=2,
            vocab_size=96, max_position_embeddings=64,
            rope_scaling={"mrope_section": [2, 3, 3]},
            sa_config={"indexer_head_dim": 8, "indexer_num_heads": 16,
                       "topk": 8},
            assumed={"padded_vocab_size": 128, "held_rows_factor": 2.0})
# the program in bfloat16 against the float32 reference at this size, three
# seeds read on the CPU: loss_gap up to 2.1e-4, grad_norm_gap up to 0.035,
# change_norm_gap up to 0.12 (a selection of 8 keys flips visibly)
TINY_LIMITS = {"loss_gap": 2e-3, "grad_norm_gap": 0.15,
               "change_norm_gap": 0.6}


@pytest.fixture(scope="module")
def keye_root(tmp_path_factory):
    root = build_tiny_root(tmp_path_factory.mktemp("tiny_keye"))
    data = root / "benchmark"
    _rewrite(data / "configs" / "keye-vl-2.0-30b-a3b.json", **TINY)
    # the reference's load-balancing loss is over its block: the whole batch
    _rewrite(data / "traffic" / "lm_seq8192_b2.json", batch=4, seq=32,
             reference_block_rows=4)
    (data / "limits" / f"{CELL}.json").write_text(json.dumps(TINY_LIMITS))
    return root


@functools.lru_cache(maxsize=None)
def _both_sides(root, positions="equal"):
    """Loss and gradients of the program (compute type float32) and of
    the reference on one seeded batch: -> (cell, got, want), each side a
    (loss, canonical gradient dict)."""
    cell = harness.load_cell(CELL, root)
    arch, mix = cell.arch, cell.mix
    mine = families.of(arch)
    canon = weights.make(weights.seed_key(3), arch)
    batch = dict(next(families.batches(arch, mix, 3)))
    if positions == "unequal":
        # a picture's worth: height and width run apart from the text's
        rng = np.random.default_rng(0)
        batch["positions"] = np.sort(rng.integers(
            0, 64, batch["positions"].shape, dtype=np.int32), axis=-1)
    model = mine.build_model(arch, mix)
    model = model.clone(config=dataclasses.replace(
        model.config, compute_dtype=jnp.float32))
    loss, grads = jax.value_and_grad(mine.loss(model))(
        mine.to_program(canon, arch), batch)
    block = {k: jnp.asarray(v) for k, v in batch.items()}
    want = jax.value_and_grad(lambda p: R.loss_part(
        p, arch, block, R.totals(batch)))(canon)
    return cell, (loss, mine.from_program(grads, arch)), want


TENSORS = sorted(families.of("keye_vl2").shapes(families.of(
    "keye_vl2").arch(dict(json.loads(
        (REPO / "benchmark" / "configs" / "keye-vl-2.0-30b-a3b.json")
        .read_text())))))


@pytest.mark.parametrize("positions", ["equal", "unequal"])
def test_loss_matches_in_float32(keye_root, positions):
    _, got, want = _both_sides(keye_root, positions)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)


@pytest.mark.parametrize("positions", ["equal", "unequal"])
@pytest.mark.parametrize("tensor", TENSORS)
def test_gradient_matches_in_float32(keye_root, tensor, positions):
    _, got, want = _both_sides(keye_root, positions)
    a, b = np.asarray(got[1][tensor]), np.asarray(want[1][tensor])
    assert np.abs(b).max() > 0, "a tensor with no gradient tests nothing"
    np.testing.assert_allclose(a, b, atol=2e-5 * np.abs(b).max())


def test_unequal_rotary_components_change_the_loss(keye_root):
    """The three components really are read: the loss under unequal
    positions is another loss."""
    equal = _both_sides(keye_root, "equal")[2][0]
    unequal = _both_sides(keye_root, "unequal")[2][0]
    assert abs(float(equal) - float(unequal)) > 1e-5


def test_tree_mapping_round_trips_and_fits_the_model(keye_root):
    cell = harness.load_cell(CELL, keye_root)
    arch = cell.arch
    mine = families.of(arch)
    canon = weights.make(weights.seed_key(1), arch)
    tree = mine.to_program(canon, arch)
    back = mine.from_program(tree, arch)
    assert set(back) == set(canon)
    for k in canon:
        assert np.array_equal(np.asarray(back[k]), np.asarray(canon[k])), k
    batch = next(families.batches(arch, cell.mix, 0))
    shapes = jax.eval_shape(lambda: mine.build_model(arch, cell.mix).init(
        jax.random.PRNGKey(0), batch["tokens"],
        position_ids=batch["positions"].transpose(1, 0, 2)))
    want = jax.tree_util.tree_map(lambda x: x.shape, shapes["params"])
    assert jax.tree_util.tree_map(lambda x: x.shape, tree) == want


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_three_adam_steps_through_the_cell(keye_root, seed):
    """amp O2 + FusedAdam on the benchmark's own step against the
    reference's three steps, through ``train_cell.run``."""
    result, compared = run_cell(keye_root, CELL, seed=seed, seconds=0.2)
    assert result["correct"] is True, compared
    assert set(compared) == set(TINY_LIMITS) | {"compiles_in_window"}
    assert result["notes"]["tensors"] == 3 + 17 * 2


def test_program_selection_is_the_reference_s_exact_top_k(keye_root):
    from apex_tpu.models.transformer_lm import topk_selection

    cell, _, _ = _both_sides(keye_root)
    arch = cell.arch
    canon = weights.make(weights.seed_key(3), arch)
    batch = next(families.batches(arch, cell.mix, 3))
    lp = {k[len("layers."):]: v[0] for k, v in canon.items()
          if k.startswith("layers.")}
    a = R.rms_norm(canon["wte"][jnp.asarray(batch["tokens"])], lp["ln1_g"],
                   arch["eps"])
    scores = jnp.stack([R.index_scores(
        a[i], lp, arch, jnp.asarray(batch["positions"][i]), T.identity)
        for i in range(a.shape[0])])
    want = jnp.stack([R.exact_selection(s, arch["indexer_topk"])
                      for s in scores])
    got = topk_selection(scores, arch["indexer_topk"]).astype(bool)
    assert bool((got == want).all())
    assert int(want.sum()) == a.shape[0] * sum(
        min(t + 1, 8) for t in range(32))


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up(side):
    """The expert layer run once for each of the two shares (4 of the 8
    experts each), summed, is the uncut layer's routed output."""
    rng = np.random.default_rng(0)
    h, f, E, k, tokens = 64, 32, 8, 2, 48
    x = jnp.asarray(rng.normal(size=(tokens, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, E)) * 0.3, jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(E, h, f)) * 0.1, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(E, f, h)) * 0.1, jnp.float32)
    arch = {"experts": E, "top_k": k}

    def reference(off, n):
        lp = {"router": router, "egate": gate[off:off + n],
              "eup": up[off:off + n], "edown": down[off:off + n]}
        return R.experts(x, lp, dict(arch, expert_offset=off,
                                     experts_held=n), T.identity)[0]

    def program(off, n):
        from apex_tpu.transformer.moe import SwitchMLP

        layer = SwitchMLP(
            hidden_size=h, ffn_hidden_size=f, num_experts=E, top_k=k,
            activation="swiglu", compute_dtype=jnp.float32,
            dispatch_mode="ragged", warn_on_dropped_losses=False,
            capacity_factor=4.0,
            **({} if n == E else dict(local_experts=n, expert_offset=off)))
        params = {"router": {"gate_weight": router},
                  "experts": {"w1": jnp.concatenate(
                      [gate[off:off + n], up[off:off + n]], -1),
                      "w2": down[off:off + n]}}
        return layer.apply({"params": params}, x[:, None, :])[:, 0, :]

    run = {"program": program, "reference": reference}[side]
    whole = run(0, E)
    shares = run(0, 4) + run(4, 4)
    assert float(jnp.abs(whole).max()) > 1e-3
    np.testing.assert_allclose(shares, whole, atol=1e-6)
    if side == "program":
        np.testing.assert_allclose(whole, reference(0, E), atol=1e-6)


def _loss_pieces(root):
    """Gradients of the indexers' loss alone, and of the rest."""
    from apex_tpu.models.gpt import gpt_loss_fn
    from apex_tpu.models.transformer_lm import indexer_loss_from_variables
    from apex_tpu.transformer.moe import moe_loss_from_variables

    cell = harness.load_cell(CELL, root)
    arch, mix = cell.arch, cell.mix
    mine = families.of(arch)
    model = mine.build_model(arch, mix)
    batch = next(families.batches(arch, mix, 5))
    params = mine.to_program(weights.make(weights.seed_key(5), arch), arch)

    def pieces(p):
        logits, sown = model.apply(
            {"params": p}, batch["tokens"],
            position_ids=batch["positions"].transpose(1, 0, 2),
            mutable=["moe_losses"])
        return (indexer_loss_from_variables(sown),
                gpt_loss_fn(logits, batch["labels"])
                + moe_loss_from_variables(sown, 1.0))

    only_indexer = jax.grad(lambda p: pieces(p)[0])(params)
    the_rest = jax.grad(lambda p: pieces(p)[1])(params)
    return (mine.from_program(only_indexer, arch),
            mine.from_program(the_rest, arch))


_CACHE = {}
INDEXER_TENSORS = {"layers.iwq", "layers.iwk", "layers.iww",
                   "layers.ikn_g", "layers.ikn_b"}


@pytest.mark.parametrize("tensor", TENSORS)
def test_the_indexer_learns_from_its_loss_alone(keye_root, tensor):
    """The indexer's parameters get gradient from ``L_I`` and from nothing
    else; no other parameter gets any from ``L_I``."""
    if "pieces" not in _CACHE:
        _CACHE["pieces"] = _loss_pieces(keye_root)
    from_indexer_loss, from_the_rest = _CACHE["pieces"]
    li = float(jnp.abs(from_indexer_loss[tensor]).max())
    rest = float(jnp.abs(from_the_rest[tensor]).max())
    if tensor in INDEXER_TENSORS:
        assert li > 0 and rest == 0
    else:
        assert li == 0 and rest > 0


def real_arch():
    config = json.loads((REPO / "benchmark" / "configs"
                         / "keye-vl-2.0-30b-a3b.json").read_text())
    return config, families.of("keye_vl2").arch(config)


def test_published_widths_are_kept_and_the_cut_is_listed():
    config, arch = real_arch()
    pub = config["published"]
    for key, value in pub.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_local_experts",
                                 "vocab_size"]
    assert (arch["hidden"], arch["heads"], arch["kv_heads"],
            arch["head_dim"], arch["ffn"]) == (2048, 32, 4, 128, 768)
    assert (arch["experts"], arch["top_k"], arch["experts_held"]) == \
        (128, 8, 16)
    assert (arch["indexer_heads"], arch["indexer_dim"],
            arch["indexer_topk"]) == (16, 64, 2048)
    assert 4 <= arch["layers"] <= 6 and arch["vocab_real"] == 151936 // 8
    assert arch["vocab"] % 128 == 0 and len(config["deployment"]) > 40
    # room for every assignment: the held share drops nothing
    assert arch["held_rows_factor"] == arch["experts"] / arch["experts_held"]


def test_flops_per_token_by_hand():
    from benchmark import flops

    _, arch = real_arch()
    fam = families.of(arch)
    assert fam.mean_selected_keys(arch, 8192) == pytest.approx(1792.125)
    # a layer outside its experts: q and o 2 x 2048 x 4096, k and v
    # 2 x 2048 x 512, indexer 2048 x (1024 + 64 + 16), router 2048 x 128
    layer = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 1104 + 2048 * 128
    assert layer == 21_397_504
    held = 8 * 16 / 128 * 3 * 2048 * 768          # one expert's worth
    want = arch["layers"] * (layer + held) + 2048 * arch["vocab"]
    assert flops.matmul_params(arch) == want
    fwd = 2 * want + arch["layers"] * (4 * 1792.125 * 4096
                                       + 2 * 4096.5 * 1024)
    assert flops.fwd_flops_per_token(arch, 8192) == pytest.approx(fwd)
    pairs = 1792.125 * 8192 * 2 * arch["layers"]
    assert fam.sparse_attention_train_flops_per_step(arch, 2, 8192) == \
        pytest.approx(14 * pairs * 4096)


# ---- the readers this family brings, on a synthetic trace

OPS = [
    Op(0, "fusion.1", 0.0, 1.0, "fusion", "kLoop"),
    Op(0, "fusion.2", 1.0, 3.0, "fusion", "kOutput"),
    Op(0, "sparse_attention_flash_fwd.3", 3.0, 4.0, "custom-call"),
    Op(0, "sparse_attention_head_probs.5.remat", 4.0, 4.5, "custom-call"),
    Op(0, "sparse_attention_flash_dkv.2", 4.5, 6.0, "custom-call"),
    Op(0, "self_attention_flash_fwd.4", 6.0, 7.0, "custom-call"),
]
BLOCKS = {"fusion.1": ("indexer", "recompute"), "fusion.2": ("moe", "forward")}


def _ctx(ops=OPS, blocks=BLOCKS, arch=None):
    return {"trace": Trace(ops, []) if ops is not None else None,
            "window": {"steps": 2, "elapsed_s": 10.0},
            "scope_blocks": blocks, "arch": arch or real_arch()[1],
            "mix": {"batch": 2, "seq": 8192},
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _read(name, context):
    return harness.load_reader(name, REPO)(context)


# the trace spans 7 s of the two steps' 10 s: 1.4 steps traced
@pytest.mark.parametrize("name,want", [("indexer_ms_per_step", 1e3 / 1.4),
                                       ("moe_ms_per_step", 2e3 / 1.4)])
def test_block_readers_give_the_hand_worked_number(name, want):
    assert _read(name, _ctx()) == pytest.approx(want)
    assert _read(name, _ctx(ops=None)) is None
    assert _read(name, _ctx(blocks=None)) is None       # the parent
    assert _read(name, _ctx(blocks={"fusion.1": ("mlp", "forward")})) is None


def test_sparse_attention_roofline_by_hand():
    arch = real_arch()[1]
    fam = families.of(arch)
    got = _read("sparse_attention_roofline", _ctx())
    # the trace spans 7 s of the 10 s window: 1.4 steps, 3 s of kernels
    need = fam.sparse_attention_train_flops_per_step(arch, 2, 8192) / 197e12
    assert need > fam.sparse_attention_train_bytes_per_step(
        arch, 2, 8192) / 819e9
    assert got == pytest.approx(100 * need * 1.4 / 3.0)
    assert _read("sparse_attention_roofline", _ctx(ops=None)) is None
    assert _read("sparse_attention_roofline", _ctx(ops=OPS[:2])) is None
    gpt2 = families.of("gpt2").arch(json.loads(
        (REPO / "benchmark" / "configs" / "gpt2-345m.json").read_text()))
    assert _read("sparse_attention_roofline", _ctx(arch=gpt2)) is None


@pytest.mark.parametrize("scope,want", [
    ("jit(s)/jvp(GPTModel)/transformer/layer_0/self_attention/indexer/"
     "indexer/scores/dot_general", ("indexer", "forward")),
    ("jit(s)/transpose(jvp(GPTModel))/transformer/checkpoint/"
     "rematted_computation/layer_1/self_attention/indexer/indexer/select/"
     "while", ("indexer", "recompute")),
    ("jit(s)/transpose(jvp(GPTModel))/transformer/layer_0/mlp/moe/experts/"
     "experts/ragged_dot", ("moe", "backward")),
    ("jit(s)/jvp(GPTModel)/transformer/layer_0/mlp/dense_h_to_4h/dot",
     ("mlp", "forward")),
    # XLA's grouped-matmul kernel for lax.ragged_dot keeps no scope
    ("ragged-dot-none", ("moe", "update")),
    ("ragged-dot-metadata", ("moe", "update")),
    ("jit(s)/jvp(GPTModel)/transformer/layer_0/self_attention/"
     "sparse_attention_flash_fwd/pallas_call",
     ("attention/kernel", "forward")),
])
def test_scopes_fold_into_the_new_blocks(scope, want):
    from apex_tpu.telemetry.scopes import classify

    assert classify(scope) == want


# ``test_cells.py`` holds three assertions that describe PR 24's benchmark
# alone (every cell on one chip at hidden 1024 x 24 layers, every
# configuration with nothing reduced), so its contract test and its two
# cases for the new cells fail since PR 28 until a ``benchmark`` PR rewrites
# them (PERF.md section 7). These hold every other assertion of those
# tests, and what the contract says of a cut configuration besides.
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"


@pytest.mark.parametrize("workload,chips", [(CELL, 1),
                                            ("gpt2_345m_train_dp4", 4)])
def test_the_new_cells_files_are_found_by_name(workload, chips):
    cell = harness.load_cell(workload, REPO)
    assert cell.chips == chips and cell.mix["kind"] == "train"
    assert (cell.mix.get("mesh") == {"data": 4}) == (chips == 4)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s_per_chip", "setup_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"], REPO))
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
    assert cell.limits, "a committed cell has its limits"
    assert len(cell.mix["why"]) > 40


def test_benchmark_json_keeps_to_the_contract_with_a_cut_configuration():
    import re

    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = [w["name"] for w in BENCH["workloads"]]
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and len(cells) == len(set(cells))
    for n in names + cells + [c["name"] for c in BENCH["configs"]]:
        assert re.match(NAME, n), n
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m["workloads"]) <= set(cells)
        layers.add(m["layer"])
    assert {"model", "kernels"} <= layers
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(cells) // 4)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).exists() and len(c["reduced"]) <= 16
        assert all(re.match(NAME, key) and not key.endswith(("_dim", "_rank"))
                   for key in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert (REPO / "BENCHMARK.json").stat().st_size < 64 * 1024
