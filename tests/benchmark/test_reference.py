"""The plain reference against the program at a tiny size, for GPT-2,
BERT and prefill + decode; the control (the reference in fp8 in the
program's place) against the same comparison; the optimizers alone."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import SERVE_CELL, run_cell
from benchmark import (compare, families, harness, optimizers, program,
                       weights)
from benchmark.reference import family, lowp
from benchmark.reference import train as ref_train


def first_batch(cell, seed=3):
    return next(families.batches(cell.arch, cell.mix, seed))


@pytest.mark.parametrize("workload", ["gpt2_345m_train", "bert_large_train"])
def test_forward_loss_matches_in_float32(tiny_root, workload):
    """With the program's compute type set to float32 the two agree to
    rounding: the reference and the program are the same model."""
    import dataclasses

    cell = harness.load_cell(workload, tiny_root)
    arch, mix = cell.arch, cell.mix
    canon = weights.make(weights.seed_key(3), arch)
    mine = families.of(arch)
    model = mine.build_model(arch, mix)
    model = model.clone(config=dataclasses.replace(
        model.config, compute_dtype=jnp.float32))
    batch = first_batch(cell)
    got = mine.loss(model)(mine.to_program(canon, arch), batch)
    fam = family(arch["family"])
    want = fam.loss_part(canon, arch, {k: jnp.asarray(v)
                                       for k, v in batch.items()},
                         fam.totals(batch))
    assert float(got) == pytest.approx(float(want), rel=2e-6)


def test_tree_mapping_round_trips(tiny_root):
    cell = harness.load_cell("bert_large_train", tiny_root)
    canon = weights.make(weights.seed_key(1), cell.arch)
    bert = families.of("bert")
    back = bert.from_program(bert.to_program(canon, cell.arch), cell.arch)
    assert set(back) == set(canon)
    for k in canon:
        assert np.array_equal(np.asarray(back[k]), np.asarray(canon[k]))
    shapes = jax.eval_shape(lambda: bert.build_model(
        cell.arch, cell.mix).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
            jnp.ones((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.int32)))
    want = jax.tree_util.tree_map(lambda x: x.shape, shapes["params"])
    got = jax.tree_util.tree_map(lambda x: x.shape,
                                 bert.to_program(canon, cell.arch))
    assert got == want


def test_weights_are_seeded_and_bf16_representable(tiny_root):
    arch = harness.load_cell("gpt2_345m_train", tiny_root).arch
    a = weights.make(weights.seed_key(2 ** 31 + 5), arch)
    b = weights.make(weights.seed_key(2 ** 31 + 5), arch)
    c = weights.make(weights.seed_key(5), arch)
    assert np.array_equal(np.asarray(a["wte"]), np.asarray(b["wte"]))
    assert not np.array_equal(np.asarray(a["wte"]), np.asarray(c["wte"]))
    for k, x in a.items():
        assert x.dtype == jnp.float32
        assert np.array_equal(np.asarray(x.astype(jnp.bfloat16)
                                         .astype(jnp.float32)),
                              np.asarray(x)), k


@pytest.mark.parametrize("name,hp", [
    ("adam", {"lr": 1e-3, "betas": [0.9, 0.999], "eps": 1e-8,
              "weight_decay": 0.0}),
    ("lamb", {"lr": 1e-2, "betas": [0.9, 0.999], "eps": 1e-6,
              "weight_decay": 0.01, "max_grad_norm": 1.0}),
])
def test_reference_optimizers_match_the_fused_ones(name, hp):
    """Three steps of the reference's Adam / LAMB against the program's
    FusedAdam / FusedLAMB on the same float32 gradients."""
    rng = np.random.default_rng(0)
    params = {"layers.fc_w": jnp.asarray(rng.normal(size=(2, 8, 16)),
                                         jnp.float32),
              "lnf_b": jnp.zeros((8,), jnp.float32)}
    fused = program.make_optimizer({"optimizer": name, "hp": hp})
    flat = {"a0": params["layers.fc_w"][0], "a1": params["layers.fc_w"][1],
            "b": params["lnf_b"]}
    plain = optimizers.of(name)
    fstate, rstate = fused.init(flat), plain.init(params)
    for step in range(3):
        grads = {k: jnp.asarray(rng.normal(size=v.shape) * 3.0, jnp.float32)
                 for k, v in params.items()}
        params, rstate = plain.step(params, grads, rstate, hp)
        fgrads = {"a0": grads["layers.fc_w"][0],
                  "a1": grads["layers.fc_w"][1], "b": grads["lnf_b"]}
        flat, fstate = fused.step(fgrads, fstate, flat)
    np.testing.assert_allclose(np.asarray(flat["a1"]),
                               np.asarray(params["layers.fc_w"][1]),
                               rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(flat["b"]),
                               np.asarray(params["lnf_b"]),
                               rtol=2e-5, atol=1e-7)


# Limits for the control at this size, where the arithmetic alone is read:
# the reference with its products' operands rounded to bfloat16 (what the
# configurations state) against the same rounded to fp8 (the step below).
# Builder's readings on the CPU, seeds 21-23, both configurations: bfloat16
# reads grad_norm_gap up to 5.9e-4, change_norm_gap up to 2.0e-3 and
# loss_gap up to 5.1e-5; fp8 reads grad_norm_gap 0.0101 to 0.0242,
# change_norm_gap 0.024 to 0.041 and loss_gap 5.9e-5 to 1.0e-3. (The
# program itself is noisier than either at this size, since it also keeps
# activations in bfloat16; its limits at the cells' own sizes come from
# chip runs, see PERF.md.)
CONTROL_LIMITS = {"loss_gap": 2e-4, "grad_norm_gap": 0.003,
                  "change_norm_gap": 0.007}


@pytest.mark.parametrize("workload", ["gpt2_345m_train", "bert_large_train"])
def test_control_in_fp8_fails_and_stated_precision_passes(tiny_root,
                                                          workload):
    """The reference computed in fp8 stands where the program stands: it
    has to fail one of the numbers. Computed in bfloat16, what the
    configuration states, it passes them all."""
    cell = harness.load_cell(workload, tiny_root)
    arch, mix = cell.arch, cell.mix
    seed = 21
    params = weights.make(weights.seed_key(seed), arch)
    batches = list(itertools.islice(families.batches(arch, mix, seed), 3))
    rows = mix["reference_block_rows"]
    readings = {}
    for name, quant in (("ref", None), ("fp8", lowp.fp8),
                        ("bf16", lowp.bf16)):
        kw = {} if quant is None else {"quant": quant}
        readings[name] = ref_train.run(arch, params, batches,
                                       mix["optimizer"], mix["hp"],
                                       block_rows=rows, **kw)
    low, _ = compare.train_numbers(readings["fp8"], readings["ref"])
    stated, _ = compare.train_numbers(readings["bf16"], readings["ref"])
    ok_low, _ = harness.compare(low, CONTROL_LIMITS)
    ok_stated, _ = harness.compare(stated, CONTROL_LIMITS)
    assert ok_stated and not ok_low, (stated, low)
    assert low["grad_norm_gap"] > 3 * stated["grad_norm_gap"]
    assert low["change_norm_gap"] > 3 * stated["change_norm_gap"]


def test_served_control_in_fp8_reads_wider_than_the_served_tokens(tiny_root):
    """The serving control: at the positions of the served tokens, the gap
    of the token that the fp8 reference puts first."""
    from benchmark import serve_cell

    cell = harness.load_cell(SERVE_CELL, tiny_root)
    rng = np.random.default_rng(5)
    params = weights.make(weights.seed_key(9), cell.arch)
    fam = family("gpt2")

    class Rec:
        pass

    picked = []
    for _ in range(6):
        rec = Rec()
        rec.arrival = Rec()
        rec.arrival.prompt = rng.integers(0, 250, 20, dtype=np.int32)
        seq = list(rec.arrival.prompt)
        out = []
        for _ in range(10):     # greedy tokens of the reference itself
            logits = fam.logits(params, cell.arch,
                                jnp.asarray([seq], jnp.int32))
            out.append(int(jnp.argmax(logits[0, -1])))
            seq.append(out[-1])
        rec.tokens = np.asarray(out, np.int64)
        picked.append(rec)
    own, n = serve_cell.served_gap(cell, 9, picked)
    control, _ = serve_cell.served_gap(cell, 9, picked, lowp.fp8)
    assert n == 60 and own == pytest.approx(0.0, abs=1e-5)
    assert control > 0.01


def test_half_batch_fault_in_the_reference_place(tiny_root):
    cell = harness.load_cell("gpt2_345m_train", tiny_root)
    arch, mix = cell.arch, cell.mix
    params = weights.make(weights.seed_key(4), arch)
    batches = list(itertools.islice(families.batches(arch, mix, 4), 3))
    ref = ref_train.Reference(arch, mix["optimizer"], mix["hp"])
    want = ref.run(params, batches)
    got = ref.run(params, batches, keep_rows=mix["batch"] // 2)
    numbers, _ = compare.train_numbers(got, want)
    assert numbers["grad_norm_gap"] > 10 * 0.0085


def test_worst_norm_gap_measures_against_the_median_tensor():
    want = np.array([1.0, 2.0, 1e-9, 3.0])
    got = np.array([1.0, 2.2, 5e-9, 3.0])
    gap, i = compare.worst_norm_gap(got, want)
    assert i == 1 and gap == pytest.approx(0.1)     # not the tiny tensor
    gap, _ = compare.worst_norm_gap(got, want,
                                    keep=np.array([1, 0, 1, 1], bool))
    assert gap < 1e-8
    gap, i = compare.worst_norm_gap(np.array([np.nan, 1.0]),
                                    np.array([1.0, 1.0]))
    assert gap == np.inf and i == 0


def test_tail_gap_sets_the_worst_tensors_aside():
    want = np.ones(100)
    got = np.ones(100)
    got[:3] = 2.0                     # three noisy tensors of a hundred
    assert compare.worst_norm_gap(got, want)[0] == pytest.approx(1.0)
    assert compare.tail_norm_gap(got, want) == 0.0
    got[:18] = 1.3                    # a whole layer's worth
    assert compare.tail_norm_gap(got, want) == pytest.approx(0.3)


def test_serve_gap_is_zero_for_the_best_token_and_wide_for_a_random_one():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(5, 1000))
    best = logits.argmax(axis=-1)
    assert compare.serve_gap(logits, best) == 0.0
    assert compare.serve_gap(logits, (best + 1) % 1000) > 1.0


def test_prefill_and_decode_against_the_reference(tiny_root):
    """The serving path's tokens (prefill through the engine, decode
    through the slotted cache) are the reference's best at every position,
    or within the limit of it."""
    result, compared = run_cell(tiny_root, SERVE_CELL, seed=5, seconds=1.0)
    assert result["correct"] is True
    assert result["notes"]["tokens_compared"] > 50
    assert compared["served_logit_gap"][0] <= 0.5
    assert compared["failed_requests"] == [0.0, 0.0]


def test_train_numbers_works_out_only_what_the_limits_name():
    norms = {"a": np.array([1.0, 2.0]), "b": np.array(3.0)}
    got = {"losses": [1.0, 0.9], "grad_norms": norms, "change_norms": norms}
    numbers, notes = compare.train_numbers(
        got, got, {"grad_norm_gap": 0.1, "compiles_in_window": 0.0})
    assert numbers == {"grad_norm_gap": 0.0}
    assert notes["tensors"] == 3
    every, _ = compare.train_numbers(got, got)
    assert set(every) == set(compare.TRAIN_NUMBERS)
