"""The comparison that decides ``correct`` is shown to fail.

Each test skips the harness's look for a chip and drives the rest of a
run with the timed path broken underneath (the ``Stepper`` or ``Driver``
the run builds is wrapped from outside; ``run`` has no hook for it): a step that returns its state
unchanged; half of the batch left out, the mean taken over the rest; a
token altered where it is produced. (The exchange between chips is not in
a one-chip cell.) ``correct`` has to come out false. The control, the
reference in fp8 put in the program's place, is tested at this size in
``test_reference.py``.

Readings at these sizes on the CPU that ``bench_tiny.TINY_LIMITS`` were
set from (builder's run, four seeds): the program reads ``loss_gap`` up to
1.8e-5 (GPT-2) and 3.0e-4 (BERT) and ``grad_norm_gap`` up to 0.0085; half
a batch reads ``loss_gap`` 7.3e-4 to 6.6e-3 and ``grad_norm_gap`` 0.086 to
0.82; a state left unchanged reads ``grad_norm_gap`` 1 by construction.
"""

import numpy as np
import pytest

from bench_tiny import SERVE_CELL, run_cell

TRAIN_CELLS = ["gpt2_345m_train", "bert_large_train"]


def state_unchanged(stepper):
    real = stepper.step

    def step(params, opt_state, batch):
        _, _, loss = real(jax_copy(params), jax_copy(opt_state), batch)
        return params, opt_state, loss

    stepper.step = step


def jax_copy(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.copy, tree)


def half_batch(stepper):
    real = stepper.next_batch

    def next_batch():
        batch = real()
        return {k: v[:v.shape[0] // 2] for k, v in batch.items()}

    stepper.next_batch = next_batch


def altered_token(driver):
    real = driver.engine.decode
    vocab = driver.engine.model.config.vocab_size

    def decode(*args, **kw):
        tokens, finite = real(*args, **kw)
        return (np.asarray(tokens) + 1) % vocab, finite

    driver.engine.decode = decode


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def test_sound_run_is_correct(tiny_root, workload):
    result, compared = run_cell(tiny_root, workload)
    assert result["correct"] is True
    assert compared["grad_norm_gap"][0] < compared["grad_norm_gap"][1]


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def test_state_left_unchanged_is_not_correct(tiny_root, workload):
    result, compared = run_cell(tiny_root, workload, fault=state_unchanged)
    assert result["correct"] is False
    assert compared["grad_norm_gap"][0] == pytest.approx(1.0)
    change = "change_norm_gap" if "gpt2" in workload else "median_change_gap"
    assert compared[change][0] == pytest.approx(1.0)


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def test_half_the_batch_left_out_is_not_correct(tiny_root, workload):
    result, compared = run_cell(tiny_root, workload, fault=half_batch)
    assert result["correct"] is False
    value, limit = compared["grad_norm_gap"]
    assert value > limit or compared["loss_gap"][0] > compared["loss_gap"][1]


def test_altered_token_is_not_correct(tiny_root):
    sound, compared = run_cell(tiny_root, SERVE_CELL)
    assert sound["correct"] is True
    assert compared["served_logit_gap"][0] <= 0.5
    result, compared = run_cell(tiny_root, SERVE_CELL, fault=altered_token)
    assert result["correct"] is False
    value, limit = compared["served_logit_gap"]
    assert value > 2.0 > limit     # a random token lies sigmas below


def test_a_compile_in_the_window_is_not_correct(tiny_root, monkeypatch):
    from benchmark import harness

    counts = iter([5, 6])
    monkeypatch.setattr(harness, "compiles_so_far", lambda: next(counts))
    result, compared = run_cell(tiny_root, "gpt2_345m_train")
    assert compared["compiles_in_window"] == [1.0, 0.0]
    assert result["correct"] is False


def test_a_missing_number_or_a_nan_is_not_correct():
    from benchmark import harness

    ok, compared = harness.compare({"a": 0.1}, {"a": 0.2, "b": 0.3})
    assert not ok and compared["b"] == [None, 0.3]
    ok, _ = harness.compare({"a": float("nan")}, {"a": 0.2})
    assert not ok
    ok, compared = harness.compare({"a": 0.1, "free": 9.0}, {"a": 0.2})
    assert ok and compared["free"] == [9.0, None]
