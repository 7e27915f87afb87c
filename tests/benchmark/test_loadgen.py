"""The traffic generator: the same seed gives the same traffic, lengths
stay inside their clips, and a seed reorders the work without changing it."""

import json

import numpy as np
import pytest

from benchmark import families, loadgen
from bench_tiny import CHAT_MIX as CHAT, REPO


def mix_of(name):
    return json.loads((REPO / "benchmark" / "traffic"
                       / f"{name}.json").read_text())


GPT2 = {"family": "gpt2", "vocab_real": 50257}
BERT = {"family": "bert", "vocab_real": 30522}
BIG_SEED = 2 ** 31 + 12345


def test_same_seed_same_requests():
    a = loadgen.schedule(CHAT, 50257, BIG_SEED, 40.0)
    b = loadgen.schedule(CHAT, 50257, BIG_SEED, 40.0)
    assert len(a) == len(b) > 50
    for x, y in zip(a, b):
        assert (x.rid, x.due_s, x.max_new_tokens) == \
            (y.rid, y.due_s, y.max_new_tokens)
        assert np.array_equal(x.prompt, y.prompt)


def test_lengths_inside_their_clips():
    for seed in (1, BIG_SEED):
        for a in loadgen.schedule(CHAT, 50257, seed, 60.0):
            assert 16 <= len(a.prompt) <= 640
            assert 8 <= a.max_new_tokens <= 128
            assert len(a.prompt) + a.max_new_tokens <= 1024
            assert a.prompt.dtype == np.int32
            assert 0 <= a.prompt.min() and a.prompt.max() < 50257


def test_due_times_ascend_at_the_stated_rate():
    arrivals = loadgen.schedule(CHAT, 50257, 3, 40.0)
    due = [a.due_s for a in arrivals]
    assert due == sorted(due) and due[-1] < 40.0
    assert len(arrivals) == pytest.approx(CHAT["rate_rps"] * 40.0, rel=0.06)


def test_a_seed_reorders_the_work_and_does_not_change_it():
    a = loadgen.schedule(CHAT, 50257, 1, 40.0)
    b = loadgen.schedule(CHAT, 50257, 2, 40.0)
    assert len(a) == len(b)
    assert sorted(len(x.prompt) for x in a) == \
        sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    for k in range(4):   # each period holds the same work
        lo, hi = k * CHAT["period_s"], (k + 1) * CHAT["period_s"]
        assert sum(x.max_new_tokens for x in a if lo <= x.due_s < hi) == \
            sum(x.max_new_tokens for x in b if lo <= x.due_s < hi)


def test_causal_lm_batches():
    mix = mix_of("lm_seq1024_b16")
    a = families.batches(GPT2, mix, BIG_SEED)
    b = families.batches(GPT2, mix, BIG_SEED)
    first, second = next(a), next(a)
    assert np.array_equal(first["tokens"], next(b)["tokens"])
    assert first["tokens"].shape == first["labels"].shape == (16, 1024)
    assert np.array_equal(first["tokens"][:, 1:], first["labels"][:, :-1])
    assert not np.array_equal(first["tokens"], second["tokens"])
    assert len({row.tobytes() for row in first["tokens"]}) == 16
    assert first["tokens"].max() < 50257


def test_mlm_nsp_batches():
    mix = dict(mix_of("mlm_nsp_seq128_b64"), min_len=32)
    batch = next(families.batches(BERT, mix, 9))
    assert batch["tokens"].shape == (64, 128)
    real = batch["padding_mask"].astype(bool)
    assert (32 <= real.sum(axis=1)).all() and (real.sum(axis=1) <= 128).all()
    chosen = batch["loss_mask"].astype(bool)
    assert not (chosen & ~real).any()             # only real positions score
    assert chosen.any(axis=1).all()
    assert (batch["tokens"][chosen] == loadgen.MASK_ID).all()
    assert 0.10 < chosen.sum() / real.sum() < 0.20
    assert (batch["tokens"][~real] == 0).all()
    assert set(np.unique(batch["segments"])) <= {0, 1}
    assert set(np.unique(batch["nsp_labels"])) <= {0, 1}
    # the cell's own file fills every row (see PERF.md: the program's
    # masked softmax divides 0 by 0 on a row with padding)
    full = next(families.batches(BERT, mix_of("mlm_nsp_seq128_b64"), 9))
    assert full["padding_mask"].all()


def test_periodic_schedule_hands_on_what_it_takes_over():
    """With a cycle, the lead replays the cycle's end: the same gaps and
    lengths at the same offsets, so a window of one cycle emits what it is
    offered, whatever the seed puts at its edges."""
    lead, cycle = CHAT["ramp_s"], 40.0
    for seed in (1, BIG_SEED):
        a = loadgen.schedule(CHAT, 50257, seed, lead + cycle + 1.0,
                             lead_s=lead, cycle_s=cycle)

        def shape(lo, hi):
            return [(round(x.due_s - lo, 6), len(x.prompt), x.max_new_tokens)
                    for x in a if lo <= x.due_s < hi]

        assert shape(0.0, lead) == shape(cycle, cycle + lead)
        offered = sum(x.max_new_tokens for x in a
                      if lead <= x.due_s < lead + cycle)
        assert offered == 3953      # the same for every seed
    prompts = [x.prompt for x in a]
    assert not np.array_equal(prompts[0], prompts[len(shape(0.0, cycle))])
