"""A calibration's records: each names the device it was read on and
holds its numbers to the cell's committed limits, so that a control's
record says ``correct: false`` by the same ``harness.compare`` a run
uses. (The readings themselves come from the chip; see PERF.md.)"""

import json

import pytest

from bench_tiny import REPO
from benchmark import calibrate, harness

V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
# BERT's lowest control reading and highest program reading of each held
# number (my chip runs 7 and 11, PR 24; benchmark/limits/bert_large_train.json)
BERT_CONTROL_LOWEST = {"grad_norm_gap": 0.0273, "grad_gap_p97": 0.0102,
                       "median_change_gap": 0.00005}
BERT_PROGRAM_HIGHEST = {"grad_norm_gap": 0.0087, "grad_gap_p97": 0.00272,
                        "median_change_gap": 0.0050}


def test_records_carry_the_device_and_the_verdict(tmp_path, capsys):
    cell = harness.load_cell("bert_large_train", REPO)
    out = tmp_path / "cal.jsonl"
    say = calibrate.Record(cell, V5E, str(out))
    say("program", seed=1, numbers=dict(BERT_PROGRAM_HIGHEST, loss_gap=0.02))
    say("control_fp8", seed=1, numbers=BERT_CONTROL_LOWEST,
        vectors={"names": ["x"]})
    say("engine_start", seconds=3.0)
    program, control, other = [json.loads(line)
                               for line in out.read_text().splitlines()]
    assert program["device"] == control["device"] == other["device"] == V5E
    assert program["correct"] is True
    assert "loss_gap" not in program["compared"]    # no limit: not held
    assert control["correct"] is False
    assert control["compared"]["grad_norm_gap"] == [0.0273, 0.016]
    assert "correct" not in other
    printed = capsys.readouterr().out
    assert '"vectors"' not in printed and '"vectors"' in out.read_text()


def test_calibration_needs_the_chip(monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["calibrate.py", "--workload",
                                     "gpt2_345m_train", "--seeds", "1"])
    with pytest.raises(harness.BenchmarkError, match="needs a TPU"):
        calibrate.main()
    assert capsys.readouterr().out == ""
