"""One tiny-size run of each kind of cell under ``JAX_PLATFORMS=cpu``:
the last line's keys, and device metrics absent, not faked, off the chip.
Also what a run does without a chip, and in a bare directory."""

import json
import os
import subprocess
import sys

import pytest

from bench_tiny import REPO, SERVE_CELL, run_cell
from benchmark import harness, run

DEVICE_METRICS = {"train_mfu_pct", "matmul_time_share_pct",
                  "attention_roofline", "device_idle_pct.train",
                  "serve_mfu_pct", "copy_time_share_pct",
                  "device_idle_pct.serve"}
CELLS = ["gpt2_345m_train", "bert_large_train", SERVE_CELL]


@pytest.mark.parametrize("workload", CELLS)
def test_end_to_end_line(tiny_root, workload, capsys):
    result, compared = run_cell(tiny_root, workload, seed=2 ** 31 + 7)
    harness.emit(result, compared)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    cell = harness.load_cell(workload, tiny_root)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]
    # each number compared stands beside its limit, also on stderr
    for name, (value, limit) in line["compared"].items():
        assert f"compared {name}: {value} limit {limit}" in err
    assert all(limit is None or value <= limit
               for value, limit in line["compared"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_traced_line_has_no_device_metric_off_the_chip(tiny_root, workload):
    result, _ = run_cell(tiny_root, workload, trace=True)
    cell = harness.load_cell(workload, tiny_root)
    names = set(result["metrics"])
    assert names, "the host-clock and counter metrics are still read"
    assert names <= {m["name"] for m in cell.per_layer}
    assert not names & DEVICE_METRICS
    assert "breakdown" not in result and "busy_s" not in result["device"]
    assert result["correct"] is True


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "gpt2_345m_train", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "needs a TPU" in err


def test_bare_directory_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program, so
    another exit code than 0 and no result."""
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2_345m_train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
