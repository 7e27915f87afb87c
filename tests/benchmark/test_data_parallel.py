"""The ``data=4`` train path, rehearsed on four virtual CPU devices, so
that ``gpt2_345m_train_dp4`` can arrive later as data: a traffic file with
``"mesh": {"data": 4}`` and a cell with ``"chips": 4``."""

import json

import jax
import pytest

from bench_tiny import build_tiny_root
from benchmark import harness, train_cell


@pytest.mark.multi_device
def test_dp4_first_loss_equals_the_one_device_loss(tmp_path):
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    root = build_tiny_root(tmp_path)
    traffic = root / "benchmark" / "traffic"
    mix = json.loads((traffic / "lm_seq1024_b16.json").read_text())
    mix.update(batch=2)
    (traffic / "dp4.json").write_text(json.dumps(
        dict(mix, mesh={"data": 4})))
    (traffic / "one_device_global.json").write_text(json.dumps(
        dict(mix, batch=8)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, mixname, chips in (("dp4_cell", "dp4", 4),
                                 ("global_cell", "one_device_global", 1)):
        bench["workloads"].append({
            "name": name, "config": "gpt2-345m", "traffic": mixname,
            "chips": chips, "why": "rehearsal"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    losses = {}
    for name in ("dp4_cell", "global_cell"):
        cell = harness.load_cell(name, root)
        stepper = train_cell.Stepper(cell, seed=13)
        first = stepper.dispatch()
        if name == "dp4_cell":
            homes = {s.device for s in
                     jax.tree_util.tree_leaves(stepper.params)[0]
                     .addressable_shards}
            assert len(homes) == 4
        losses[name] = [stepper.fetch(first),
                        stepper.fetch(stepper.dispatch())]
    assert losses["dp4_cell"][0] == pytest.approx(
        losses["global_cell"][0], rel=1e-5)
    assert losses["dp4_cell"][1] == pytest.approx(
        losses["global_cell"][1], rel=1e-4)
    assert train_cell.global_mix(harness.load_cell(
        "dp4_cell", root).mix)["batch"] == 8
