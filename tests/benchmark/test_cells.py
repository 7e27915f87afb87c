"""Every cell's files are found by name, the committed ``BENCHMARK.json``
keeps to the contract's shape, and a made-up extra configuration, mix,
per-layer metric and cell are taken with no edit to a file that is there."""

import json
import re

import pytest

from bench_tiny import REPO, build_tiny_root, run_cell
from benchmark import harness

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_files_are_found_by_name(workload):
    cell = harness.load_cell(workload, REPO)
    assert cell.chips == 1
    assert cell.mix["kind"] == "train"
    assert cell.arch["hidden"] == 1024 and cell.arch["layers"] == 24
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"], REPO))
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
    assert cell.limits, "a committed cell has its limits"
    assert len(cell.mix["why"]) > 40


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + CELLS + [c["name"] for c in BENCH["configs"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m["workloads"]) <= set(CELLS)
        layers.add(m["layer"])
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert (REPO / c["file"]).exists() and c["reduced"] == []
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert (REPO / "BENCHMARK.json").stat().st_size < 64 * 1024


MADE_UP_FAMILY = '''
"""A made-up third family: a causal LM under llama-style keys with an
untied head, run by the program's GPTModel."""

from benchmark import loadgen
from benchmark.families import megatron

TOP_LEAVES = {"wte": ("word_embeddings", "weight"),
              "wpe": ("position_embeddings",),
              "lnf_g": ("final_layernorm", "weight"),
              "lnf_b": ("final_layernorm", "bias"),
              "head": ("lm_head",)}
TASKS = {"causal_lm": loadgen.causal_lm_batches}


def arch(config):
    return {"family": config["family"], "hidden": config["hidden_size"],
            "layers": config["num_hidden_layers"],
            "heads": config["num_attention_heads"],
            "ffn": config["intermediate_size"],
            "positions": config["max_position_embeddings"],
            "vocab_real": config["vocab_size"],
            "vocab": config["vocab_size"], "eps": config["norm_eps"],
            "act": "gelu_tanh", "tied": False}


def shapes(arch):
    return dict(megatron.stack_shapes(arch),
                head=(arch["hidden"], arch["vocab"]))


def matmul_params(arch):
    h, f = arch["hidden"], arch["ffn"]
    return arch["layers"] * (4 * h * h + 2 * h * f) + h * arch["vocab"]


def fwd_flops_per_token(arch, seq):
    return megatron.palm_fwd_flops_per_token(arch, seq, matmul_params(arch))


def to_program(canon, arch):
    return megatron.to_program(canon, arch, TOP_LEAVES)


def from_program(tree, arch):
    return megatron.from_program(tree, arch, shapes(arch), TOP_LEAVES)


def build_model(arch, mix, decode=False):
    from apex_tpu.models import GPTModel

    return GPTModel(megatron.model_config(arch, mix, causal=True),
                    decode=decode)


def loss(model):
    from benchmark.families import gpt2

    return gpt2.loss(model)
'''
MADE_UP_REFERENCE = '''
"""Its plain reference: the same causal stack, head untied."""
from benchmark.reference.gpt2 import logits, loss_part, totals  # noqa: F401
'''


def test_a_later_pr_adds_a_cell_as_files_and_entries_alone(tmp_path,
                                                           monkeypatch):
    """A made-up model family with its reference, configuration, mix,
    per-layer metrics and cell: new files and new entries, and no file
    that was there is edited."""
    import benchmark.families
    import benchmark.reference

    root = build_tiny_root(tmp_path)
    data = root / "benchmark"
    before = {p: p.read_bytes() for p in data.rglob("*") if p.is_file()}
    code_before = {p: p.read_bytes()
                   for p in (REPO / "benchmark").rglob("*.py")}
    # the new family's two modules, where the packages look for them (a
    # later PR writes them into benchmark/families and benchmark/reference)
    for package, text in ((benchmark.families, MADE_UP_FAMILY),
                          (benchmark.reference, MADE_UP_REFERENCE)):
        home = tmp_path / "new_code" / package.__name__.rsplit(".", 1)[-1]
        home.mkdir(parents=True)
        (home / "made_up_lm.py").write_text(text)
        monkeypatch.setattr(package, "__path__",
                            list(package.__path__) + [str(home)])
    (data / "configs" / "made-up-lm.json").write_text(json.dumps({
        "family": "made_up_lm", "hidden_size": 32, "num_hidden_layers": 1,
        "num_attention_heads": 2, "intermediate_size": 64,
        "max_position_embeddings": 64, "vocab_size": 256,
        "norm_eps": 1e-5}))
    mix = json.loads((data / "traffic" / "lm_seq1024_b16.json").read_text())
    mix.update(batch=2, seq=64)
    (data / "traffic" / "made_up_mix.json").write_text(json.dumps(mix))
    (data / "limits" / "made_up_cell.json").write_text(json.dumps(
        {"loss_gap": 4e-4, "grad_norm_gap": 0.04}))
    (data / "layer_metrics" / "made_up.steps.py").write_text(
        "def read(ctx):\n    return float(ctx['window']['steps'])\n")
    (data / "layer_metrics" / "made_up.gflop.py").write_text(
        "def read(ctx):\n    return ctx['flops'].train_flops_per_token("
        "ctx['arch'], ctx['mix']['seq']) / 1e9\n")
    (data / "layer_metrics" / "made_up.silent.py").write_text(
        "def read(ctx):\n    return None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "made-up-lm", "source": "https://example.org/made-up",
        "file": "benchmark/configs/made-up-lm.json", "reduced": [],
        "why": "made up"})
    bench["workloads"].append({
        "name": "made_up_cell", "config": "made-up-lm",
        "traffic": "made_up_mix", "chips": 1, "why": "made up"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("made_up_cell")
    for name in ("made_up.steps", "made_up.gflop", "made_up.silent"):
        bench["per_layer"].append({
            "name": name, "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "train step",
            "moves": "train_tokens_per_s_per_chip",
            "workloads": ["made_up_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    result, compared = run_cell(root, "made_up_cell", trace=True,
                                seconds=0.3)
    assert result["correct"] is True      # held to its own reference
    assert set(compared) == {"loss_gap", "grad_norm_gap",
                             "compiles_in_window"}
    assert result["metrics"]["made_up.steps"]["value"] == \
        result["attempted"] > 0
    # 3 x (2 x (4 h^2 + 2 h ffn + h vocab) + 4 seq h), one layer, by hand
    assert result["metrics"]["made_up.gflop"]["value"] == pytest.approx(
        3 * (2 * (4 * 32 * 32 + 2 * 32 * 64 + 32 * 256) + 4 * 64 * 32)
        / 1e9)
    assert "made_up.silent" not in result["metrics"]    # nothing to read
    # metrics that list other cells are not handed to this one
    assert "step_ms_p50" not in result["metrics"]
    result, _ = run_cell(root, "made_up_cell", seconds=0.3)
    assert set(result["metrics"]) == {"train_tokens_per_s_per_chip",
                                      "setup_s"}
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"
    for path, content in code_before.items():
        assert path.read_bytes() == content, f"{path} was edited"


def test_unknown_cell_and_unknown_device_are_errors():
    with pytest.raises(harness.BenchmarkError, match="unknown workload"):
        harness.load_cell("no_such_cell", REPO)
    with pytest.raises(harness.BenchmarkError, match="not in benchmark"):
        harness.peaks("cpu")
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_a_metric_without_workloads_goes_to_cells_that_report_its_target():
    metrics = [{"name": "a", "moves": "x"}, {"name": "b", "moves": "y"},
               {"name": "c", "moves": "y", "workloads": ["other"]}]
    got = harness._selected(metrics, "cell", reported={"x"})
    assert [m["name"] for m in got] == ["a"]
