"""Helpers for the benchmark's tests: a checkout of its own, holding
``BENCHMARK.json`` and the data files of the real cells cut to a size a
test run can hold (the widths and lengths are tiny; the kinds of cell, the
metrics and the readers are the real ones). The serve cell is not in the
committed ``BENCHMARK.json`` (PERF.md, Open questions): the tiny checkout
brings one as a later PR would, as a traffic file, a limits file and
entries."""

import json
import pathlib
import shutil
from unittest import mock

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY_GPT2 = dict(n_embd=64, n_layer=2, n_head=4, n_positions=128,
                 n_inner=256, vocab_size=250)
TINY_BERT = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                 intermediate_size=256, max_position_embeddings=64,
                 vocab_size=250)
# limits for the tiny sizes on the CPU, from the readings in the
# docstring of test_faults.py (the chip's limits are in benchmark/limits)
TINY_LIMITS = {
    "gpt2_345m_train": {"loss_gap": 4e-4, "grad_norm_gap": 0.04,
                        "change_norm_gap": 0.5},
    "bert_large_train": {"loss_gap": 1e-3, "grad_norm_gap": 0.04,
                         "median_change_gap": 0.5},
    "serve_chat_tiny": {"served_logit_gap": 0.5},
}
# the chat mix ISSUE 24 describes, at the rate PR 24's runs offered: the
# load generator's tests read it, and the tiny serve cell is cut from it
CHAT_MIX = {
    "kind": "serve", "rate_rps": 1.75, "period_s": 8.0,
    "shape_seed": 20260929,
    "prompt_len": {"median": 128, "sigma": 0.8, "min": 16, "max": 640},
    "output_len": {"median": 48, "sigma": 0.6, "min": 8, "max": 128},
    "max_total_len": 1024, "ramp_s": 16.0,
    "engine": {"num_slots": 16, "cache_mode": "bf16",
               "batch_buckets": [2, 16], "prefill_buckets": [640]},
    "why": "chat-length requests, open loop, Poisson on the wall clock",
}
TINY_CHAT = dict(
    CHAT_MIX, rate_rps=20.0, period_s=1.0, ramp_s=1.0, max_total_len=64,
    prompt_len={"median": 12, "sigma": 0.5, "min": 4, "max": 32},
    output_len={"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    engine={"num_slots": 4, "cache_mode": "bf16", "batch_buckets": [2, 4],
            "prefill_buckets": [32]})
SERVE_CELL = "serve_chat_tiny"
SERVE_END_TO_END = [("serve_tokens_per_s", "tokens/s", "higher"),
                    ("itl_p95_ms", "ms", "lower")]
# reader name -> the end-to-end metric it moves
SERVE_PER_LAYER = {
    "gen_lateness_p95_ms": "serve_tokens_per_s",
    "queue_wait_p50_ms": "serve_tokens_per_s",
    "ttft_p50_ms.sched": "serve_tokens_per_s",
    "ttft_p95_ms.sched": "serve_tokens_per_s",
    "slot_occupancy_pct": "serve_tokens_per_s",
    "prefill_call_ms_p50": "serve_tokens_per_s",
    "serve_mfu_pct": "serve_tokens_per_s",
    "decode_step_ms_p50": "itl_p95_ms",
    "copy_time_share_pct": "itl_p95_ms",
    "device_idle_pct.serve": "itl_p95_ms",
}


def _rewrite(path, **changes):
    data = json.loads(path.read_text())
    for key, value in changes.items():
        if isinstance(value, dict) and isinstance(data.get(key), dict):
            data[key].update(value)
        else:
            data[key] = value
    path.write_text(json.dumps(data, indent=1))


def add_serve_cell(root: pathlib.Path):
    """The serve cell, as files and entries alone."""
    data = root / "benchmark"
    (data / "traffic" / "chat_tiny.json").write_text(json.dumps(TINY_CHAT))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": SERVE_CELL, "config": "gpt2-345m", "traffic": "chat_tiny",
        "chips": 1, "why": "open-loop chat through continuous batching"})
    for name, unit, better in SERVE_END_TO_END:
        bench["end_to_end"].append({
            "name": name, "unit": unit, "better": better, "bound": 0.03,
            "source": "host_clock", "workloads": [SERVE_CELL]})
    for name, moves in SERVE_PER_LAYER.items():
        bench["per_layer"].append({
            "name": name, "unit": "%" if name.endswith("pct") else "ms",
            "better": "lower", "source": "host_clock", "layer": "serving",
            "moves": moves, "workloads": [SERVE_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def build_tiny_root(root: pathlib.Path) -> pathlib.Path:
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    data = root / "benchmark"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, data / sub)
    (data / "limits").mkdir()
    for cell, limits in TINY_LIMITS.items():
        (data / "limits" / f"{cell}.json").write_text(json.dumps(limits))
    _rewrite(data / "configs" / "gpt2-345m.json", **TINY_GPT2,
             assumed={"padded_vocab_size": 256})
    _rewrite(data / "configs" / "bert-large.json", **TINY_BERT,
             assumed={"padded_vocab_size": 256})
    _rewrite(data / "traffic" / "lm_seq1024_b16.json", batch=4, seq=128,
             flash_attention=False)
    _rewrite(data / "traffic" / "mlm_nsp_seq128_b64.json", batch=8, seq=32,
             min_len=32, reference_block_rows=4)
    add_serve_cell(root)
    return root


def any_device(chips: int) -> dict:
    """In ``harness.require_chips``' place: whatever device JAX has,
    named as it is."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def run_cell(root, workload, *, seed=11, seconds=0.5, trace=False,
             fault=None):
    """Drive the rest of a run past the harness's look for a chip.
    ``fault`` breaks the timed path underneath: it is handed the
    ``Stepper`` or ``Driver`` the run builds, before the run uses it.
    -> (result line as the harness would print it, compared)."""
    import importlib

    from benchmark import harness

    cell = harness.load_cell(workload, root)
    kind = importlib.import_module(f"benchmark.{cell.mix['kind']}_cell")
    timed = "Stepper" if cell.mix["kind"] == "train" else "Driver"
    real = getattr(kind, timed)

    def built(*args, **kw):
        obj = real(*args, **kw)
        if fault is not None:
            fault(obj)
        return obj

    with mock.patch.object(kind, timed, built), \
            mock.patch.object(harness, "require_chips", any_device):
        return kind.run(cell, seed, seconds, trace, harness.Clock())
