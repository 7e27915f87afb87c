"""tools/bench_schema_check over wrapper records + the live bench._emit
output format (ISSUE 2 satellite: the bench JSON contract — incl. the
telemetry fields — is enforced)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "tools"))
sys.path.insert(0, ROOT)

import bench_schema_check as schema  # noqa: E402


def test_wrapper_schema_rejects_bad_records():
    errors = schema.check_wrapper({"n": "one", "cmd": 3, "rc": 0},
                                  errors=[])
    joined = "\n".join(errors)
    assert "key 'n'" in joined
    assert "key 'cmd'" in joined
    assert "missing required key 'tail'" in joined
    assert "rc == 0 but no parsed metric line" in joined


def test_metric_line_requires_telemetry_fields_since_round7():
    line = {"metric": "m", "value": 1.0, "unit": "x/sec",
            "vs_baseline": 1.0, "tflops_per_sec": 1.0, "mfu": 0.1,
            "comm_bytes_per_step": 10}
    # round 6: telemetry fields not yet required
    assert schema.check_metric_line(dict(line), round_n=6, errors=[]) == []
    errors = schema.check_metric_line(dict(line), round_n=7, errors=[])
    assert any("measured_comm_bytes_per_step" in e for e in errors)
    line.update(measured_comm_bytes_per_step=None,
                model_flops_per_step_xla=1e9)
    assert schema.check_metric_line(line, round_n=7, errors=[]) == []


def test_bench_error_contract_by_round():
    err = {"metric": "bench_error", "value": 0, "unit": "error",
           "vs_baseline": 0.0, "kind": "no_tpu"}
    assert schema.check_metric_line(dict(err), round_n=5, errors=[]) == []
    msgs = schema.check_metric_line(dict(err), round_n=6, errors=[])
    assert any("comm_bytes_per_step" in m for m in msgs)
    err["comm_bytes_per_step"] = None
    assert schema.check_metric_line(err, round_n=6, errors=[]) == []


def test_numerics_overhead_gated_at_round9():
    """ISSUE 4 satellite: numerics_overhead_pct (the ddp_numerics
    field) is defined from round 9 — older records carrying it are
    flagged, newer ones must hold a number or null."""
    line = {"metric": "ddp_numerics_steps_per_sec", "value": 1.0,
            "unit": "steps/sec", "vs_baseline": 1.0,
            "tflops_per_sec": 1.0, "mfu": 0.1,
            "comm_bytes_per_step": 10,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "numerics_overhead_pct": 3.2}
    assert schema.check_metric_line(dict(line), round_n=9, errors=[]) == []
    msgs = schema.check_metric_line(dict(line), round_n=8, errors=[])
    assert any("numerics_overhead_pct" in m for m in msgs)
    # absent stays valid at every round
    del line["numerics_overhead_pct"]
    assert schema.check_metric_line(dict(line), round_n=8, errors=[]) == []
    # type enforcement from round 9
    line["numerics_overhead_pct"] = "fast"
    msgs = schema.check_metric_line(dict(line), round_n=9, errors=[])
    assert any("must be numeric or null" in m for m in msgs)
    line["numerics_overhead_pct"] = None
    assert schema.check_metric_line(dict(line), round_n=9, errors=[]) == []


def test_memwatch_fields_gated_at_round10():
    """ISSUE 5 satellite: peak_hbm_bytes / hbm_headroom_pct /
    compile_count (the compile & memory observability fields) are
    required — nullable — from round 10; BENCH_r01-r06 records without
    them stay valid."""
    line = {"metric": "ddp_memwatch_steps_per_sec", "value": 1.0,
            "unit": "steps/sec", "vs_baseline": 1.0,
            "tflops_per_sec": 1.0, "mfu": 0.1,
            "comm_bytes_per_step": 10,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None}
    # round 9: not yet part of the contract
    assert schema.check_metric_line(dict(line), round_n=9, errors=[]) == []
    msgs = schema.check_metric_line(dict(line), round_n=10, errors=[])
    assert any("peak_hbm_bytes" in m for m in msgs)
    assert any("hbm_headroom_pct" in m for m in msgs)
    assert any("compile_count" in m for m in msgs)
    line.update(peak_hbm_bytes=123456, hbm_headroom_pct=87.5,
                compile_count=1)
    assert schema.check_metric_line(dict(line), round_n=10,
                                    errors=[]) == []
    # nullable: a config that measured neither still conforms
    line.update(peak_hbm_bytes=None, hbm_headroom_pct=None,
                compile_count=None)
    assert schema.check_metric_line(dict(line), round_n=10,
                                    errors=[]) == []
    # typed when present
    line["peak_hbm_bytes"] = "big"
    msgs = schema.check_metric_line(dict(line), round_n=10, errors=[])
    assert any("must be numeric or null" in m for m in msgs)
    line["peak_hbm_bytes"] = None
    line["compile_count"] = -2
    msgs = schema.check_metric_line(dict(line), round_n=10, errors=[])
    assert any("non-negative" in m for m in msgs)


def test_recovery_fields_gated_at_round13():
    """ISSUE 8 satellite: ddp_recovery's supervised-chaos accounting
    (restarts / mttr_steps / snapshot_restores / goodput_step_ratio)
    is required on ddp_recovery lines from round 13, and flagged on
    records from rounds where the fields did not exist."""
    base = {"metric": "ddp_recovery_steps_per_sec", "value": 1.0,
            "unit": "steps/sec", "vs_baseline": 1.0,
            "tflops_per_sec": 1.0, "mfu": 0.1,
            "comm_bytes_per_step": 10,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": None}
    line = dict(base, restarts=3, mttr_steps=2.7, snapshot_restores=2,
                goodput_step_ratio=0.64)
    assert schema.check_metric_line(dict(line), round_n=13, errors=[]) == []
    # a pre-13 record carrying them is flagged — the fields did not exist
    msgs = schema.check_metric_line(dict(line), round_n=12, errors=[])
    assert any("only defined" in m for m in msgs)
    # from 13, a ddp_recovery line without them is incomplete
    msgs = schema.check_metric_line(dict(base), round_n=13, errors=[])
    for key in ("restarts", "mttr_steps", "snapshot_restores",
                "goodput_step_ratio"):
        assert any(key in m for m in msgs)
    # other configs never need them
    other = dict(base, metric="gpt2_345m_tokens_per_sec_per_chip")
    assert schema.check_metric_line(other, round_n=13, errors=[]) == []
    # typed when present
    line["mttr_steps"] = "fast"
    msgs = schema.check_metric_line(dict(line), round_n=13, errors=[])
    assert any("must be numeric or null" in m for m in msgs)


def test_lint_violations_gated_at_round14():
    """ISSUE 9 satellite: lint_violations (the static HLO lint's
    finding count over the lowered step — apex_tpu.analysis) is
    required, nullable, on every successful metric line from round 14;
    a pre-round-14 record carrying it is flagged."""
    base = {"metric": "gpt2_345m_tokens_per_sec_per_chip", "value": 1.0,
            "unit": "tokens/sec", "vs_baseline": 1.0,
            "tflops_per_sec": 1.0, "mfu": 0.1,
            "comm_bytes_per_step": 10,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": None}
    # round 13: not yet part of the contract — absent is valid, and a
    # live line carrying it (bench._emit always writes the key) is
    # tolerated, same as the memwatch fields
    assert schema.check_metric_line(dict(base), round_n=13,
                                    errors=[]) == []
    assert schema.check_metric_line(dict(base, lint_violations=0),
                                    round_n=13, errors=[]) == []
    # from 14 the key is required
    msgs = schema.check_metric_line(dict(base), round_n=14, errors=[])
    assert any("lint_violations" in m for m in msgs)
    # nullable (bench ran without APEX_TPU_HLO_LINT=1) and zero both ok
    for val in (None, 0, 3):
        assert schema.check_metric_line(
            dict(base, lint_violations=val), round_n=14, errors=[]) == []
    # typed: negative or non-int rejected
    for bad in (-1, "clean", 1.5):
        msgs = schema.check_metric_line(
            dict(base, lint_violations=bad), round_n=14, errors=[])
        assert any("non-negative integer" in m for m in msgs)


def test_overlap_and_backend_fields_gated_at_round15():
    """ISSUE 10 satellite: the overlap contract (overlap_segments /
    comm_hidden_pct / baseline_step_ms on ddp_overlapped lines) and
    the one-shot backend probe verdict are defined from round 15 —
    overlap fields on older records are flagged, `backend` follows the
    tolerate-on-live-lines discipline."""
    base = {"metric": "gpt2_345m_tokens_per_sec_per_chip", "value": 1.0,
            "unit": "tokens/sec", "vs_baseline": 1.0,
            "tflops_per_sec": 1.0, "mfu": 0.1,
            "comm_bytes_per_step": 10,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": None, "lint_violations": None}
    # round 14: backend not yet required (tolerated when present with a
    # sane value), overlap fields did not exist
    assert schema.check_metric_line(dict(base), round_n=14,
                                    errors=[]) == []
    assert schema.check_metric_line(dict(base, backend="cpu-mesh"),
                                    round_n=14, errors=[]) == []
    msgs = schema.check_metric_line(dict(base, backend="gpu"),
                                    round_n=14, errors=[])
    assert any("backend" in m for m in msgs)
    msgs = schema.check_metric_line(dict(base, comm_hidden_pct=40.0),
                                    round_n=14, errors=[])
    assert any("only defined" in m for m in msgs)
    # round 15: backend required on every successful line
    msgs = schema.check_metric_line(dict(base), round_n=15, errors=[])
    assert any("backend" in m for m in msgs)
    base15 = dict(base, backend="cpu-mesh")
    assert schema.check_metric_line(dict(base15), round_n=15,
                                    errors=[]) == []
    for bogus in ("gpu", 3, True):
        msgs = schema.check_metric_line(dict(base15, backend=bogus),
                                        round_n=15, errors=[])
        assert any("backend" in m for m in msgs)
    # ddp_overlapped lines additionally need the overlap contract
    ovl = dict(base15, metric="ddp_overlapped_int8_steps_per_sec")
    msgs = schema.check_metric_line(dict(ovl), round_n=15, errors=[])
    assert sum("ddp_overlapped line missing" in m for m in msgs) == 3
    ovl.update(overlap_segments=4, comm_hidden_pct=47.7,
               baseline_step_ms=690.0)
    assert schema.check_metric_line(dict(ovl), round_n=15,
                                    errors=[]) == []
    # comm_hidden_pct is nullable (degenerate decomposition)
    assert schema.check_metric_line(dict(ovl, comm_hidden_pct=None),
                                    round_n=15, errors=[]) == []
    # non-overlapped lines never need the overlap fields
    assert schema.check_metric_line(dict(base15), round_n=15,
                                    errors=[]) == []


def test_fleet_fields_gated_at_round16():
    """ISSUE 11 satellite: the serve_fleet contract (per-tier p99
    TTFT, rebalance_latency_ms, replicas_respawned) is required on
    serve_fleet lines from round 16; pre-16 records carrying the
    fields are flagged, other configs never need them."""
    base = {"metric": "serve_fleet_tokens_per_sec", "value": 1.0,
            "unit": "tokens/sec", "vs_baseline": 1.0,
            "tflops_per_sec": 1.0, "mfu": 0.1,
            "comm_bytes_per_step": 0,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": 4, "lint_violations": None,
            "backend": "cpu-mesh"}
    msgs = schema.check_metric_line(dict(base), round_n=16, errors=[])
    for key in ("ttft_p99_ms_interactive", "ttft_p99_ms_batch",
                "rebalance_latency_ms", "replicas_respawned"):
        assert any(key in m for m in msgs)
    full = dict(base, ttft_p99_ms_interactive=2.0, ttft_p99_ms_batch=8.0,
                rebalance_latency_ms=1.2, replicas_respawned=1)
    assert schema.check_metric_line(dict(full), round_n=16,
                                    errors=[]) == []
    # nullable: a clean leg with no migration has no rebalance latency
    assert schema.check_metric_line(
        dict(full, rebalance_latency_ms=None), round_n=16,
        errors=[]) == []
    msgs = schema.check_metric_line(dict(full), round_n=15, errors=[])
    assert any("only defined from round 16" in m for m in msgs)
    msgs = schema.check_metric_line(
        dict(full, replicas_respawned="one"), round_n=16, errors=[])
    assert any("must be numeric or null" in m for m in msgs)
    other = dict(base, metric="gpt2_345m_tokens_per_sec_per_chip")
    assert schema.check_metric_line(other, round_n=16, errors=[]) == []


def test_serve_spec_fields_gated_at_round17():
    """ISSUE 12 satellite: the serve_spec contract
    (accepted_tokens_per_sec, acceptance_rate, prefix_hit_rate,
    ttft_p50_prefix_hit_ms) is required on serve_spec lines from round
    17; pre-17 records carrying the fields are flagged, other configs
    never need them."""
    base = {"metric": "serve_spec_accepted_tokens_per_sec",
            "value": 1200.0, "unit": "tokens/sec", "vs_baseline": 1.0,
            "tflops_per_sec": 1.0, "mfu": 0.1,
            "comm_bytes_per_step": 0,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": 9, "lint_violations": None,
            "backend": "cpu-mesh"}
    msgs = schema.check_metric_line(dict(base), round_n=17, errors=[])
    for key in ("accepted_tokens_per_sec", "acceptance_rate",
                "prefix_hit_rate", "ttft_p50_prefix_hit_ms"):
        assert any(key in m for m in msgs)
    full = dict(base, accepted_tokens_per_sec=1200.0,
                acceptance_rate=0.88, prefix_hit_rate=0.62,
                ttft_p50_prefix_hit_ms=44.3)
    assert schema.check_metric_line(dict(full), round_n=17,
                                    errors=[]) == []
    # nullable: a trace that never hit the store has no hit-TTFT p50
    assert schema.check_metric_line(
        dict(full, ttft_p50_prefix_hit_ms=None), round_n=17,
        errors=[]) == []
    msgs = schema.check_metric_line(dict(full), round_n=16, errors=[])
    assert any("only defined from round 17" in m for m in msgs)
    msgs = schema.check_metric_line(
        dict(full, acceptance_rate="high"), round_n=17, errors=[])
    assert any("must be numeric or null" in m for m in msgs)
    other = dict(base, metric="serve_decode_tokens_per_sec_per_chip",
                 ttft_p50_ms=1.0, ttft_p99_ms=2.0,
                 tok_latency_p50_ms=0.5, tok_latency_p99_ms=1.0,
                 kv_cache_bytes=1024)
    assert schema.check_metric_line(other, round_n=17, errors=[]) == []


def test_static_comm_gated_at_round18():
    """ISSUE 13 satellite: static_comm_bytes_per_step (the collective
    dataflow graph's ring-model wire bytes parsed from the lowered
    step — apex_tpu.analysis.sharding) is required, nullable, on every
    successful metric line from round 18; a pre-round-18 record
    carrying a measured value is flagged (the field did not exist
    yet), while the always-written null key on live lines is
    tolerated, same as lint_violations."""
    base = {"metric": "gpt2_345m_tokens_per_sec_per_chip", "value": 1.0,
            "unit": "tokens/sec", "vs_baseline": 1.0,
            "tflops_per_sec": 1.0, "mfu": 0.1,
            "comm_bytes_per_step": 10,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": None, "lint_violations": None,
            "backend": "cpu-mesh"}
    # round 17: absent is valid and the always-written key is
    # tolerated on LIVE lines (lint_violations discipline)
    assert schema.check_metric_line(dict(base), round_n=17,
                                    errors=[]) == []
    assert schema.check_metric_line(
        dict(base, static_comm_bytes_per_step=None), round_n=17,
        errors=[]) == []
    # ... but a CHECKED-IN pre-18 record carrying a measured value is
    # flagged — the field did not exist at capture time
    wrapper = {"n": 17, "cmd": "python bench.py", "rc": 0, "tail": "",
               "parsed": dict(base, static_comm_bytes_per_step=1820)}
    msgs = schema.check_wrapper(wrapper, errors=[])
    assert any("only defined from round 18" in m for m in msgs)
    assert schema.check_wrapper(
        {"n": 18, "cmd": "c", "rc": 0, "tail": "",
         "parsed": dict(base, static_comm_bytes_per_step=1820)},
        errors=[]) == []
    # from 18 the key is required
    msgs = schema.check_metric_line(dict(base), round_n=18, errors=[])
    assert any("static_comm_bytes_per_step" in m for m in msgs)
    # nullable (no step measured) and measured values both ok
    for val in (None, 0, 1820, 58695.0):
        assert schema.check_metric_line(
            dict(base, static_comm_bytes_per_step=val), round_n=18,
            errors=[]) == []
    # typed: negative or non-numeric rejected
    for bad in (-1, "many", True):
        msgs = schema.check_metric_line(
            dict(base, static_comm_bytes_per_step=bad), round_n=18,
            errors=[])
        assert any("non-negative number" in m for m in msgs)


def test_kernels_fields_gated_at_round19():
    """ISSUE 14 satellite: the kernels capture contract — per-family
    kernel-vs-XLA timings on kernels lines, the int4 dual-quantization
    wire model on ddp_compressed lines — is required from round 19;
    pre-19 records carrying the fields are flagged, other configs
    never need them."""
    base = {"metric": "kernels_speedup_geomean", "value": 1.0,
            "unit": "x", "vs_baseline": 1.0,
            "tflops_per_sec": 0.0, "mfu": 0.0,
            "comm_bytes_per_step": 0,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": None, "lint_violations": None,
            "static_comm_bytes_per_step": None,
            "backend": "cpu-mesh"}
    # round 19: every per-family timing pair is required
    msgs = schema.check_metric_line(dict(base), round_n=19, errors=[])
    for key in schema.KERNELS_REQUIRED_FIELDS:
        assert any(key in m for m in msgs)
    full = dict(base, **{k: 1.5 for k in
                         schema.KERNELS_REQUIRED_FIELDS})
    assert schema.check_metric_line(dict(full), round_n=19,
                                    errors=[]) == []
    # nullable (a family whose leg crashed records null)
    assert schema.check_metric_line(
        dict(full, lamb_kernel_ms=None), round_n=19, errors=[]) == []
    # pre-19 records carrying them are flagged
    msgs = schema.check_metric_line(dict(full), round_n=18, errors=[])
    assert any("only defined from round 19" in m for m in msgs)
    # typed
    msgs = schema.check_metric_line(
        dict(full, adam_xla_ms="fast"), round_n=19, errors=[])
    assert any("must be numeric or null" in m for m in msgs)

    # ddp_compressed: comm_bytes_per_step_int4 required from 19
    ddp = dict(base, metric="ddp_compressed_int8_steps_per_sec",
               value=1.1, unit="steps/sec")
    msgs = schema.check_metric_line(dict(ddp), round_n=19, errors=[])
    assert any("comm_bytes_per_step_int4" in m for m in msgs)
    assert schema.check_metric_line(
        dict(ddp, comm_bytes_per_step_int4=23275007), round_n=19,
        errors=[]) == []
    msgs = schema.check_metric_line(
        dict(ddp, comm_bytes_per_step_int4=23275007), round_n=18,
        errors=[])
    assert any("only defined from round 19" in m for m in msgs)
    # other configs never need the kernels fields at round 19
    assert schema.check_metric_line(dict(base, metric="resnet50_amp_o2"),
                                    round_n=19, errors=[]) == []


def test_pp_tp_dp_fields_gated_at_round22():
    """ISSUE 17 satellite: a pp_tp_dp metric line must carry the 1F1B
    bubble fraction next to its analytic model, the schedule shape,
    the baseline-vs-overlapped step times, the per-axis comm dicts
    WITH the pipe axis priced, and the 3-D reshard verdict from round
    22; pre-22 records carrying the pipeline-only fields are flagged,
    other configs never need them."""
    base = {"metric": "pp_tp_dp_steps_per_sec", "value": 46.0,
            "unit": "steps/sec", "vs_baseline": 1.0,
            "tflops_per_sec": 0.0, "mfu": 0.0,
            "comm_bytes_per_step": 35608,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": None, "lint_violations": None,
            "static_comm_bytes_per_step": None,
            "backend": "cpu-mesh"}
    axis = {"data": 35364, "model": 245760, "pipe": 102928}
    full = dict(base, bubble_fraction=0.13, bubble_fraction_model=0.2,
                pipeline_stages=2, microbatches=4,
                baseline_step_ms=24.1, overlapped_step_ms=21.5,
                measured_comm_bytes_per_axis=dict(axis),
                static_comm_bytes_per_axis=dict(axis),
                reshard_bitexact=True)
    assert schema.check_metric_line(dict(full), round_n=22,
                                    errors=[]) == []
    # round 22: every pipeline field is required on pp_tp_dp lines
    msgs = schema.check_metric_line(dict(base), round_n=22, errors=[])
    for key in schema.PP_TP_DP_REQUIRED_FIELDS:
        assert any(key in m for m in msgs)
    # the per-axis dicts must price the pipe axis
    two_axis = {"data": 1, "model": 2}
    msgs = schema.check_metric_line(
        dict(full, measured_comm_bytes_per_axis=two_axis),
        round_n=22, errors=[])
    assert any("must price the 'pipe' axis" in m for m in msgs)
    # nullable (single-device run measures nothing) and typed
    assert schema.check_metric_line(
        dict(full, bubble_fraction=None,
             measured_comm_bytes_per_axis=None), round_n=22,
        errors=[]) == []
    msgs = schema.check_metric_line(
        dict(full, bubble_fraction="small"), round_n=22, errors=[])
    assert any("must be numeric" in m for m in msgs)
    msgs = schema.check_metric_line(
        dict(full, static_comm_bytes_per_axis={"pipe": "many"}),
        round_n=22, errors=[])
    assert any("axis-name" in m for m in msgs)
    # pre-22 checked-in records carrying the pipeline-only fields are
    # flagged — the fields did not exist at capture time
    wrapper = {"n": 21, "cmd": "python bench.py pp_tp_dp", "rc": 0,
               "tail": "", "parsed": dict(full)}
    msgs = schema.check_wrapper(wrapper, errors=[])
    assert any("only defined from round 22" in m for m in msgs)
    assert schema.check_wrapper(
        {"n": 22, "cmd": "c", "rc": 0, "tail": "",
         "parsed": dict(full)}, errors=[]) == []
    # other configs never need the pipeline fields at round 22, and
    # tp_dp lines keep their own (round-20) contract untouched
    assert schema.check_metric_line(dict(base, metric="resnet50_amp_o2"),
                                    round_n=22, errors=[]) == []
    tp = dict(base, metric="tp_dp_steps_per_sec",
              baseline_step_ms=1.0, overlapped_step_ms=0.9,
              measured_comm_bytes_per_axis={"data": 1, "model": 2},
              static_comm_bytes_per_axis={"data": 1, "model": 2},
              reshard_bitexact=True)
    assert schema.check_metric_line(dict(tp), round_n=22,
                                    errors=[]) == []


def test_serve_migrate_fields_gated_at_round23():
    """ISSUE 18 satellite: a serve_migrate metric line must carry the
    KV-state migration contract from round 23 — the short/long-context
    migration wall-times (the flat-cost claim), the fleet handoff byte
    count, the loud checksum-fallback count, and the fleet-wide prefix
    hit rate, all nullable; pre-23 records carrying any of them are
    flagged, other configs never need them."""
    base = {"metric": "serve_migrate_migration_ms", "value": 12.7,
            "unit": "ms", "vs_baseline": 1.0,
            "tflops_per_sec": 0.0, "mfu": 0.0,
            "comm_bytes_per_step": 0,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": None, "lint_violations": None,
            "static_comm_bytes_per_step": None,
            "backend": "cpu-mesh"}
    full = dict(base, migration_ms_short_ctx=14.5,
                migration_ms_long_ctx=12.7, kv_handoff_bytes=131080,
                fallback_reprefills=0, fleet_prefix_hit_rate=0.09)
    assert schema.check_metric_line(dict(full), round_n=23,
                                    errors=[]) == []
    # round 23: every migration field is required on serve_migrate lines
    msgs = schema.check_metric_line(dict(base), round_n=23, errors=[])
    for key in schema.SERVE_MIGRATE_REQUIRED_FIELDS:
        assert any(key in m for m in msgs)
    # nullable (a smoke host that skipped a leg stays honest) and typed
    assert schema.check_metric_line(
        dict(full, fleet_prefix_hit_rate=None,
             migration_ms_long_ctx=None), round_n=23, errors=[]) == []
    msgs = schema.check_metric_line(
        dict(full, kv_handoff_bytes="lots"), round_n=23, errors=[])
    assert any("must be numeric" in m for m in msgs)
    # pre-23 checked-in records carrying the migration-only fields are
    # flagged — the fields did not exist at capture time
    wrapper = {"n": 22, "cmd": "python bench.py serve_migrate",
               "rc": 0, "tail": "", "parsed": dict(full)}
    msgs = schema.check_wrapper(wrapper, errors=[])
    assert any("only defined from round 23" in m for m in msgs)
    assert schema.check_wrapper(
        {"n": 23, "cmd": "c", "rc": 0, "tail": "",
         "parsed": dict(full)}, errors=[]) == []
    # other configs never need the migration fields at round 23, and
    # serve_fleet lines keep their own (round-16) contract untouched
    assert schema.check_metric_line(dict(base, metric="resnet50_amp_o2"),
                                    round_n=23, errors=[]) == []
    fleet = dict(base, metric="serve_fleet_tokens_per_sec",
                 ttft_p99_ms_interactive=1.0, ttft_p99_ms_batch=2.0,
                 rebalance_latency_ms=3.0, replicas_respawned=1)
    assert schema.check_metric_line(dict(fleet), round_n=23,
                                    errors=[]) == []


def test_trace_overhead_fields_gated_at_round24():
    """ISSUE 19 satellite: a trace_overhead metric line must carry the
    causal-tracing contract from round 24 — span_count, the on-vs-off
    overhead percentage, both leg step times, and the disabled-leg
    event count (which must be 0 — the zero-overhead-off proof), all
    nullable; pre-24 records carrying any of them are flagged, other
    configs never need them."""
    base = {"metric": "trace_overhead_step_ms", "value": 11.5,
            "unit": "ms", "vs_baseline": 1.0,
            "tflops_per_sec": 0.01, "mfu": 0.0001,
            "comm_bytes_per_step": 0,
            "measured_comm_bytes_per_step": None,
            "model_flops_per_step_xla": None,
            "peak_hbm_bytes": None, "hbm_headroom_pct": None,
            "compile_count": 1, "lint_violations": None,
            "static_comm_bytes_per_step": None,
            "backend": "cpu-mesh"}
    full = dict(base, span_count=60, tracing_overhead_pct=0.8,
                untraced_step_ms=11.1, traced_step_ms=11.2,
                disabled_leg_events=0)
    assert schema.check_metric_line(dict(full), round_n=24,
                                    errors=[]) == []
    # round 24: every tracing field is required on trace_overhead lines
    msgs = schema.check_metric_line(dict(base), round_n=24, errors=[])
    for key in schema.TRACE_OVERHEAD_REQUIRED_FIELDS:
        assert any(key in m for m in msgs)
    # nullable (a host that skipped a leg stays honest) and typed
    assert schema.check_metric_line(
        dict(full, tracing_overhead_pct=None, untraced_step_ms=None),
        round_n=24, errors=[]) == []
    msgs = schema.check_metric_line(
        dict(full, span_count="many"), round_n=24, errors=[])
    assert any("must be numeric" in m for m in msgs)
    # a nonzero disabled-leg event count is a contract violation, not
    # just a number — the disabled registry recorded something
    msgs = schema.check_metric_line(
        dict(full, disabled_leg_events=3), round_n=24, errors=[])
    assert any("zero-overhead-off" in m for m in msgs)
    # pre-24 checked-in records carrying the tracing-only fields are
    # flagged — the fields did not exist at capture time
    wrapper = {"n": 23, "cmd": "python bench.py trace_overhead",
               "rc": 0, "tail": "", "parsed": dict(full)}
    msgs = schema.check_wrapper(wrapper, errors=[])
    assert any("only defined from round 24" in m for m in msgs)
    assert schema.check_wrapper(
        {"n": 24, "cmd": "c", "rc": 0, "tail": "",
         "parsed": dict(full)}, errors=[]) == []
    # other configs never need the tracing fields at round 24, and
    # serve_migrate lines keep their own (round-23) contract untouched
    assert schema.check_metric_line(dict(base, metric="resnet50_amp_o2"),
                                    round_n=24, errors=[]) == []
    migrate = dict(base, metric="serve_migrate_migration_ms",
                   migration_ms_short_ctx=14.5,
                   migration_ms_long_ctx=12.7, kv_handoff_bytes=131080,
                   fallback_reprefills=0, fleet_prefix_hit_rate=0.09)
    assert schema.check_metric_line(dict(migrate), round_n=24,
                                    errors=[]) == []


def test_live_emit_passes_current_schema(capsys):
    """What bench._emit prints today must satisfy the round-14
    (current) metric-line contract — telemetry + memwatch + lint
    fields included."""
    import bench

    bench._emit("unit_test_metric", 12.5, "things/sec",
                flops_per_step=1e9, steps=10, dt=1.0,
                **bench._comm_fields(n_elements=1000))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert schema.check_metric_line(line, round_n=7, errors=[]) == []
    assert schema.check_metric_line(line, round_n=10, errors=[]) == []
    assert schema.check_metric_line(line, round_n=14, errors=[]) == []
    assert schema.check_metric_line(line, round_n=15, errors=[]) == []
    assert schema.check_metric_line(line, round_n=18, errors=[]) == []
    assert line["backend"] == "cpu-mesh"  # the tests' virtual mesh
    assert line["measured_comm_bytes_per_step"] is None  # none staged
    assert line["peak_hbm_bytes"] is None                # none staged
    assert line["compile_count"] is None                 # none staged
    assert line["lint_violations"] is None               # none staged
    assert line["static_comm_bytes_per_step"] is None    # none staged
    assert "comm_bytes_per_step" in line


def test_live_bench_error_passes_current_schema(capsys):
    import bench

    bench._emit_bench_error("unit test error", "crash")
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert schema.check_metric_line(line, round_n=7, errors=[]) == []


def test_cli_refuses_the_cpu_unless_asked(monkeypatch, capsys):
    """``python bench.py`` measures on a TPU. The CPU mesh is a choice
    the caller states with JAX_PLATFORMS=cpu; without it the CLI exits
    non-zero with a parseable ``no_tpu`` line instead of quietly timing
    whatever backend it found."""
    import bench

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._require_tpu_unless_cpu_asked() is None
    assert capsys.readouterr().out == ""
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as exc:
        bench._require_tpu_unless_cpu_asked()
    assert exc.value.code == 2
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "bench_error" and line["kind"] == "no_tpu"
    assert schema.check_metric_line(line, round_n=7, errors=[]) == []


def test_dryrun_multichip_raises_on_too_few_devices(monkeypatch):
    """No silent switch of platform: asked for more devices than the
    platform in use has, the driver entry raises."""
    import __graft_entry__

    # not the asked-for CPU dry run: neither the device-count flag nor
    # JAX_PLATFORMS=cpu is in the environment of this call
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="need 64 devices, have"):
        __graft_entry__.dryrun_multichip(64)


@pytest.mark.parametrize("bad", [
    {"metric": "m"},                              # missing most keys
    {"metric": "m", "value": True, "unit": "u",   # bool is not numeric
     "vs_baseline": 1.0},
])
def test_metric_line_rejects(bad):
    assert schema.check_metric_line(bad, errors=[]) != []
