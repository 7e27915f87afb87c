#!/usr/bin/env python
"""Validate the repo's checked-in BENCH_*.json capture records and raw
bench.py metric lines against the capture contract.

Two layers of schema:

1. **Wrapper records** (``BENCH_rNN.json``, written by the capture
   driver): ``{"n": int, "cmd": str, "rc": int, "tail": str}`` with an
   optional ``"parsed"`` dict holding the last JSON line bench.py
   printed.
2. **Metric lines** (what ``bench._emit`` / ``_emit_bench_error``
   print): ``metric/value/unit/vs_baseline`` always; successful lines
   additionally carry the roofline (``tflops_per_sec``, ``mfu``) and
   the comm/telemetry accounting.

The contract grew over rounds, so requirements are gated on the round
number ``n`` (old checked-in records stay valid):

- ``n >= 6``: ``comm_bytes_per_step`` must be present in ``parsed``
  (the round-6 capture contract — even on the bench_error path).
- ``n >= 7``: successful metric lines must carry the telemetry fields
  ``measured_comm_bytes_per_step`` and ``model_flops_per_step_xla``
  (nullable — null means "not measured in this config", e.g. a serving
  bench) next to ``mfu``.
- ``n >= 11``: ``serve_decode`` metric lines must carry the serving
  contract — p50/p99 TTFT and per-token latency plus
  ``kv_cache_bytes`` — next to their tokens/sec value.
- ``n >= 12``: ``serve_chaos`` metric lines must carry the serving
  fault-tolerance contract — ``goodput_ratio``, ``shed_rate``,
  ``poisoned_evictions``, ``decode_retries`` and ``ttft_p99_ms`` —
  next to their goodput tokens/sec value.
- ``n >= 13``: ``ddp_recovery`` metric lines must carry the training
  recovery contract — ``restarts``, ``mttr_steps``,
  ``snapshot_restores``, ``goodput_step_ratio`` — next to their
  steps/sec value.
- ``n >= 14``: successful metric lines must carry ``lint_violations``
  (the static HLO lint's finding count over the lowered step —
  apex_tpu.analysis; null means the bench ran without
  ``APEX_TPU_HLO_LINT=1``).
- ``n >= 15``: successful metric lines must carry ``backend`` (the
  backend stamp, ``"cpu-mesh"`` or ``"tpu"`` — which perf
  series the line belongs to), and ``ddp_overlapped`` metric lines
  must carry the overlap contract — ``overlap_segments``,
  ``comm_hidden_pct`` and ``baseline_step_ms`` — next to their
  steps/sec value.
- ``n >= 16``: ``serve_fleet`` metric lines must carry the fleet
  contract — per-tier p99 TTFT (``ttft_p99_ms_interactive`` /
  ``ttft_p99_ms_batch``), ``rebalance_latency_ms`` and
  ``replicas_respawned`` — next to their fleet tokens/sec value.
- ``n >= 17``: ``serve_spec`` metric lines must carry the speculative
  + prefix-cache contract — ``accepted_tokens_per_sec``,
  ``acceptance_rate``, ``prefix_hit_rate`` and
  ``ttft_p50_prefix_hit_ms`` (null when the trace never hit) — next
  to their accepted tokens/sec value.
- ``n >= 18``: successful metric lines must carry
  ``static_comm_bytes_per_step`` (the collective-dataflow-graph wire
  bytes parsed out of the lowered step — apex_tpu.analysis.sharding;
  null means the config measured no step or ran with
  ``APEX_TPU_STATIC_COMM=0``); pre-round-18 records carrying it are
  flagged.
- ``n >= 19``: ``kernels`` metric lines must carry the per-family
  kernel-vs-XLA timings (``<family>_kernel_ms`` / ``<family>_xla_ms``,
  nullable) and ``ddp_compressed`` lines the int4 dual-quantization
  wire model (``comm_bytes_per_step_int4``); pre-round-19 records
  carrying any of them are
  flagged — the field did not exist yet.
- ``n >= 20``: ``tp_dp`` metric lines (the 2-D (data, model) mesh
  composition) must carry ``baseline_step_ms`` /
  ``overlapped_step_ms``, the per-mesh-axis comm-byte split
  (``measured_comm_bytes_per_axis`` / ``static_comm_bytes_per_axis``,
  axis-name -> bytes dicts) and the elastic 2-D reshard verdict
  ``reshard_bitexact``; pre-round-20 records carrying any of them are
  flagged.
- ``n >= 21``: ``fused_cc`` metric lines (the fused
  computation-collective kernels) must carry the per-family
  fused-vs-unfused timings (``fused_cc_<family>_{fused,unfused}_ms``)
  and the HBM-intermediate counts
  (``hbm_intermediates_{unfused,fused}_<family>``); pre-round-21
  records carrying any of them are flagged.
- ``n >= 22``: ``pp_tp_dp`` metric lines (the 3-D pipeline mesh) must
  carry ``bubble_fraction`` / ``bubble_fraction_model``, the schedule
  shape (``pipeline_stages``, ``microbatches``), the step times, the
  per-axis comm dicts WITH the ``pipe`` axis priced, and
  ``reshard_bitexact``; pre-round-22 records carrying the
  pipeline-only fields are flagged.
- ``n >= 23``: ``serve_migrate`` metric lines (KV-state migration)
  must carry ``migration_ms_short_ctx`` / ``migration_ms_long_ctx``
  (the flat-cost claim), ``kv_handoff_bytes``,
  ``fallback_reprefills`` and ``fleet_prefix_hit_rate`` — all
  nullable; pre-round-23 records carrying any of them are flagged.
- ``n >= 24``: ``trace_overhead`` metric lines (causal-tracing tax)
  must carry ``span_count`` / ``tracing_overhead_pct`` (the
  enabled-vs-disabled step-time delta), the two leg step times
  (``untraced_step_ms`` / ``traced_step_ms``) and
  ``disabled_leg_events`` (must aggregate to 0 — the
  zero-overhead-off proof) — all nullable; pre-round-24 records
  carrying any of them are flagged.
- ``n >= 25``: ``monitor_overhead`` metric lines (live-monitoring tax)
  must carry the two leg wall-clocks (``unmonitored_run_s`` /
  ``monitored_run_s``), ``alerts_fired`` (the rule table actually
  evaluated under chaos), ``alerts_firing_final`` (0 on a healthy
  run — everything resolved) and ``disabled_leg_monitor_events``
  (must be 0 — the monitor-plane zero-overhead-off proof) — all
  nullable; pre-round-25 records carrying any of them are flagged.

Usage::

    python tools/bench_schema_check.py            # repo root BENCH_*.json
    python tools/bench_schema_check.py DIR ...    # explicit dirs/files

Exit code 0 = every file valid; 1 = violations (printed one per line).
"""

import glob
import json
import os
import sys

# the round from which the telemetry fields (measured comm bytes + XLA
# flops) became part of the successful-metric-line contract
TELEMETRY_FIELDS_SINCE_ROUND = 7
# the resilience capture contract: steps_skipped (the guard's skipped-
# step count) is an OPTIONAL field defined from round 8 — only the
# guarded configs (ddp_resilience) emit it, old records stay valid
# without it, and a pre-round-8 record carrying it is flagged (the
# field did not exist yet)
STEPS_SKIPPED_SINCE_ROUND = 8
# the numerics capture contract: numerics_overhead_pct (cost of the
# in-graph per-layer stats + flight-recorder ring vs the numerics-off
# step) is an OPTIONAL field defined from round 9 — only ddp_numerics
# emits it; same gating discipline as steps_skipped
NUMERICS_OVERHEAD_SINCE_ROUND = 9
# the compile & memory observability contract: peak_hbm_bytes /
# hbm_headroom_pct (telemetry/memory.py step accounting) and
# compile_count (the step function's trace count — 1 in a shape-stable
# run) are REQUIRED (nullable — null means "not measured in this
# config") on successful metric lines from round 10; BENCH_r01-r06
# records stay valid without them
MEMWATCH_FIELDS_SINCE_ROUND = 10
# the serving capture contract (apex_tpu.serving, round 11): a
# serve_decode metric line must carry the latency percentiles and the
# KV-cache byte accounting next to its tokens/sec value; the fields
# did not exist before round 11, so a pre-round-11 record carrying
# them is flagged — same gating discipline as steps_skipped
SERVE_FIELDS_SINCE_ROUND = 11
SERVE_METRIC_PREFIX = "serve_decode"
SERVE_REQUIRED_FIELDS = ("ttft_p50_ms", "ttft_p99_ms",
                         "tok_latency_p50_ms", "tok_latency_p99_ms",
                         "kv_cache_bytes")
# the serving fault-tolerance contract (apex_tpu.serving.robust, round
# 12): a serve_chaos metric line must carry the chaos accounting —
# goodput ratio vs the clean run, storm shed rate, quarantine/retry
# counts, and the tail latency under fault — next to its goodput
# tokens/sec value; pre-round-12 records carrying them are flagged
SERVE_CHAOS_FIELDS_SINCE_ROUND = 12
SERVE_CHAOS_METRIC_PREFIX = "serve_chaos"
SERVE_CHAOS_REQUIRED_FIELDS = ("goodput_ratio", "shed_rate",
                               "poisoned_evictions", "decode_retries",
                               "ttft_p99_ms")
# the training recovery contract (resilience.supervisor, round 13): a
# ddp_recovery metric line must carry the supervised-chaos accounting —
# restart count, MTTR in steps (snapshot-cadence bound), snapshot
# restores, and the goodput ratio (committed steps over dispatches
# incl. replays); pre-round-13 records carrying them are flagged
RECOVERY_FIELDS_SINCE_ROUND = 13
RECOVERY_METRIC_PREFIX = "ddp_recovery"
RECOVERY_REQUIRED_FIELDS = ("restarts", "mttr_steps",
                            "snapshot_restores", "goodput_step_ratio")
# the static-analysis capture contract (apex_tpu.analysis, round 14):
# lint_violations (findings of the HLO lint pass over the lowered step;
# null = the bench ran without APEX_TPU_HLO_LINT=1) is REQUIRED
# (nullable) on successful metric lines from round 14 — same gating
# discipline as the memwatch fields (bench._emit always writes the
# key, so older-round checks of live lines must tolerate it)
LINT_FIELDS_SINCE_ROUND = 14
# the overlapped-step capture contract (parallel/overlap.py, round 15):
# a ddp_overlapped metric line must carry the measured overlap
# accounting — segment count, the in-invocation bucketed-baseline step
# time, and the % of baseline comm cost hidden — and EVERY successful
# line must carry the backend stamp ("cpu-mesh" |
# "tpu"), the field that makes the CPU-mesh numbers a first-class
# tracked series; pre-round-15 records carrying the overlap fields are
# flagged (they did not exist yet), while `backend` follows the
# lint_violations discipline (bench._emit always writes it, so
# older-round checks of live lines must tolerate it)
OVERLAP_FIELDS_SINCE_ROUND = 15
OVERLAP_METRIC_PREFIX = "ddp_overlapped"
OVERLAP_REQUIRED_FIELDS = ("overlap_segments", "comm_hidden_pct",
                           "baseline_step_ms")
BACKEND_VERDICTS = ("cpu-mesh", "tpu")
# the serving-fleet capture contract (apex_tpu.serving.fleet, round
# 16): a serve_fleet metric line must carry the per-tier tail
# latencies, the quarantine->re-dispatch rebalance latency (null when
# the chaos leg never migrated), and the respawn count next to its
# fleet tokens/sec value; pre-round-16 records carrying them are
# flagged — the fields did not exist yet
FLEET_FIELDS_SINCE_ROUND = 16
FLEET_METRIC_PREFIX = "serve_fleet"
FLEET_REQUIRED_FIELDS = ("ttft_p99_ms_interactive", "ttft_p99_ms_batch",
                         "rebalance_latency_ms", "replicas_respawned")
# the speculative + prefix-cached serving contract (ServeConfig
# draft_model / prefix_cache, round 17): a serve_spec metric line must
# carry the acceptance and prefix-reuse accounting next to its
# accepted tokens/sec value; pre-round-17 records carrying them are
# flagged — the fields did not exist yet
SERVE_SPEC_FIELDS_SINCE_ROUND = 17
SERVE_SPEC_METRIC_PREFIX = "serve_spec"
SERVE_SPEC_REQUIRED_FIELDS = ("accepted_tokens_per_sec",
                              "acceptance_rate", "prefix_hit_rate",
                              "ttft_p50_prefix_hit_ms")
# the SPMD communication-audit contract (apex_tpu.analysis.sharding,
# round 18): static_comm_bytes_per_step (ring-model wire bytes of the
# collective dataflow graph parsed from the lowered step; null = the
# config measured no step) is REQUIRED (nullable) on successful metric
# lines from round 18, cross-validated in-bench against
# measured_comm_bytes_per_step within 25%; a pre-round-18 record
# carrying it is flagged — the field did not exist yet
STATIC_COMM_FIELDS_SINCE_ROUND = 18
# the Pallas kernel-layer contract (apex_tpu.kernels, round 19): a
# kernels metric line carries per-family kernel-vs-XLA timings, and
# ddp_compressed lines carry the int4 dual-quantization wire model
# (comm_bytes_per_step_int4) next to the int8 payload; pre-round-19
# records carrying any of them are flagged — the fields did not exist
KERNELS_FIELDS_SINCE_ROUND = 19
KERNELS_METRIC_PREFIX = "kernels_"
KERNELS_REQUIRED_FIELDS = (
    "softmax_kernel_ms", "softmax_xla_ms",
    "adam_kernel_ms", "adam_xla_ms",
    "lamb_kernel_ms", "lamb_xla_ms",
    "int4_kernel_ms", "int4_xla_ms")
INT4_COMM_FIELD = "comm_bytes_per_step_int4"
DDP_COMPRESSED_METRIC_PREFIX = "ddp_compressed"
# the 2-D mesh composition contract (apex_tpu.parallel.mesh2d, round
# 20): a tp_dp metric line must carry the baseline-vs-overlapped 2-D
# step times, the per-mesh-axis comm-byte split (measured counter
# deltas AND the static collective-graph model, both keyed by axis
# name), and the elastic 2-D ZeRO reshard verdict; pre-round-20
# records carrying any of them are flagged — the fields did not exist
TP_DP_FIELDS_SINCE_ROUND = 20
TP_DP_METRIC_PREFIX = "tp_dp"
TP_DP_NUM_FIELDS = ("baseline_step_ms", "overlapped_step_ms")
TP_DP_AXIS_FIELDS = ("measured_comm_bytes_per_axis",
                     "static_comm_bytes_per_axis")
TP_DP_BOOL_FIELD = "reshard_bitexact"
TP_DP_REQUIRED_FIELDS = (TP_DP_NUM_FIELDS + TP_DP_AXIS_FIELDS
                         + (TP_DP_BOOL_FIELD,))
# the 3-D pipeline-mesh contract (apex_tpu.parallel.pipeline, round
# 22): a pp_tp_dp metric line must carry the measured 1F1B bubble
# fraction next to its analytic model, the schedule shape
# (pipeline_stages, microbatches), the baseline-vs-overlapped step
# times, the per-axis comm-byte dicts WITH the pipe axis priced, and
# the elastic 3-D ZeRO reshard verdict; pre-round-22 records carrying
# the pipeline-only fields are flagged — the fields did not exist
PP_TP_DP_FIELDS_SINCE_ROUND = 22
PP_TP_DP_METRIC_PREFIX = "pp_tp_dp"
PP_TP_DP_NUM_FIELDS = ("bubble_fraction", "bubble_fraction_model",
                       "pipeline_stages", "microbatches",
                       "baseline_step_ms", "overlapped_step_ms")
# presence-gated pre-22: the fields no earlier bench ever emitted
PP_TP_DP_NEW_FIELDS = ("bubble_fraction", "bubble_fraction_model",
                       "pipeline_stages", "microbatches")
PP_TP_DP_PIPE_AXIS = "pipe"
PP_TP_DP_REQUIRED_FIELDS = (PP_TP_DP_NUM_FIELDS + TP_DP_AXIS_FIELDS
                            + (TP_DP_BOOL_FIELD,))
# the KV-state migration contract (apex_tpu.serving.fleet, round 23):
# a serve_migrate metric line must carry the short/long-context
# migration wall-times (the flat-cost claim next to the linear
# re-prefill comparator), the fleet handoff byte count, the loud
# checksum-fallback count, and the fleet-wide prefix hit rate —
# required-nullable so a smoke host that skipped a leg stays honest;
# pre-round-23 records carrying any of them are flagged — the fields
# did not exist
SERVE_MIGRATE_FIELDS_SINCE_ROUND = 23
SERVE_MIGRATE_METRIC_PREFIX = "serve_migrate"
SERVE_MIGRATE_NUM_FIELDS = (
    "migration_ms_short_ctx", "migration_ms_long_ctx",
    "kv_handoff_bytes", "fallback_reprefills",
    "fleet_prefix_hit_rate")
SERVE_MIGRATE_REQUIRED_FIELDS = SERVE_MIGRATE_NUM_FIELDS
# the causal-tracing contract (apex_tpu.telemetry.trace, round 24): a
# trace_overhead metric line must carry the enabled-leg span event
# count, the on-vs-off per-step overhead, both leg step times, and the
# disabled-leg event count (0 on a healthy run — the zero-overhead-off
# contract, measured not assumed) — required-nullable so a host that
# skipped a leg stays honest; pre-round-24 records carrying any of
# them are flagged — the fields did not exist
TRACE_OVERHEAD_FIELDS_SINCE_ROUND = 24
TRACE_OVERHEAD_METRIC_PREFIX = "trace_overhead"
TRACE_OVERHEAD_NUM_FIELDS = (
    "span_count", "tracing_overhead_pct", "untraced_step_ms",
    "traced_step_ms", "disabled_leg_events")
TRACE_OVERHEAD_REQUIRED_FIELDS = TRACE_OVERHEAD_NUM_FIELDS
# the live-monitoring contract (apex_tpu.telemetry.monitor, round 25):
# a monitor_overhead metric line carries both leg wall-clocks, the
# fired-alert count (the rule table actually evaluated under the
# injected replica loss), the final firing count (0 = everything
# resolved after respawn) and the disabled-leg monitor/alert event
# count (0 on a healthy run — a Monitor on a disabled registry must be
# inert, measured not assumed); pre-round-25 records carrying any of
# them are flagged — the fields did not exist
MONITOR_OVERHEAD_FIELDS_SINCE_ROUND = 25
MONITOR_OVERHEAD_METRIC_PREFIX = "monitor_overhead"
MONITOR_OVERHEAD_NUM_FIELDS = (
    "unmonitored_run_s", "monitored_run_s", "alerts_fired",
    "alerts_firing_final", "disabled_leg_monitor_events")
MONITOR_OVERHEAD_REQUIRED_FIELDS = MONITOR_OVERHEAD_NUM_FIELDS
# the fused computation-collective contract (apex_tpu.kernels
# .fused_cc, round 21): a fused_cc metric line carries per-family
# fused-vs-unfused timings plus the traced-jaxpr HBM-intermediate
# counts the bench's strictly-reduced invariant was checked against;
# pre-round-21 records carrying any of them are flagged
FUSED_CC_FIELDS_SINCE_ROUND = 21
FUSED_CC_METRIC_PREFIX = "fused_cc_"
FUSED_CC_REQUIRED_FIELDS = (
    "fused_cc_matmul_psum_fused_ms", "fused_cc_matmul_psum_unfused_ms",
    "fused_cc_verify_fused_ms", "fused_cc_verify_unfused_ms",
    "fused_cc_int4_ring_fused_ms", "fused_cc_int4_ring_unfused_ms",
    "hbm_intermediates_unfused_matmul_psum",
    "hbm_intermediates_fused_matmul_psum",
    "hbm_intermediates_unfused_verify",
    "hbm_intermediates_fused_verify",
    "hbm_intermediates_unfused_int4_ring",
    "hbm_intermediates_fused_int4_ring")
COMM_BYTES_SINCE_ROUND = 6
# bench_error lines grew the kind discriminator in round 3
ERROR_KIND_SINCE_ROUND = 3

_NUM = (int, float)


def _type_ok(value, types):
    # bool is an int subclass; never accept it where a number is meant
    if isinstance(value, bool):
        return bool in types if isinstance(types, tuple) else types is bool
    return isinstance(value, types)


def check_metric_line(obj, *, round_n=None, errors=None, where=""):
    """Validate one bench.py-emitted JSON object (success or
    bench_error). Appends messages to ``errors`` (or raises ValueError
    on the first problem when ``errors`` is None)."""
    own = errors if errors is not None else []

    def bad(msg):
        own.append(f"{where}{msg}")

    for key, types in (("metric", str), ("value", _NUM), ("unit", str),
                       ("vs_baseline", _NUM)):
        if key not in obj:
            bad(f"missing required key {key!r}")
        elif not _type_ok(obj[key], types):
            bad(f"key {key!r} has type {type(obj[key]).__name__}, "
                f"wanted {types}")
    if obj.get("metric") == "bench_error":
        if ((round_n is None or round_n >= ERROR_KIND_SINCE_ROUND)
                and obj.get("kind") not in ("crash", "no_tpu")):
            bad(f"bench_error kind {obj.get('kind')!r} not in "
                f"('crash', 'no_tpu')")
        if (round_n is not None and round_n >= COMM_BYTES_SINCE_ROUND
                and "comm_bytes_per_step" not in obj):
            bad("bench_error missing comm_bytes_per_step "
                f"(required since round {COMM_BYTES_SINCE_ROUND})")
    else:
        for key in ("tflops_per_sec", "mfu"):
            if key not in obj:
                bad(f"successful metric line missing {key!r}")
            elif not _type_ok(obj[key], _NUM):
                bad(f"key {key!r} must be numeric")
        if "comm_bytes_per_step" not in obj:
            bad("successful metric line missing comm_bytes_per_step")
        elif not (obj["comm_bytes_per_step"] is None
                  or _type_ok(obj["comm_bytes_per_step"], _NUM)):
            bad("comm_bytes_per_step must be numeric or null")
        if round_n is None or round_n >= TELEMETRY_FIELDS_SINCE_ROUND:
            for key in ("measured_comm_bytes_per_step",
                        "model_flops_per_step_xla"):
                if key not in obj:
                    bad(f"missing telemetry field {key!r} (required "
                        f"since round {TELEMETRY_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"telemetry field {key!r} must be numeric or "
                        f"null")
        if round_n is None or round_n >= MEMWATCH_FIELDS_SINCE_ROUND:
            for key in ("peak_hbm_bytes", "hbm_headroom_pct",
                        "compile_count"):
                if key not in obj:
                    bad(f"missing memwatch field {key!r} (required "
                        f"since round {MEMWATCH_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"memwatch field {key!r} must be numeric or "
                        f"null")
            cc = obj.get("compile_count")
            if isinstance(cc, (int, float)) and not isinstance(cc, bool) \
                    and cc < 0:
                bad("compile_count must be non-negative")
        if "steps_skipped" in obj:
            if (round_n is not None
                    and round_n < STEPS_SKIPPED_SINCE_ROUND):
                bad(f"steps_skipped is only defined from round "
                    f"{STEPS_SKIPPED_SINCE_ROUND}")
            elif not (obj["steps_skipped"] is None
                      or (_type_ok(obj["steps_skipped"], int)
                          and obj["steps_skipped"] >= 0)):
                bad("steps_skipped must be a non-negative integer or "
                    "null")
        is_serve = str(obj.get("metric", "")).startswith(
            SERVE_METRIC_PREFIX)
        present_serve = [k for k in SERVE_REQUIRED_FIELDS if k in obj]
        if present_serve and (round_n is not None
                              and round_n < SERVE_FIELDS_SINCE_ROUND):
            bad(f"serve fields {present_serve} are only defined from "
                f"round {SERVE_FIELDS_SINCE_ROUND}")
        elif is_serve and (round_n is None
                           or round_n >= SERVE_FIELDS_SINCE_ROUND):
            for key in SERVE_REQUIRED_FIELDS:
                if key not in obj:
                    bad(f"serve_decode line missing {key!r} (required "
                        f"since round {SERVE_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"serve field {key!r} must be numeric or null")
        is_chaos = str(obj.get("metric", "")).startswith(
            SERVE_CHAOS_METRIC_PREFIX)
        # presence-gate only the chaos-specific fields: ttft_p99_ms is
        # shared with the round-11 serve_decode contract
        present_chaos = [k for k in SERVE_CHAOS_REQUIRED_FIELDS
                         if k in obj and k not in SERVE_REQUIRED_FIELDS]
        if present_chaos and (round_n is not None
                              and round_n < SERVE_CHAOS_FIELDS_SINCE_ROUND):
            bad(f"serve_chaos fields {present_chaos} are only defined "
                f"from round {SERVE_CHAOS_FIELDS_SINCE_ROUND}")
        elif is_chaos and (round_n is None
                           or round_n >= SERVE_CHAOS_FIELDS_SINCE_ROUND):
            for key in SERVE_CHAOS_REQUIRED_FIELDS:
                if key not in obj:
                    bad(f"serve_chaos line missing {key!r} (required "
                        f"since round {SERVE_CHAOS_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"serve_chaos field {key!r} must be numeric or "
                        f"null")
        is_recovery = str(obj.get("metric", "")).startswith(
            RECOVERY_METRIC_PREFIX)
        present_recovery = [k for k in RECOVERY_REQUIRED_FIELDS
                            if k in obj]
        if present_recovery and (round_n is not None
                                 and round_n < RECOVERY_FIELDS_SINCE_ROUND):
            bad(f"recovery fields {present_recovery} are only defined "
                f"from round {RECOVERY_FIELDS_SINCE_ROUND}")
        elif is_recovery and (round_n is None
                              or round_n >= RECOVERY_FIELDS_SINCE_ROUND):
            for key in RECOVERY_REQUIRED_FIELDS:
                if key not in obj:
                    bad(f"ddp_recovery line missing {key!r} (required "
                        f"since round {RECOVERY_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"recovery field {key!r} must be numeric or "
                        f"null")
        is_fleet = str(obj.get("metric", "")).startswith(
            FLEET_METRIC_PREFIX)
        present_fleet = [k for k in FLEET_REQUIRED_FIELDS if k in obj]
        if present_fleet and (round_n is not None
                              and round_n < FLEET_FIELDS_SINCE_ROUND):
            bad(f"serve_fleet fields {present_fleet} are only defined "
                f"from round {FLEET_FIELDS_SINCE_ROUND}")
        elif is_fleet and (round_n is None
                           or round_n >= FLEET_FIELDS_SINCE_ROUND):
            for key in FLEET_REQUIRED_FIELDS:
                if key not in obj:
                    bad(f"serve_fleet line missing {key!r} (required "
                        f"since round {FLEET_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"serve_fleet field {key!r} must be numeric or "
                        f"null")
        is_spec = str(obj.get("metric", "")).startswith(
            SERVE_SPEC_METRIC_PREFIX)
        present_spec = [k for k in SERVE_SPEC_REQUIRED_FIELDS
                        if k in obj]
        if present_spec and (round_n is not None
                             and round_n < SERVE_SPEC_FIELDS_SINCE_ROUND):
            bad(f"serve_spec fields {present_spec} are only defined "
                f"from round {SERVE_SPEC_FIELDS_SINCE_ROUND}")
        elif is_spec and (round_n is None
                          or round_n >= SERVE_SPEC_FIELDS_SINCE_ROUND):
            for key in SERVE_SPEC_REQUIRED_FIELDS:
                if key not in obj:
                    bad(f"serve_spec line missing {key!r} (required "
                        f"since round {SERVE_SPEC_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"serve_spec field {key!r} must be numeric or "
                        f"null")
        is_overlap = str(obj.get("metric", "")).startswith(
            OVERLAP_METRIC_PREFIX)
        present_overlap = [k for k in OVERLAP_REQUIRED_FIELDS
                           if k in obj]
        if present_overlap and (round_n is not None
                                and round_n < OVERLAP_FIELDS_SINCE_ROUND):
            bad(f"overlap fields {present_overlap} are only defined "
                f"from round {OVERLAP_FIELDS_SINCE_ROUND}")
        elif is_overlap and (round_n is None
                             or round_n >= OVERLAP_FIELDS_SINCE_ROUND):
            for key in OVERLAP_REQUIRED_FIELDS:
                if key not in obj:
                    bad(f"ddp_overlapped line missing {key!r} (required "
                        f"since round {OVERLAP_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"overlap field {key!r} must be numeric or "
                        f"null")
        if round_n is None or round_n >= OVERLAP_FIELDS_SINCE_ROUND:
            if "backend" not in obj:
                bad(f"missing backend verdict (required since round "
                    f"{OVERLAP_FIELDS_SINCE_ROUND})")
            elif not (obj["backend"] is None
                      or obj["backend"] in BACKEND_VERDICTS):
                bad(f"backend verdict {obj['backend']!r} not in "
                    f"{BACKEND_VERDICTS} (or null)")
        elif "backend" in obj and not (
                obj["backend"] is None
                or obj["backend"] in BACKEND_VERDICTS):
            bad(f"backend verdict {obj['backend']!r} not in "
                f"{BACKEND_VERDICTS} (or null)")
        if round_n is None or round_n >= LINT_FIELDS_SINCE_ROUND:
            if "lint_violations" not in obj:
                bad(f"missing lint field 'lint_violations' (required "
                    f"since round {LINT_FIELDS_SINCE_ROUND})")
            elif not (obj["lint_violations"] is None
                      or (_type_ok(obj["lint_violations"], int)
                          and obj["lint_violations"] >= 0)):
                bad("lint_violations must be a non-negative integer "
                    "or null")
        # bench._emit always writes the key (null when unmeasured), so
        # LIVE lines checked against older rounds tolerate it — same
        # discipline as lint_violations/backend; the presence flag for
        # pre-18 CHECKED-IN records lives in check_wrapper, where the
        # capture round is authoritative
        if round_n is None or \
                round_n >= STATIC_COMM_FIELDS_SINCE_ROUND:
            if "static_comm_bytes_per_step" not in obj:
                bad(f"missing static comm field "
                    f"'static_comm_bytes_per_step' (required since "
                    f"round {STATIC_COMM_FIELDS_SINCE_ROUND})")
            elif not (obj["static_comm_bytes_per_step"] is None
                      or (_type_ok(obj["static_comm_bytes_per_step"],
                                   _NUM)
                          and obj["static_comm_bytes_per_step"] >= 0)):
                bad("static_comm_bytes_per_step must be a non-negative "
                    "number or null")
        is_kernels = str(obj.get("metric", "")).startswith(
            KERNELS_METRIC_PREFIX)
        present_kernels = [k for k in KERNELS_REQUIRED_FIELDS if k in obj]
        if present_kernels and (round_n is not None
                                and round_n < KERNELS_FIELDS_SINCE_ROUND):
            bad(f"kernels fields {present_kernels} are only defined "
                f"from round {KERNELS_FIELDS_SINCE_ROUND}")
        elif is_kernels and (round_n is None
                             or round_n >= KERNELS_FIELDS_SINCE_ROUND):
            for key in KERNELS_REQUIRED_FIELDS:
                if key not in obj:
                    bad(f"kernels line missing {key!r} (required since "
                        f"round {KERNELS_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"kernels field {key!r} must be numeric or "
                        f"null")
        is_fused_cc = str(obj.get("metric", "")).startswith(
            FUSED_CC_METRIC_PREFIX)
        present_fused = [k for k in FUSED_CC_REQUIRED_FIELDS if k in obj]
        if present_fused and (round_n is not None
                              and round_n < FUSED_CC_FIELDS_SINCE_ROUND):
            bad(f"fused_cc fields {present_fused} are only defined "
                f"from round {FUSED_CC_FIELDS_SINCE_ROUND}")
        elif is_fused_cc and (round_n is None
                              or round_n >= FUSED_CC_FIELDS_SINCE_ROUND):
            for key in FUSED_CC_REQUIRED_FIELDS:
                if key not in obj:
                    bad(f"fused_cc line missing {key!r} (required "
                        f"since round {FUSED_CC_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"fused_cc field {key!r} must be numeric or "
                        f"null")
        is_ddp_compressed = str(obj.get("metric", "")).startswith(
            DDP_COMPRESSED_METRIC_PREFIX)
        if INT4_COMM_FIELD in obj and (
                round_n is not None
                and round_n < KERNELS_FIELDS_SINCE_ROUND):
            bad(f"{INT4_COMM_FIELD} is only defined from round "
                f"{KERNELS_FIELDS_SINCE_ROUND}")
        elif is_ddp_compressed and (
                round_n is None
                or round_n >= KERNELS_FIELDS_SINCE_ROUND):
            if INT4_COMM_FIELD not in obj:
                bad(f"ddp_compressed line missing {INT4_COMM_FIELD!r} "
                    f"(required since round "
                    f"{KERNELS_FIELDS_SINCE_ROUND})")
            elif not (obj[INT4_COMM_FIELD] is None
                      or _type_ok(obj[INT4_COMM_FIELD], _NUM)):
                bad(f"{INT4_COMM_FIELD} must be numeric or null")
        is_tp_dp = str(obj.get("metric", "")).startswith(
            TP_DP_METRIC_PREFIX)
        # presence-gate only the round-20-new per-axis dicts:
        # baseline/overlapped_step_ms ride ddp_overlapped lines since
        # round 15 and reshard_bitexact rides ddp_recovery since 13
        present_tp_dp = [k for k in TP_DP_AXIS_FIELDS if k in obj]
        if present_tp_dp and (round_n is not None
                              and round_n < TP_DP_FIELDS_SINCE_ROUND):
            bad(f"tp_dp fields {present_tp_dp} are only defined from "
                f"round {TP_DP_FIELDS_SINCE_ROUND}")
        elif is_tp_dp and (round_n is None
                           or round_n >= TP_DP_FIELDS_SINCE_ROUND):
            for key in TP_DP_NUM_FIELDS:
                if key not in obj:
                    bad(f"tp_dp line missing {key!r} (required since "
                        f"round {TP_DP_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"tp_dp field {key!r} must be numeric or null")
            for key in TP_DP_AXIS_FIELDS:
                if key not in obj:
                    bad(f"tp_dp line missing {key!r} (required since "
                        f"round {TP_DP_FIELDS_SINCE_ROUND})")
                elif obj[key] is not None and not (
                        isinstance(obj[key], dict)
                        and all(isinstance(k, str)
                                and (v is None or _type_ok(v, _NUM))
                                for k, v in obj[key].items())):
                    bad(f"tp_dp field {key!r} must be an axis-name -> "
                        f"bytes dict or null")
            if TP_DP_BOOL_FIELD not in obj:
                bad(f"tp_dp line missing {TP_DP_BOOL_FIELD!r} "
                    f"(required since round {TP_DP_FIELDS_SINCE_ROUND})")
            elif not (obj[TP_DP_BOOL_FIELD] is None
                      or isinstance(obj[TP_DP_BOOL_FIELD], bool)):
                bad(f"{TP_DP_BOOL_FIELD} must be a boolean or null")
        is_pp_tp_dp = str(obj.get("metric", "")).startswith(
            PP_TP_DP_METRIC_PREFIX)
        present_pp = [k for k in PP_TP_DP_NEW_FIELDS if k in obj]
        if present_pp and (round_n is not None
                           and round_n < PP_TP_DP_FIELDS_SINCE_ROUND):
            bad(f"pp_tp_dp fields {present_pp} are only defined from "
                f"round {PP_TP_DP_FIELDS_SINCE_ROUND}")
        elif is_pp_tp_dp and (round_n is None
                              or round_n >= PP_TP_DP_FIELDS_SINCE_ROUND):
            for key in PP_TP_DP_NUM_FIELDS:
                if key not in obj:
                    bad(f"pp_tp_dp line missing {key!r} (required "
                        f"since round {PP_TP_DP_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"pp_tp_dp field {key!r} must be numeric or "
                        f"null")
            for key in TP_DP_AXIS_FIELDS:
                if key not in obj:
                    bad(f"pp_tp_dp line missing {key!r} (required "
                        f"since round {PP_TP_DP_FIELDS_SINCE_ROUND})")
                elif obj[key] is not None and not (
                        isinstance(obj[key], dict)
                        and all(isinstance(k, str)
                                and (v is None or _type_ok(v, _NUM))
                                for k, v in obj[key].items())):
                    bad(f"pp_tp_dp field {key!r} must be an axis-name "
                        f"-> bytes dict or null")
                elif (isinstance(obj[key], dict)
                      and PP_TP_DP_PIPE_AXIS not in obj[key]):
                    bad(f"pp_tp_dp field {key!r} must price the "
                        f"{PP_TP_DP_PIPE_AXIS!r} axis")
            if TP_DP_BOOL_FIELD not in obj:
                bad(f"pp_tp_dp line missing {TP_DP_BOOL_FIELD!r} "
                    f"(required since round "
                    f"{PP_TP_DP_FIELDS_SINCE_ROUND})")
            elif not (obj[TP_DP_BOOL_FIELD] is None
                      or isinstance(obj[TP_DP_BOOL_FIELD], bool)):
                bad(f"{TP_DP_BOOL_FIELD} must be a boolean or null")
        is_migrate = str(obj.get("metric", "")).startswith(
            SERVE_MIGRATE_METRIC_PREFIX)
        present_mig = [k for k in SERVE_MIGRATE_NUM_FIELDS if k in obj]
        if present_mig and (round_n is not None
                            and round_n
                            < SERVE_MIGRATE_FIELDS_SINCE_ROUND):
            bad(f"serve_migrate fields {present_mig} are only defined "
                f"from round {SERVE_MIGRATE_FIELDS_SINCE_ROUND}")
        elif is_migrate and (round_n is None
                             or round_n
                             >= SERVE_MIGRATE_FIELDS_SINCE_ROUND):
            for key in SERVE_MIGRATE_NUM_FIELDS:
                if key not in obj:
                    bad(f"serve_migrate line missing {key!r} (required "
                        f"since round "
                        f"{SERVE_MIGRATE_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None or _type_ok(obj[key], _NUM)):
                    bad(f"serve_migrate field {key!r} must be numeric "
                        f"or null")
        is_trace = str(obj.get("metric", "")).startswith(
            TRACE_OVERHEAD_METRIC_PREFIX)
        present_tr = [k for k in TRACE_OVERHEAD_NUM_FIELDS if k in obj]
        if present_tr and (round_n is not None
                           and round_n
                           < TRACE_OVERHEAD_FIELDS_SINCE_ROUND):
            bad(f"trace_overhead fields {present_tr} are only defined "
                f"from round {TRACE_OVERHEAD_FIELDS_SINCE_ROUND}")
        elif is_trace and (round_n is None
                           or round_n
                           >= TRACE_OVERHEAD_FIELDS_SINCE_ROUND):
            for key in TRACE_OVERHEAD_NUM_FIELDS:
                if key not in obj:
                    bad(f"trace_overhead line missing {key!r} "
                        f"(required since round "
                        f"{TRACE_OVERHEAD_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None
                          or _type_ok(obj[key], _NUM)):
                    bad(f"trace_overhead field {key!r} must be "
                        f"numeric or null")
            if _type_ok(obj.get("disabled_leg_events"), _NUM) \
                    and obj["disabled_leg_events"] != 0:
                bad(f"trace_overhead disabled_leg_events = "
                    f"{obj['disabled_leg_events']} — the disabled "
                    f"registry recorded events (zero-overhead-off "
                    f"contract broken)")
        is_monitor = str(obj.get("metric", "")).startswith(
            MONITOR_OVERHEAD_METRIC_PREFIX)
        present_mon = [k for k in MONITOR_OVERHEAD_NUM_FIELDS
                       if k in obj]
        if present_mon and (round_n is not None
                            and round_n
                            < MONITOR_OVERHEAD_FIELDS_SINCE_ROUND):
            bad(f"monitor_overhead fields {present_mon} are only "
                f"defined from round "
                f"{MONITOR_OVERHEAD_FIELDS_SINCE_ROUND}")
        elif is_monitor and (round_n is None
                             or round_n
                             >= MONITOR_OVERHEAD_FIELDS_SINCE_ROUND):
            for key in MONITOR_OVERHEAD_NUM_FIELDS:
                if key not in obj:
                    bad(f"monitor_overhead line missing {key!r} "
                        f"(required since round "
                        f"{MONITOR_OVERHEAD_FIELDS_SINCE_ROUND})")
                elif not (obj[key] is None
                          or _type_ok(obj[key], _NUM)):
                    bad(f"monitor_overhead field {key!r} must be "
                        f"numeric or null")
            if _type_ok(obj.get("disabled_leg_monitor_events"), _NUM) \
                    and obj["disabled_leg_monitor_events"] != 0:
                bad(f"monitor_overhead disabled_leg_monitor_events = "
                    f"{obj['disabled_leg_monitor_events']} — the "
                    f"disabled leg saw monitor-plane events "
                    f"(zero-overhead-off contract broken)")
        if "numerics_overhead_pct" in obj:
            if (round_n is not None
                    and round_n < NUMERICS_OVERHEAD_SINCE_ROUND):
                bad(f"numerics_overhead_pct is only defined from round "
                    f"{NUMERICS_OVERHEAD_SINCE_ROUND}")
            elif not (obj["numerics_overhead_pct"] is None
                      or _type_ok(obj["numerics_overhead_pct"], _NUM)):
                bad("numerics_overhead_pct must be numeric or null")
    if errors is None and own:
        raise ValueError("; ".join(own))
    return own


def check_wrapper(obj, *, errors=None, where=""):
    """Validate one BENCH_rNN.json capture-wrapper record."""
    own = errors if errors is not None else []

    def bad(msg):
        own.append(f"{where}{msg}")

    for key, types in (("n", int), ("cmd", str), ("rc", int),
                       ("tail", str)):
        if key not in obj:
            bad(f"missing required key {key!r}")
        elif not _type_ok(obj[key], types):
            bad(f"key {key!r} has type {type(obj[key]).__name__}, "
                f"wanted {types.__name__}")
    parsed = obj.get("parsed")
    if parsed is not None:
        if not isinstance(parsed, dict):
            bad("'parsed' must be a dict when present")
        else:
            n = obj.get("n")
            # a record CAPTURED before round 18 cannot carry a measured
            # static_comm_bytes_per_step — the field did not exist yet
            # (live lines are exempt: bench._emit always writes the
            # key, null when unmeasured)
            if isinstance(n, int) \
                    and n < STATIC_COMM_FIELDS_SINCE_ROUND \
                    and parsed.get("static_comm_bytes_per_step") \
                    is not None:
                bad(f"parsed: static_comm_bytes_per_step is only "
                    f"defined from round "
                    f"{STATIC_COMM_FIELDS_SINCE_ROUND}")
            check_metric_line(parsed, round_n=n, errors=own,
                              where=where + "parsed: ")
    elif obj.get("rc") == 0:
        bad("rc == 0 but no parsed metric line")
    if errors is None and own:
        raise ValueError("; ".join(own))
    return own


def check_file(path, errors):
    where = f"{os.path.basename(path)}: "
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as e:
        errors.append(f"{where}unreadable/invalid JSON ({e})")
        return
    if not isinstance(obj, dict):
        errors.append(f"{where}top level must be a JSON object")
        return
    if "metric" in obj and "n" not in obj:
        check_metric_line(obj, errors=errors, where=where)
    else:
        check_wrapper(obj, errors=errors, where=where)


def collect_paths(args):
    if not args:
        args = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
    paths = []
    for a in args:
        if os.path.isdir(a):
            paths.extend(sorted(glob.glob(os.path.join(a, "BENCH_*.json"))))
        else:
            paths.append(a)
    return paths


def main(argv=None):
    paths = collect_paths(list(argv if argv is not None else sys.argv[1:]))
    if not paths:
        print("bench_schema_check: no BENCH_*.json files found")
        return 1
    errors = []
    for path in paths:
        check_file(path, errors)
    for e in errors:
        print(f"SCHEMA ERROR {e}")
    print(f"bench_schema_check: {len(paths)} file(s), "
          f"{len(errors)} violation(s)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
