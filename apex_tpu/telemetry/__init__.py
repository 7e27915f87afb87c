"""apex_tpu.telemetry — unified tracing, metrics, and XLA cost accounting.

The observability layer under the parallel/optimizer/bench stack:

- :mod:`registry`  — process-wide counters/gauges/histograms + a JSONL
  event sink under ``$APEX_TPU_TELEMETRY_DIR`` (rank-aware).
- :mod:`trace`     — named :func:`span` context managers (optional
  device-sync fencing, nested under ``jax.profiler.TraceAnnotation`` /
  ``jax.named_scope``), causal identity (:class:`TraceContext` on a
  contextvar; spans emit begin/end events carrying
  trace/span/parent ids — the substrate ``tools/trace_export.py``
  turns into a Perfetto-loadable Chrome trace).
- :mod:`scopes`    — device time by module:
  :func:`~apex_tpu.telemetry.scopes.scope_table` reads, from a compiled
  step, the program scope of every instruction a device profile names,
  and :func:`~apex_tpu.telemetry.scopes.classify` folds it into a block
  (``attention``, ``optimizer``, ``amp``, ...) and a phase (forward,
  backward, recompute, update).
- :mod:`xla_cost`  — ``lower().cost_analysis()`` extraction for a
  jitted step + achieved MFU / HBM-utilization against a per-backend
  peak table.
- :mod:`comm`      — measured collective accounting (per-call payload
  dtype/bytes from ``_psum_with_policy`` and the compression paths),
  the measured counterpart to ``compression.estimate_allreduce_bytes``.
- :mod:`numerics`  — jit-native per-layer gradient/activation stats
  (:func:`~apex_tpu.telemetry.numerics.tensor_stats` /
  :func:`~apex_tpu.telemetry.numerics.tree_stats`): norms, zero
  fraction, non-finite counts, fp16/bf16 under/overflow fractions —
  computed entirely in-graph.
- :mod:`recorder`  — :class:`~apex_tpu.telemetry.recorder.FlightRecorder`,
  a device-side ring buffer of the last K steps' stats, fetched once
  for a ``numerics-postmortem-rank<N>.json`` when the resilience guard
  trips.
- :mod:`monitor`   — the live control plane
  (:class:`~apex_tpu.telemetry.monitor.Monitor`): rolling windows over
  registry snapshots + tailed cross-rank JSONL, a declarative
  :class:`~apex_tpu.telemetry.monitor.AlertRule` table with
  firing/resolved ``alert`` events, OpenMetrics exposition
  (:func:`~apex_tpu.telemetry.monitor.render_openmetrics`, scrape
  endpoint gated by ``APEX_TPU_MONITOR_PORT``), and the
  ``tools/monitor_dash.py`` terminal view.
- :mod:`attribution` — online 3-D-mesh attribution
  (:class:`~apex_tpu.telemetry.attribution.PipelineAttributor`):
  exposure-difference straggler detection over ``pp_tick_<t>`` spans,
  measured vs analytic bubble fraction, per-axis exposed-comm split.
- :mod:`compile_watch` — the compile path. Always on: one
  ``jax.monitoring`` listener (jax 0.9) behind a bounded record of every
  trace, lowering and backend compile or cache load, and of the
  package's own import, on the ``perf_counter`` clock
  (:func:`~apex_tpu.telemetry.compile_watch.phase_records`,
  :func:`~apex_tpu.telemetry.compile_watch.phase_table`,
  :func:`~apex_tpu.telemetry.compile_watch.process_start_perf`), with
  ``compile/*`` and ``compile_cache/*`` counters and ``compile/trace``
  / ``compile/lower`` / ``compile/backend`` spans when the registry is
  enabled: where set-up's seconds go. Opt-in via
  ``APEX_TPU_COMPILE_WATCH=1``: trace/compile accounting per jitted
  function (:class:`~apex_tpu.telemetry.compile_watch.CompileWatcher`),
  ``compile`` events that name exactly which argument changed on a
  recompile. And the
  :func:`~apex_tpu.telemetry.compile_watch.assert_no_recompiles`
  test primitive.
- :mod:`memory`    — HBM budget accounting:
  :func:`~apex_tpu.telemetry.memory.step_memory` (XLA
  ``memory_analysis()`` -> peak bytes + ``memory/hbm_headroom``
  gauge), :func:`~apex_tpu.telemetry.memory.live_buffer_census`,
  :func:`~apex_tpu.telemetry.memory.preflight`, and the
  ``memory-postmortem-rank<N>.json`` OOM handler
  (:func:`~apex_tpu.telemetry.memory.oom_guard`).

Everything is host-side: recording inside jitted code happens at trace
time (once per compilation == once per step of the compiled program)
and never inserts callbacks into compiled programs. Disabled — the
default, when ``APEX_TPU_TELEMETRY_DIR`` is unset and nothing called
``enable()`` — every instrument is a shared no-op.

Quickstart (docs/observability.md has the full tour)::

    APEX_TPU_TELEMETRY_DIR=/tmp/tel python bench.py ddp_compressed
    python tools/telemetry_report.py /tmp/tel
"""

from apex_tpu.telemetry.registry import (  # noqa: F401
    MetricsRegistry,
    get_registry,
    set_registry,
    use_registry,
)
from apex_tpu.telemetry.trace import (  # noqa: F401
    Span,
    TraceContext,
    current_trace,
    device_sync,
    emit_flow,
    emit_span,
    new_span_id,
    new_trace_id,
    span,
    trace_context,
)
from apex_tpu.telemetry import comm  # noqa: F401
from apex_tpu.telemetry import compile_watch  # noqa: F401
from apex_tpu.telemetry import memory  # noqa: F401
from apex_tpu.telemetry import numerics  # noqa: F401
from apex_tpu.telemetry import recorder  # noqa: F401
from apex_tpu.telemetry import scopes  # noqa: F401
from apex_tpu.telemetry import xla_cost  # noqa: F401
from apex_tpu.telemetry.attribution import (  # noqa: F401
    PipelineAttributor,
)
from apex_tpu.telemetry.compile_watch import (  # noqa: F401
    CompileWatcher,
    RecompileError,
    assert_no_recompiles,
)
from apex_tpu.telemetry.monitor import (  # noqa: F401
    AlertRule,
    JsonlTailer,
    Monitor,
    default_rules,
    parse_openmetrics,
    render_openmetrics,
)
from apex_tpu.telemetry.memory import (  # noqa: F401
    HBMExhaustedError,
    MemoryBudgetError,
    live_buffer_census,
    oom_guard,
    oom_postmortem,
    preflight,
    step_memory,
)
from apex_tpu.telemetry.numerics import (  # noqa: F401
    TensorStats,
    tensor_stats,
    tree_stats,
)
from apex_tpu.telemetry.recorder import (  # noqa: F401
    FlightRecorder,
    RecorderState,
)
