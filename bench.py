"""Benchmark: the two headline metrics from BASELINE.json.

    python bench.py [batch] [steps]        ResNet-50 amp O2 + FusedAdam
                                           imgs/sec/chip  (default; the
                                           driver runs this form)
    python bench.py bert [batch] [steps]   BERT-large FusedLAMB
                                           samples/sec/chip
    python bench.py gpt [seq] [steps]      long-context GPT (16x1024,
                                           flash attention) tokens/sec/chip
    python bench.py gpt2 [batch] [steps]   GPT-2 345M tokens/sec/chip + MFU
                                           (flags: APEX_TPU_GPT2_FLASH=0,
                                           APEX_TPU_GPT2_SCAN=1)
    python bench.py moe [batch] [steps]    MoE GPT (8 experts top-1, every
                                           other layer) tokens/sec/chip
    python bench.py moe_serve [seq] [steps] dropless Mixtral-shaped MoE
                                           forward at seq>=2048 (ragged
                                           dispatch) tokens/sec/chip
    python bench.py mla_decode [prefix] [steps] MLA latent-cache decode at
                                           long prefix: Pallas kernel vs
                                           einsum tokens/sec/chip
    python bench.py llama [batch] [steps]  Llama-style GPT (RoPE + GQA +
                                           SwiGLU + RMSNorm) tokens/sec/chip
    python bench.py decode [batch] [new]   KV-cache decode throughput
                                           (serving) tokens/sec/chip
    python bench.py serve_decode [reqs] [len]  continuous-batching serve
    python bench.py serve_spec [reqs] [len]  speculative + prefix-cached serve
                                           engine (apex_tpu.serving):
                                           AOT bucket ladder, two
                                           Poisson traces, tokens/sec +
                                           p50/p99 TTFT/latency +
                                           kv_cache_bytes (bf16 + int8)
                                           + flat compile_count
    python bench.py serve_chaos [reqs] [len]  serving fault-tolerance
    python bench.py serve_fleet [reqs] [len]  multi-replica fleet chaos
                                           chaos: injected slot-NaN +
                                           transient decode failure +
                                           request storm through one
                                           engine; emits goodput_ratio,
                                           shed_rate, poisoned
                                           evictions, p99 — compile
                                           count still the ladder
    python bench.py ddp_compressed [batch] [steps]  DDP step with int8
                                           block-quantized grad
                                           collectives + error feedback;
                                           emits comm_bytes_per_step
                                           (int8 vs fp32)
    python bench.py ddp_overlapped [batch] [steps]  overlapped
                                           backward/collective DDP step
                                           (per-bucket int8 psum
                                           emitted mid-backward) vs the
                                           ddp_compressed bucketed
                                           baseline at identical comm
                                           bytes; emits
                                           baseline_step_ms /
                                           comm_hidden_pct /
                                           overlap_segments
    python bench.py tp_dp [batch] [steps]  2-D (data, model) mesh
                                           composition: GPT-2
                                           column/row-parallel blocks,
                                           int8 DP compression scoped
                                           to the data axis, baseline
                                           vs overlapped step at
                                           identical comm bytes; emits
                                           per-axis comm bytes +
                                           reshard_bitexact
    python bench.py pp_tp_dp [batch] [steps]  3-D (data, model, pipe)
                                           mesh: stage-partitioned
                                           GPT-2 under the host-driven
                                           1F1B schedule, DP bucket
                                           psums in the cooldown
                                           bubbles; emits
                                           bubble_fraction (vs the
                                           (pp-1)/(m+pp-1) model),
                                           per-axis comm bytes incl.
                                           pipe, 3-D reshard_bitexact
    python bench.py ddp_numerics [batch] [steps]  guarded DDP step with
                                           in-graph per-layer stats +
                                           flight-recorder ring; emits
                                           numerics_overhead_pct vs the
                                           numerics-off step
    python bench.py monitor_overhead [reqs] [len]  live-monitoring tax:
                                           the fleet chaos leg run
                                           unmonitored (disabled
                                           registry — asserts ZERO
                                           monitor/alert events) vs
                                           monitored (stock rule table
                                           tapped in, 20 ms poll loop);
                                           emits monitor_overhead_pct /
                                           alerts_fired /
                                           alerts_firing_final /
                                           disabled_leg_monitor_events
    python bench.py ddp_memwatch [batch] [steps]  guarded DDP step under
                                           the compile watcher + HBM
                                           accounting (+ optional
                                           injected alloc failure ->
                                           memory post-mortem); emits
                                           peak_hbm_bytes /
                                           hbm_headroom_pct /
                                           compile_count

The reference publishes no numbers (BASELINE.md), so ``vs_baseline`` is
reported as 1.0 by convention until a measured baseline lands in
BASELINE.json; the honest absolute metric is the roofline: every bench
also reports achieved ``tflops_per_sec`` (model FLOPs / step time, PaLM
appendix-B convention — 6N per token plus 12*L*h*s attention, no causal
discount) and ``mfu`` = achieved / the published peak of the device the bench
ran on (``telemetry.xla_cost.peak_table``, keyed by ``device_kind``; an
unknown device is an error).

``python bench.py ...`` exits non-zero when JAX finds no TPU. Setting
``JAX_PLATFORMS=cpu`` asks for the virtual CPU mesh instead; every line
of such a run is stamped ``"backend": "cpu-mesh"`` and none of its
timings is a device metric.

Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "tflops_per_sec", "mfu",
"measured_comm_bytes_per_step", "static_comm_bytes_per_step",
"model_flops_per_step_xla"} — static is the collective-dataflow-graph
wire-byte total parsed from the lowered step
(apex_tpu.analysis.sharding); when the step's collectives are
instrumented the bench FAILS on >25% static-vs-measured disagreement
(APEX_TPU_COMM_GATE=0 disables).

Telemetry (apex_tpu.telemetry, docs/observability.md): the bench opts
the registry in so every line carries the measured per-step collective
bytes (comm-counter delta around one trace of the step — compare with
the modeled ``comm_bytes_per_step``) and XLA's own FLOP count for the
step (``lower().cost_analysis()`` — no extra compile). Set
APEX_TPU_TELEMETRY_DIR to also get the JSONL event stream (step spans,
per-collective payloads, the cost_analysis-derived mfu gauge); read it
with tools/telemetry_report.py.
"""

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _emit_bench_error(error, kind):
    """The one bench_error emission point — a parseable line for
    whoever reads stdout. ``kind`` is ``"crash"`` (the bench raised) or
    ``"no_tpu"`` (the CLI found no TPU and was not asked for the CPU
    mesh). ``comm_bytes_per_step`` rides along even here (the round-6
    capture contract: the comm-bytes field must appear in every BENCH
    JSON) — it carries the last estimate the dying bench computed, or
    null before model init."""
    print(json.dumps({
        "metric": "bench_error", "value": 0, "unit": "error",
        "vs_baseline": 0.0, "kind": kind, "error": error,
        "comm_bytes_per_step": _LAST_COMM_BYTES,
        "backend": _BACKEND,
    }), flush=True)


# last comm-bytes estimate computed by any bench in this process; the
# bench_error path reports it so a crash after model init still records
# the comm accounting for the config that died
_LAST_COMM_BYTES = None


def _tree_size(params):
    return int(sum(np.prod(l.shape)
                   for l in jax.tree_util.tree_leaves(params)))


def _comm_fields(params=None, *, compress=None, n_elements=None,
                 training=True):
    """Estimated per-step gradient-sync bytes for the emitted JSON.

    Single-chip captures have no live collectives, so this models the
    DP allreduce the config would run at scale: a ring over
    APEX_TPU_COMM_WORLD replicas (default 8) moving one gradient set of
    the model's parameter count per step, at the wire width selected by
    ``compress`` (see compression.estimate_allreduce_bytes — int8
    counts the EQuARX-style quantized payload). Serving benches pass
    ``training=False`` and report 0 — no grad sync exists to compress.
    """
    global _LAST_COMM_BYTES
    from apex_tpu.parallel import compression

    if not training:
        fields = {"comm_bytes_per_step": 0,
                  "comm_model": "none (serving: no grad sync)"}
        _LAST_COMM_BYTES = 0
        return fields
    n = _tree_size(params) if n_elements is None else int(n_elements)
    world = int(os.environ.get("APEX_TPU_COMM_WORLD", "8"))
    fields = {
        "comm_bytes_per_step": compression.estimate_allreduce_bytes(
            n, world=world, compress=compress),
        "comm_model": f"ring allreduce, dp={world}, "
                      f"payload={compress or 'fp32'}",
    }
    _LAST_COMM_BYTES = fields["comm_bytes_per_step"]
    return fields


def _peak_tflops():
    """Published bf16 peak of the device this process runs on, in
    TFLOP/s (one table, keyed by ``device_kind``)."""
    from apex_tpu.telemetry.xla_cost import peak_table

    return peak_table()[0] / 1e12


# Per-layer activation recompute re-executes the forward during backward
# (~25-30% of step FLOPs). The short-sequence train benches (bert seq
# 128, llama/moe/gpt2 seq 1024 at small batch) fit HBM without it, so
# they default it OFF; the long-context bench keeps it. Set
# APEX_TPU_BENCH_REMAT=1 to force recompute back on everywhere (e.g. if
# a capture OOMs).
BENCH_REMAT = os.environ.get("APEX_TPU_BENCH_REMAT", "0") == "1"


def _transformer_fwd_flops_per_token(cfg, seq):
    """Forward model-FLOPs per token: 2 FLOPs per matmul parameter
    touched (qkv/out/ffn/vocab head; MoE counts top_k experts only)
    plus the 4*s*h*L attention matmuls (PaLM MFU convention: full
    matmul, no causal discount)."""
    h, L = cfg.hidden_size, cfg.num_layers
    ffn = cfg.ffn_hidden_size or 4 * h
    heads = cfg.num_attention_heads
    groups = cfg.num_query_groups or heads
    kv_h = h * groups // heads
    attn_params = h * h + 2 * h * kv_h + h * h  # q, k+v, out projections
    ffn_mults = 3 if cfg.activation == "swiglu" else 2
    dense_ffn = ffn_mults * h * ffn
    if cfg.num_moe_experts:
        # layers 0, freq, 2*freq, ... are MoE -> ceil(L / freq) of them
        moe_layers = -(-L // cfg.moe_layer_freq)
        moe_ffn = cfg.moe_top_k * dense_ffn + h * cfg.num_moe_experts
        ffn_total = moe_layers * moe_ffn + (L - moe_layers) * dense_ffn
    else:
        ffn_total = L * dense_ffn
    matmul_params = L * attn_params + ffn_total + h * cfg.vocab_size
    return 2 * matmul_params + 4 * seq * h * L


def _enable_bench_telemetry():
    """Opt the process-wide registry in for the bench run: in-memory
    collection always (so ``measured_comm_bytes_per_step`` appears in
    the emitted JSON even without a sink), JSONL events too when
    APEX_TPU_TELEMETRY_DIR is set. Library defaults stay off — this is
    the bench's explicit opt-in."""
    from apex_tpu import telemetry

    telemetry.get_registry().enable(
        jsonl_dir=os.environ.get("APEX_TPU_TELEMETRY_DIR") or None)


# per-bench measured fields staged by _measure_step_cost / consumed
# (and cleared) by _emit, so a bench that skips measurement emits nulls
# instead of a stale predecessor's numbers
_PENDING_MEASURED = {}


def _measure_step_cost(jitted, args):
    """One extra host-side trace of the step (``.lower()`` — no second
    compile) with the telemetry comm counters delta'd around it: the
    measured per-step collective bytes plus XLA's own FLOP/byte count
    for the step. Called BEFORE the first real invocation so donated
    buffers are still live. Returns its findings and stages them for
    the next _emit.

    The same lowering also feeds the HBM accounting
    (``telemetry.memory.report_from_lowered`` — argument/output/temp
    bytes, peak, headroom vs the backend's capacity). That step DOES
    compile the lowered program; with the persistent compile cache
    (default-on for bench runs) the jit call that follows is then a
    disk hit, so the total compile cost stays ~1x. Set
    APEX_TPU_BENCH_MEMWATCH=0 to skip it (e.g. cache off + a 25-minute
    model)."""
    from apex_tpu import telemetry

    _enable_bench_telemetry()
    reg = telemetry.get_registry()
    before = reg.counter_value("comm/bytes")
    try:
        lowered = jitted.lower(*args)
    except Exception:
        lowered = None
    measured = reg.counter_value("comm/bytes") - before
    cost = (telemetry.xla_cost.cost_from_lowered(lowered)
            if lowered is not None else None)
    mem = None
    if lowered is not None and \
            os.environ.get("APEX_TPU_BENCH_MEMWATCH", "1") != "0":
        mem = telemetry.memory.report_from_lowered(lowered)
    static_comm = None
    if lowered is not None and \
            os.environ.get("APEX_TPU_STATIC_COMM", "1") != "0":
        # the round-18 capture contract: parse the SAME lowering's
        # StableHLO into the collective dataflow graph
        # (apex_tpu.analysis.sharding) and stamp the static ring-model
        # wire bytes next to the trace-measured counter delta — the
        # static-vs-dynamic cross-validation no single layer provides.
        # Parser crash -> null (an analyzer bug must not kill a bench);
        # a real DISAGREEMENT fails loudly below.
        try:
            from apex_tpu.analysis import sharding as _sharding

            static_comm = _sharding.static_comm_bytes(lowered.as_text())
        except Exception:
            static_comm = None
    if static_comm is not None and measured > 0 and \
            os.environ.get("APEX_TPU_COMM_GATE", "1") != "0":
        # static and measured model the same semantic wire format
        # (int8 emulation counted at 1 byte/elem on both sides), so
        # divergence beyond the band means one of them is lying —
        # fail the bench rather than emit a number nobody can trust.
        # Gate only when collectives were instrumented (measured > 0):
        # un-instrumented TP/MoE psums legitimately show static-only
        # bytes, and that asymmetry is the lint's job, not this gate's.
        tol = float(os.environ.get("APEX_TPU_COMM_GATE_TOL", "0.25"))
        rel = abs(static_comm - measured) / measured
        if rel > tol:
            raise RuntimeError(
                f"static/measured comm-bytes disagreement: static "
                f"{static_comm} vs measured {int(round(measured))} "
                f"({rel * 100.0:.1f}% > {tol * 100.0:.0f}% band) — "
                f"the collective structure of the lowered step is not "
                f"what the instrumentation thinks it is")
    lint_count = None
    if lowered is not None and \
            os.environ.get("APEX_TPU_HLO_LINT", "") not in ("", "0"):
        # the round-14 capture contract: lint the lowered step against
        # the hot-path invariants (apex_tpu.analysis) and carry the
        # violation count in the emitted JSON; findings land as `lint`
        # JSONL events. Opt-in (as_text on a big on-chip model is not
        # free), so the field stays null when unset.
        try:
            from apex_tpu import analysis

            report = analysis.report_to_registry(
                analysis.lint_lowered(lowered, name="bench/step"),
                registry=reg)
            lint_count = len(report.findings)
        except Exception:
            lint_count = None
    _PENDING_MEASURED.clear()
    _PENDING_MEASURED.update({
        "measured_comm_bytes_per_step": int(round(measured)),
        "model_flops_per_step_xla": cost["flops"] if cost else None,
        "_xla_cost": cost,
        "peak_hbm_bytes": mem["peak_bytes"] if mem else None,
        "hbm_headroom_pct": round(mem["headroom_frac"] * 100.0, 2)
        if mem and mem.get("headroom_frac") is not None else None,
        "lint_violations": lint_count,
        "static_comm_bytes_per_step": static_comm,
    })
    return cost, measured


def _stage_compile_count(jitted):
    """Stage the step function's trace/compile count (the pjit cache
    size — 1 in a shape-stable run) for the next _emit. Call AFTER the
    timed loop so any mid-run retrace is counted."""
    try:
        _PENDING_MEASURED["compile_count"] = int(jitted._cache_size())
    except Exception:
        pass


def _stage_aot_compile_count(n):
    """Stage an explicit compile count for AOT-compiled configs
    (serve_decode, the decode scan): ``lower().compile()`` executables
    never populate the pjit call cache, so ``_stage_compile_count``
    would report 0 where the honest number is the bucket-ladder size."""
    _PENDING_MEASURED["compile_count"] = int(n)


def _emit(metric, value, unit, flops_per_step, steps, dt, **extra):
    from apex_tpu import telemetry

    tflops = flops_per_step * steps / dt / 1e12
    measured = _PENDING_MEASURED.pop("measured_comm_bytes_per_step", None)
    flops_xla = _PENDING_MEASURED.pop("model_flops_per_step_xla", None)
    xla_cost = _PENDING_MEASURED.pop("_xla_cost", None)
    peak_hbm = _PENDING_MEASURED.pop("peak_hbm_bytes", None)
    headroom_pct = _PENDING_MEASURED.pop("hbm_headroom_pct", None)
    compile_count = _PENDING_MEASURED.pop("compile_count", None)
    lint_violations = _PENDING_MEASURED.pop("lint_violations", None)
    static_comm = _PENDING_MEASURED.pop("static_comm_bytes_per_step",
                                        None)
    _PENDING_MEASURED.clear()
    reg = telemetry.get_registry()
    if reg.enabled:
        reg.gauge(f"bench/{metric}").set(value)
        reg.gauge("tflops_per_sec").set(tflops)
        # the mfu gauge from the analytic model; overwritten below by
        # the cost_analysis()-derived value when one was measured
        reg.gauge("mfu").set(tflops / _peak_tflops())
        telemetry.xla_cost.record_step_cost(xla_cost, dt / max(steps, 1),
                                            registry=reg)
        reg.event("bench", metric, value=round(value, 2), unit=unit,
                  steps=steps, seconds=round(dt, 4))
        reg.flush()
    print(json.dumps({
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": 1.0,
        # the reference publishes no numbers (SURVEY.md §6), so
        # vs_baseline is 1.0 BY CONVENTION, not a measurement — the
        # honest comparator is the roofline below
        "vs_baseline_basis": "convention: reference publishes no numbers; "
                             "see mfu",
        "tflops_per_sec": round(tflops, 2),
        "mfu": round(tflops / _peak_tflops(), 4),
        # which series this line belongs to (round-15 capture
        # contract): "tpu", or "cpu-mesh" for a run that asked for the
        # virtual CPU mesh — whose timings are not device metrics
        "backend": _backend_verdict(),
        "measured_comm_bytes_per_step": measured,
        "model_flops_per_step_xla": flops_xla,
        # HBM + compile accounting (round-10 capture contract;
        # telemetry/memory.py + telemetry/compile_watch.py): null when
        # the config measured neither
        "peak_hbm_bytes": peak_hbm,
        "hbm_headroom_pct": headroom_pct,
        "compile_count": compile_count,
        # static HLO lint (round-14 capture contract; apex_tpu.analysis):
        # null unless the bench ran with APEX_TPU_HLO_LINT=1
        "lint_violations": lint_violations,
        # static collective-graph wire bytes for the lowered step
        # (round-18 capture contract; apex_tpu.analysis.sharding) —
        # cross-validated in-bench against measured_comm_bytes_per_step
        # within 25%; null when the config measured no step
        "static_comm_bytes_per_step": static_comm,
        **extra,
    }))


def _time_steps(train_step, state, steps, loss_index):
    """Warm up (compile + one steady step), then time `steps` chained
    steps. Each boundary is a host fetch of the loss — data-dependent on
    the whole step chain, so it waits for the device to finish it.
    Returns (elapsed_seconds, final_out).

    Also the telemetry hook: before the first call (donated buffers
    still live) one ``.lower()`` trace measures the step's collective
    bytes and XLA cost (:func:`_measure_step_cost`), and the timed loop
    runs under host-side spans (``bench/step`` per dispatch,
    ``bench/timed_loop`` around loop + completion barrier)."""
    from apex_tpu.telemetry import span

    _measure_step_cost(train_step, state)
    out = train_step(*state)
    float(out[loss_index])
    out = train_step(*out[:loss_index])
    float(out[loss_index])
    with span("bench/timed_loop", steps=steps):
        t0 = time.perf_counter()
        for _ in range(steps):
            with span("bench/step"):
                out = train_step(*out[:loss_index])
        float(out[loss_index])
        dt = time.perf_counter() - t0
    _stage_compile_count(train_step)
    return dt, out


def bench_bert(batch, steps):
    """BERT-large (24x1024, 16 heads, seq 128) MLM+NSP with FusedLAMB —
    BASELINE.json metric 2 / config 4 (FusedLAMB + FusedLayerNorm)."""
    from apex_tpu.models import BertModel, TransformerConfig, bert_loss_fn
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.enums import AttnMaskType

    parallel_state.destroy_model_parallel()
    seq = 128
    cfg = TransformerConfig(
        hidden_size=1024, num_layers=24, num_attention_heads=16,
        vocab_size=30528, max_position_embeddings=512,
        compute_dtype=jnp.bfloat16, use_flash_attention=False,
        attn_mask_type=AttnMaskType.padding,
        activation_checkpointing=BENCH_REMAT)
    model = BertModel(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    padding_mask = jnp.ones((batch, seq), jnp.int32)
    tokentype = jnp.zeros((batch, seq), jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    loss_mask = jnp.asarray(
        (rng.rand(batch, seq) < 0.15).astype(np.float32))
    nsp_labels = jnp.asarray(rng.randint(0, 2, (batch,)))

    params = model.init(jax.random.PRNGKey(0), tokens, padding_mask,
                        tokentype)
    opt = FusedLAMB(lr=1e-3, weight_decay=0.01)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state):
        def loss_fn(p):
            mlm, nsp = model.apply(p, tokens, padding_mask, tokentype)
            return bert_loss_fn(mlm, nsp, labels, loss_mask, nsp_labels)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_opt_state, loss

    dt, _ = _time_steps(train_step, (params, opt_state), steps,
                        loss_index=2)
    flops = 3 * batch * seq * _transformer_fwd_flops_per_token(cfg, seq)
    _emit("bert_large_fused_lamb_samples_per_sec_per_chip",
          batch * steps / dt, "samples/sec", flops, steps, dt,
          **_comm_fields(params))


def bench_gpt_long(seq, steps):
    """Long-context GPT (16 layers x 1024, flash attention) — the
    capability beyond the reference (its long-context story is SP only;
    SURVEY.md §5). Numbers in PERF.md."""
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    cfg = TransformerConfig(
        hidden_size=1024, num_layers=16, num_attention_heads=16,
        vocab_size=32000, max_position_embeddings=seq,
        compute_dtype=jnp.bfloat16, use_flash_attention=True)
    model = GPTModel(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, seq)))
    params = model.init(jax.random.PRNGKey(0), tokens)
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state):
        def loss_fn(p):
            logp = jax.nn.log_softmax(
                model.apply(p, tokens).astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                                 -1))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_opt_state, loss

    dt, _ = _time_steps(train_step, (params, opt_state), steps,
                        loss_index=2)
    flops = 3 * seq * _transformer_fwd_flops_per_token(cfg, seq)
    _emit(f"gpt_long_context_seq{seq}_tokens_per_sec_per_chip",
          seq * steps / dt, "tokens/sec", flops, steps, dt,
          **_comm_fields(params))


def bench_llama(batch, steps):
    """Llama-style GPT (16 layers x 1024, RoPE + GQA 4 groups + SwiGLU +
    RMSNorm, flash attention, scan_layers) single-chip training
    throughput — the modern-LLM architecture knobs end to end."""
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.models.gpt import gpt_loss_fn
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    seq = 1024
    cfg = TransformerConfig(
        hidden_size=1024, num_layers=16, num_attention_heads=16,
        vocab_size=32000, max_position_embeddings=seq,
        compute_dtype=jnp.bfloat16, use_flash_attention=True,
        normalization="rmsnorm", position_embedding_type="rope",
        activation="swiglu", num_query_groups=4,
        ffn_hidden_size=2816,  # ~8/3 * h, llama sizing
        scan_layers=True, activation_checkpointing=BENCH_REMAT)
    model = GPTModel(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state):
        loss, grads = jax.value_and_grad(
            lambda p: gpt_loss_fn(model.apply({"params": p}, tokens),
                                  labels))(params)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_opt_state, loss

    dt, _ = _time_steps(train_step, (params, opt_state), steps,
                        loss_index=2)
    flops = 3 * batch * seq * _transformer_fwd_flops_per_token(cfg, seq)
    _emit("llama_style_gpt_tokens_per_sec_per_chip",
          batch * seq * steps / dt, "tokens/sec", flops, steps, dt,
          **_comm_fields(params))


def bench_decode(batch, steps):
    """KV-cache decode throughput (tokens/sec) on the llama-style config:
    prefill 128 tokens, then timed single-token steps through the jitted
    scan — the serving-shaped metric."""
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    cfg = TransformerConfig(
        hidden_size=1024, num_layers=16, num_attention_heads=16,
        vocab_size=32000, max_position_embeddings=2048,
        compute_dtype=jnp.bfloat16, use_flash_attention=False,
        normalization="rmsnorm", position_embedding_type="rope",
        activation="swiglu", num_query_groups=4, ffn_hidden_size=2816)
    model = GPTModel(cfg, decode=True)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, 128)))
    params = GPTModel(cfg).init(jax.random.PRNGKey(0), prompt)["params"]

    # AOT-compile the prefill + decode-scan pair once
    # (lower().compile()), then run the timed pass against the compiled
    # executables. The old warmup called generate() twice — paying a
    # full un-timed prefill + steps-token scan EXECUTION just to warm
    # the jit cache; compiling ahead of time warms without running.
    from apex_tpu.models import generation

    plen = prompt.shape[1]
    prefill_fn, decode_all = generation._compiled(
        model, plen, steps, 0.0, None, None, None, 0)
    cache = generation.init_cache(model, batch, prompt.dtype)
    init = (cache, jnp.zeros((batch, cfg.vocab_size), jnp.float32),
            jnp.asarray(plen, jnp.int32), jax.random.PRNGKey(0),
            jnp.zeros((batch,), bool))
    _measure_step_cost(decode_all, (params, init))
    pre_exec = prefill_fn.lower(params, cache, prompt).compile()
    dec_exec = decode_all.lower(params, init).compile()
    _stage_aot_compile_count(2)

    cache, last = pre_exec(params, cache, prompt)
    jax.block_until_ready(last)
    t0 = time.perf_counter()
    _, out = dec_exec(params, (cache, last, jnp.asarray(plen, jnp.int32),
                               jax.random.PRNGKey(0),
                               jnp.zeros((batch,), bool)))
    int(out[-1, 0])  # host fetch = completion barrier
    dt = time.perf_counter() - t0
    # fwd-only; attention reads an average KV length of prefill + half
    # the generated span (the timed window is the decode scan — the
    # serving hot loop; prefill is compiled but untimed)
    flops = batch * steps * _transformer_fwd_flops_per_token(
        cfg, plen + steps // 2)
    _emit("llama_style_decode_tokens_per_sec_per_chip",
          batch * steps / dt, "tokens/sec", flops, 1, dt,
          **_comm_fields(training=False))


def bench_gpt2(batch, steps, *, flash=None, scan=None, remat=None,
               loss="vocab_ce", tiny=False, emit=True):
    """GPT-2 345M (24x1024, 16 heads, vocab 50304, seq 1024) single-chip
    training throughput + MFU — the flagship tokens/sec target
    (BASELINE.json config 5 model at tp=1; VERDICT r1 item 6 asks this
    MFU pushed toward >=0.5). Also the engine for tools/mfu_sweep.py
    (kwargs override the env-default knobs; ``tiny`` is the CPU smoke
    config). Per-layer activation recompute defaults OFF here — 345M at
    batch 8 fits HBM, and remat re-executes the whole forward in
    backward (~25-30% of step FLOPs); set APEX_TPU_GPT2_REMAT=1 if a
    memory-limited config needs it back.
    """
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.models.gpt import gpt_loss_fn
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state

    if flash is None:
        flash = os.environ.get("APEX_TPU_GPT2_FLASH", "1") == "1"
    if scan is None:
        scan = os.environ.get("APEX_TPU_GPT2_SCAN", "0") == "1"
    if remat is None:
        remat = (os.environ.get("APEX_TPU_GPT2_REMAT", "0") == "1"
                 or BENCH_REMAT)
    parallel_state.destroy_model_parallel()
    seq = 64 if tiny else 1024
    cfg = TransformerConfig(
        hidden_size=64 if tiny else 1024,
        num_layers=2 if tiny else 24,
        num_attention_heads=4 if tiny else 16,
        vocab_size=256 if tiny else 50304,
        max_position_embeddings=seq,
        compute_dtype=jnp.bfloat16,
        use_flash_attention=flash and not tiny,
        scan_layers=scan,
        activation_checkpointing=remat)
    model = GPTModel(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)

    if loss == "xent":
        from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

        def loss_fn(p):
            logits = model.apply({"params": p}, tokens)
            return jnp.mean(softmax_cross_entropy_loss(
                logits.reshape(-1, cfg.vocab_size), labels.reshape(-1),
                padding_idx=None, half_to_float=True))
    else:
        def loss_fn(p):
            return gpt_loss_fn(model.apply({"params": p}, tokens), labels)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state):
        loss_v, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_opt_state, loss_v

    dt, _ = _time_steps(train_step, (params, opt_state), steps,
                        loss_index=2)
    flops = 3 * batch * seq * _transformer_fwd_flops_per_token(cfg, seq)
    tflops = flops * steps / dt / 1e12
    result = {
        "tokens_per_sec": round(batch * seq * steps / dt, 1),
        "ms_per_step": round(dt / steps * 1e3, 2),
        "tflops_per_sec": round(tflops, 2),
        "mfu": round(tflops / _peak_tflops(), 4),
        "measured_comm_bytes_per_step":
            _PENDING_MEASURED.get("measured_comm_bytes_per_step"),
        "model_flops_per_step_xla":
            _PENDING_MEASURED.get("model_flops_per_step_xla"),
    }
    if emit:
        _emit("gpt2_345m_tokens_per_sec_per_chip",
              batch * seq * steps / dt, "tokens/sec", flops, steps, dt,
              **_comm_fields(params))
    else:
        # emit=False variants consume their staging here: a later bench
        # that measures nothing must emit nulls, not this config's stale
        # numbers
        _PENDING_MEASURED.clear()
    return result


def bench_t5(batch, steps):
    """T5-base encoder-decoder (12+12 x 768, relative-position buckets)
    single-chip training throughput — the encoder_and_decoder model
    family the reference's split-rank pipeline machinery exists for."""
    from apex_tpu.models import T5Config, T5Model, t5_loss_fn
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    enc_s = dec_s = 512
    cfg = T5Config(
        vocab_size=32128, d_model=768, d_kv=64, d_ff=3072,
        num_layers=12, num_decoder_layers=12, num_heads=12,
        compute_dtype=jnp.bfloat16,
        activation_checkpointing=BENCH_REMAT)
    model = T5Model(cfg)
    rng = np.random.RandomState(0)
    enc = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, enc_s)))
    dec = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, dec_s)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, dec_s)))
    params = model.init(jax.random.PRNGKey(0), enc, dec)["params"]
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state):
        def loss_fn(p):
            return t5_loss_fn(
                model.apply({"params": p}, enc, dec), labels)

        loss_v, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_opt_state, loss_v

    dt, _ = _time_steps(train_step, (params, opt_state), steps,
                        loss_index=2)
    # fwd model FLOPs (2 / matmul param touched + attention matmuls):
    h, inner, ffn = cfg.d_model, cfg.inner_dim, cfg.d_ff
    enc_layer = 4 * h * inner + 2 * h * ffn          # qkvo + ffn params
    dec_layer = 8 * h * inner + 2 * h * ffn          # self + cross + ffn
    fwd = (batch * enc_s * (cfg.num_layers * (2 * enc_layer
                                              + 4 * enc_s * inner))
           + batch * dec_s * (cfg.decoder_layers * (2 * dec_layer
                                                    + 4 * dec_s * inner
                                                    + 4 * enc_s * inner)
                              + 2 * h * cfg.vocab_size))
    flops = 3 * fwd  # train = fwd + bwd (2x)
    total_tokens = batch * (enc_s + dec_s)
    _emit("t5_base_tokens_per_sec_per_chip",
          total_tokens * steps / dt, "tokens/sec", flops, steps, dt,
          **_comm_fields(params))


def bench_whisper(batch, steps):
    """Whisper-base-shaped (6+6 x 512, mel 80, 30 s audio = 3000 frames)
    single-chip training throughput — the audio family; the conv
    frontend and both stacks ride the MXU."""
    from apex_tpu.models import WhisperConfig, WhisperModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    dec_s = 256
    cfg = WhisperConfig(compute_dtype=jnp.bfloat16, d_model=512,
                        encoder_layers=6, decoder_layers=6, num_heads=8,
                        encoder_ffn_dim=2048, decoder_ffn_dim=2048)
    model = WhisperModel(cfg)
    rng = np.random.RandomState(0)
    feats = jnp.asarray(rng.randn(
        batch, cfg.num_mel_bins,
        2 * cfg.max_source_positions).astype(np.float32))
    dec = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, dec_s)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, dec_s)))
    params = model.init(jax.random.PRNGKey(0), feats[:1], dec[:1])["params"]
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state):
        def loss_fn(p):
            logits = model.apply({"params": p}, feats, dec)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(
                jnp.take_along_axis(logp, labels[..., None], -1))

        loss_v, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_opt_state, loss_v

    dt, _ = _time_steps(train_step, (params, opt_state), steps,
                        loss_index=2)
    h = cfg.d_model
    enc_s = cfg.max_source_positions
    enc_layer = 4 * h * h + 2 * h * cfg.encoder_ffn_dim
    dec_layer = 8 * h * h + 2 * h * cfg.decoder_ffn_dim
    fwd = (batch * enc_s * (cfg.encoder_layers * (2 * enc_layer
                                                  + 4 * enc_s * h))
           + batch * dec_s * (cfg.decoder_layers * (2 * dec_layer
                                                    + 4 * dec_s * h
                                                    + 4 * enc_s * h)
                              + 2 * h * cfg.vocab_size)
           + batch * 2 * enc_s * 2 * (3 * cfg.num_mel_bins * h
                                      + 3 * h * h) // 2)
    _emit("whisper_base_audio_seconds_per_sec_per_chip",
          batch * 30.0 * steps / dt, "audio_s/sec", 3 * fwd, steps, dt,
          **_comm_fields(params))


def bench_vit(batch, steps):
    """ViT-base/16 @ 224 single-chip training throughput (the vision
    family on the parallel transformer stack; patches feed the MXU as
    one [b,196+1,768] bidirectional stack)."""
    from apex_tpu.models import ViTModel, vit_config, vit_loss_fn
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    cfg = vit_config(hidden_size=768, num_layers=12, num_heads=12,
                     ffn_hidden_size=3072,
                     activation_checkpointing=BENCH_REMAT)
    model = ViTModel(cfg, image_size=224, patch_size=16, num_classes=1000)
    rng = np.random.RandomState(0)
    imgs = jnp.asarray(rng.randn(batch, 224, 224, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1000, size=(batch,)))
    params = model.init(jax.random.PRNGKey(0), imgs[:2])["params"]
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state):
        loss_v, grads = jax.value_and_grad(
            lambda p: vit_loss_fn(model.apply({"params": p}, imgs),
                                  labels))(params)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_opt_state, loss_v

    dt, _ = _time_steps(train_step, (params, opt_state), steps,
                        loss_index=2)
    # fwd FLOPs: patch conv + 12 blocks on seq 197 + classifier
    s, h, ffn = 197, cfg.hidden_size, cfg.ffn_size
    per_tok = cfg.num_layers * (2 * (4 * h * h + 2 * h * ffn)
                                + 4 * s * h)
    patch = 2 * (16 * 16 * 3) * h  # per patch position
    fwd = batch * (s * per_tok + (s - 1) * patch + 2 * h * 1000)
    _emit("vit_base_imgs_per_sec_per_chip", batch * steps / dt,
          "imgs/sec", 3 * fwd, steps, dt, **_comm_fields(params))


def bench_moe(batch, steps):
    """MoE GPT (16 layers x 1024, 8 experts top-1, seq 1024) single-chip
    training throughput — the expert-parallel capability beyond the
    reference; grouped expert FFNs ride the MXU as batched einsums."""
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.moe import moe_loss_from_variables

    parallel_state.destroy_model_parallel()
    seq = 1024
    cfg = TransformerConfig(
        hidden_size=1024, num_layers=16, num_attention_heads=16,
        vocab_size=32000, max_position_embeddings=seq,
        compute_dtype=jnp.bfloat16, use_flash_attention=True,
        num_moe_experts=8, moe_layer_freq=2, moe_capacity_factor=1.25,
        activation_checkpointing=BENCH_REMAT)
    model = GPTModel(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state):
        def loss_fn(p):
            logits, mut = model.apply({"params": p}, tokens,
                                      mutable=["moe_losses"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
            return ce + moe_loss_from_variables(mut, cfg.moe_aux_loss_coeff,
                                                cfg.moe_z_loss_coeff)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_opt_state, loss

    dt, _ = _time_steps(train_step, (params, opt_state), steps,
                        loss_index=2)
    flops = 3 * batch * seq * _transformer_fwd_flops_per_token(cfg, seq)
    _emit("gpt_moe_8expert_tokens_per_sec_per_chip",
          batch * seq * steps / dt, "tokens/sec", flops, steps, dt,
          **_comm_fields(params))


def bench_moe_serve(seq, steps):
    """Dropless MoE serving forward (Mixtral-shaped: 8 experts top-2,
    SwiGLU, renormalized gates) at real sequence length — the ragged
    grouped-matmul dispatch (lax.ragged_dot, zero capacity padding).
    VERDICT r4 item 3: the dense one-hot dispatch was O(T^2 E) at
    dropless capacity; this path is linear in tokens. The emitted line
    carries ``dispatch_flops_ratio``: per-token HLO flops at seq vs
    seq/2 from XLA cost analysis (~1.0 = linear; the einsum path
    measures ~2x)."""
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    E, k = 8, 2
    # APEX_TPU_MOE_SERVE_SMOKE=1: toy dims so the 1-core CPU host can
    # exercise the exact code path pre-capture (the on-chip run uses the
    # real shape)
    smoke = os.environ.get("APEX_TPU_MOE_SERVE_SMOKE") == "1"
    cfg = TransformerConfig(
        hidden_size=64 if smoke else 1024,
        num_layers=2 if smoke else 8,
        num_attention_heads=4 if smoke else 16,
        vocab_size=512 if smoke else 32000,
        max_position_embeddings=seq,
        compute_dtype=jnp.bfloat16, use_flash_attention=not smoke,
        activation="swiglu", num_query_groups=4 if smoke else 8,
        position_embedding_type="rope", normalization="rmsnorm",
        num_moe_experts=E, moe_top_k=k, moe_layer_freq=1,
        moe_capacity_factor=float(E) / k,  # dropless -> ragged dispatch
        activation_checkpointing=False)
    model = GPTModel(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, seq)))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    @jax.jit
    def fwd(tokens):
        return model.apply({"params": params}, tokens)

    def per_token_flops(s):
        toks = jnp.zeros((1, s), jnp.int32)
        c = jax.jit(fwd).lower(toks).compile().cost_analysis()
        an = c if isinstance(c, dict) else c[0]
        return an["flops"] / s

    ratio = per_token_flops(seq) / per_token_flops(seq // 2)

    # PR-5 staging (round-10 capture contract): measured comm bytes
    # (0 — forward only), XLA flops, peak HBM / headroom for the
    # serving forward, and the pjit cache size after the timed loop
    _measure_step_cost(fwd, (tokens,))

    # serving loop: logits of the last position act as the barrier
    out = fwd(tokens)
    float(out[0, -1, 0])
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fwd(tokens)
    float(out[0, -1, 0])
    dt = time.perf_counter() - t0
    _stage_compile_count(fwd)
    flops = seq * _transformer_fwd_flops_per_token(cfg, seq)
    _emit("moe_dropless_serve_tokens_per_sec_per_chip",
          seq * steps / dt, "tokens/sec", flops, steps, dt,
          seq=seq, dispatch_flops_ratio=round(float(ratio), 3),
          **_comm_fields(training=False))


def bench_mla_decode(prefix, steps):
    """MLA latent-cache decode at long prefix (DeepSeek-V2-Lite-shaped
    attention: 16 heads, kv latent 512 + rope 64, absorbed projections).
    Times single-token steps twice — streaming Pallas kernel
    (contrib/mla_decode.py) vs the XLA einsum formulation — and reports
    the kernel's tokens/sec with ``einsum_tokens_per_sec``/``speedup``
    alongside (VERDICT r4 item 4: the cache-size win was demonstrated,
    this measures the speed win)."""
    from apex_tpu.models.mla import DeepseekModel, MLAConfig
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    batch = 8
    max_len = -(-(prefix + steps + 2) // 512) * 512
    cfg = MLAConfig(
        vocab_size=32000, hidden_size=1024, num_layers=4, num_heads=16,
        q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, ffn_hidden_size=2816,
        max_decode_length=max_len, compute_dtype=jnp.bfloat16)
    model = DeepseekModel(cfg)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, prefix)))
    params = model.init(jax.random.PRNGKey(0), prompt[:, :8])["params"]

    def run_variant(flash):
        # the kernel/einsum choice is a trace-time branch: fresh jitted
        # callables per variant get their own cache entries
        if flash:
            os.environ.pop("APEX_TPU_KERNELS", None)
        else:
            os.environ["APEX_TPU_KERNELS"] = "0"

        @jax.jit
        def prefill(params, prompt):
            logits, var = model.apply({"params": params}, prompt,
                                      mode="prefill", mutable=["cache"])
            return jnp.argmax(logits[:, -1:], -1), var["cache"]

        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(params, cache, tok):
            logits, var = model.apply({"params": params, "cache": cache},
                                      tok, mode="step", mutable=["cache"])
            return jnp.argmax(logits[:, -1:], -1), var["cache"]

        tok, cache = prefill(params, prompt)
        if flash:
            # PR-5 staging for the headline (kernel) variant: one
            # lower() BEFORE the first step call — donation is live
            _measure_step_cost(step, (params, cache, tok))
        tok, cache = step(params, cache, tok)  # compile + warm
        int(tok[0, 0])
        t0 = time.perf_counter()
        for _ in range(steps):
            tok, cache = step(params, cache, tok)
        int(tok[0, 0])  # host fetch = completion barrier
        dt = time.perf_counter() - t0
        if flash:
            _stage_compile_count(step)
        return dt

    dt_einsum = run_variant(False)
    dt_flash = run_variant(True)
    os.environ.pop("APEX_TPU_KERNELS", None)

    # fwd flops/token: projections + absorbed attention over the mean
    # live prefix + swiglu + head (rough; the roofline here is HBM —
    # the cache stream — not the MXU)
    h, n = cfg.hidden_size, cfg.num_heads
    lat, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    L_row = lat + rope
    t_avg = prefix + steps // 2
    per_layer = 2 * (h * n * cfg.qk_head_dim + h * L_row
                     + n * cfg.qk_nope_head_dim * lat   # q absorb
                     + n * L_row * t_avg                # scores
                     + n * lat * t_avg                  # combine
                     + n * lat * cfg.v_head_dim         # W_v expand
                     + n * cfg.v_head_dim * h
                     + 3 * h * cfg.ffn_hidden_size)
    flops = batch * steps * (cfg.num_layers * per_layer
                             + 2 * h * cfg.vocab_size)
    _emit("mla_latent_decode_tokens_per_sec_per_chip",
          batch * steps / dt_flash, "tokens/sec", flops, 1, dt_flash,
          prefix=prefix,
          einsum_tokens_per_sec=round(batch * steps / dt_einsum, 2),
          speedup=round(dt_einsum / dt_flash, 3),
          **_comm_fields(training=False))


# the backend stamp ("tpu" | "cpu-mesh"), cached once per process and
# written into every emitted JSON line
_BACKEND = None


def _backend_verdict():
    """``"cpu-mesh"`` when every jax device is a CPU (tier-1 tests, a
    CLI run under ``JAX_PLATFORMS=cpu``), else ``"tpu"``."""
    global _BACKEND
    if _BACKEND is None:
        plats = {d.platform for d in jax.devices()}
        _BACKEND = "cpu-mesh" if plats == {"cpu"} else "tpu"
    return _BACKEND


def _require_tpu_unless_cpu_asked():
    """The CLI's device rule: measure on a TPU, or on the CPU mesh only
    when the caller asked for it with ``JAX_PLATFORMS=cpu``. Anything
    else (no chip found, another accelerator) exits non-zero — a bench
    that quietly ran on whatever it found is how CPU timings end up
    under device metric names."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu":
        return
    _emit_bench_error(
        f"no TPU: jax.devices()[0].platform is {platform!r}. Set "
        f"JAX_PLATFORMS=cpu to run on the virtual CPU mesh (stamped "
        f"\"cpu-mesh\"; not a device measurement)", "no_tpu")
    sys.exit(2)


def bench_resnet(batch, steps):
    """ResNet-50 amp O2 + FusedAdam — the driver's default metric
    (BASELINE.json metric 1). A function of its own so tier-1 can call
    it in-process."""
    from apex_tpu import amp
    from apex_tpu.models import ResNet50
    from apex_tpu.optimizers import FusedAdam

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, 224, 224, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1000, size=(batch,)))

    variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    # amp O2: model params bf16 (norm layers fp32), fp32 masters in the
    # optimizer, dynamic loss scaling.
    params, opt = amp.initialize(params, FusedAdam(lr=1e-3), opt_level="O2",
                                 verbosity=0)
    opt_state = opt.init(params)

    # Donation ON (round 4): the round-2/3 INVALID_ARGUMENT was root-
    # caused as OUR bug, not the backend's — amp O2's fp32 masters were
    # no-op-cast ALIASES of the already-fp32 norm params, so donating
    # params and opt_state presented the same buffer twice to Execute()
    # (reproduced on CPU; fixed by master_copy_tree, now enforced at
    # trace time by the double-donation lint rule in
    # apex_tpu.analysis). APEX_TPU_RESNET_DONATE=0 opts out.
    donate = ({} if os.environ.get("APEX_TPU_RESNET_DONATE") == "0"
              else dict(donate_argnums=(0, 1, 2)))

    @functools.partial(jax.jit, **donate)
    def train_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.mean(
                jnp.take_along_axis(logp, labels[:, None], axis=-1))
            return loss, updates["batch_stats"]

        scale = opt_state["scaler"].loss_scale
        (loss, new_bs), grads = jax.value_and_grad(
            lambda p: (lambda l, b: (l * scale, b))(*loss_fn(p)),
            has_aux=True)(params)
        new_params, new_opt_state = opt.step(grads, opt_state, params)
        return new_params, new_bs, new_opt_state, loss / scale

    _measure_step_cost(train_step,
                       (params, batch_stats, opt_state, images, labels))
    # warmup / compile. Timing ends with a host fetch of the loss, which
    # is data-dependent on the whole step chain — it returns only when
    # the device has finished every step.
    out = train_step(params, batch_stats, opt_state, images, labels)
    float(out[3])
    out = train_step(*out[:3], images, labels)
    float(out[3])

    t0 = time.perf_counter()
    for _ in range(steps):
        out = train_step(*out[:3], images, labels)
    float(out[3])  # host fetch = completion barrier for the whole chain
    dt = time.perf_counter() - t0
    _stage_compile_count(train_step)

    imgs_per_sec = batch * steps / dt
    # ResNet-50 fwd ~4.09 GFLOPs/image at 224x224; train = 3x fwd
    _emit("resnet50_amp_o2_fused_adam_imgs_per_sec_per_chip",
          imgs_per_sec, "imgs/sec", 3 * 4.09e9 * batch, steps, dt,
          **_comm_fields(params))


def bench_kernels(size, steps):
    """Per-kernel-family microbench for the apex_tpu.kernels layer
    (round-19 capture contract): each family runs the SAME jitted
    computation twice — once with the Pallas kernel forced on (compiled
    on TPU; interpreter mode on this CPU container, which measures the
    kernel *dataflow* lowered through XLA's loop machinery — honest,
    and expected slower than the fused jnp path here) and once on the
    jnp oracle at identical semantics — and emits
    ``<family>_kernel_ms`` / ``<family>_xla_ms`` / ``<family>_speedup``
    plus a ``kernel`` telemetry event per family. ``size`` scales the
    row count; the headline value is the geomean speedup (on cpu-mesh
    this tracks interpreter overhead, the TPU series is the real one —
    the ``backend`` field disambiguates, same convention as every
    other config)."""
    import math

    from apex_tpu.kernels import optim as _koptim
    from apex_tpu.kernels import quant4 as _quant4
    from apex_tpu.kernels.registry import get_kernel_registry
    from apex_tpu.parallel import compression
    from apex_tpu.transformer.functional import fused_softmax as _fsm

    kreg = get_kernel_registry()
    rng = np.random.RandomState(0)
    h = 512
    rows = int(size)
    x3d = jnp.asarray(rng.randn(8, 128, 128).astype(np.float32))
    nflat = rows * h
    g, p, m, v = (jnp.asarray(rng.randn(nflat).astype(np.float32))
                  for _ in range(4))
    x_blocks = jnp.asarray(
        rng.randn(nflat // 256, 256).astype(np.float32))

    on_tpu = _backend_verdict() == "tpu"

    def time_leg(make_fn, args, names, kernel_on):
        old = {"APEX_TPU_KERNELS": os.environ.get("APEX_TPU_KERNELS")}
        try:
            if not kernel_on:
                os.environ["APEX_TPU_KERNELS"] = "0"
            elif not on_tpu:
                kreg.force_interpret(True, names)
            fn = jax.jit(make_fn())
            out = fn(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = fn(*args)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / steps * 1e3
        finally:
            for k, val in old.items():
                if val is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = val
            kreg.force_interpret(False, names)

    def sm_make():
        def f(x):
            return jax.value_and_grad(
                lambda xx: jnp.sum(
                    _fsm.scaled_upper_triang_masked_softmax(xx, 1.0)
                    ** 2))(x)
        return f

    def adam_make():
        def f(gv, pv, mv, vv):
            return _koptim.fused_adam_update(
                gv, pv, mv, vv, lr=1e-3, bc1=0.9, bc2=0.99, b1=0.9,
                b2=0.999, eps=1e-8, weight_decay=0.01, adam_w=True)
        return f

    def lamb_make():
        def f(gv, pv, mv, vv):
            return _koptim.fused_lamb_mvu(
                gv, pv, mv, vv, bc1=0.9, bc2=0.99, b1=0.9, b2=0.999,
                beta3=0.1, eps=1e-6, weight_decay=0.01, adam_w=True)
        return f

    def int4_make():
        def f(x):
            absmax = jnp.maximum(
                jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12)
            sq, gmax = _quant4.int4_block_scales(absmax)
            scales = _quant4.effective_scales(sq, gmax)
            q = _quant4.quantize_int4(x, scales)
            packed = _quant4.pack_int4(q)
            return _quant4.dequantize_int4(
                _quant4.unpack_int4(packed), scales)
        return f

    families = [
        ("softmax", sm_make, (x3d,), ["softmax"]),
        ("adam", adam_make, (g, p, m, v), ["adam"]),
        ("lamb", lamb_make, (g, p, m, v), ["lamb"]),
        ("int4", int4_make, (x_blocks,), ["quant4"]),
    ]
    from apex_tpu import telemetry

    reg = telemetry.get_registry()
    fields = {}
    speedups = []
    t_total0 = time.perf_counter()
    for fam, make, args, names in families:
        xla_ms = time_leg(make, args, names, kernel_on=False)
        kernel_ms = time_leg(make, args, names, kernel_on=True)
        speedup = xla_ms / kernel_ms if kernel_ms > 0 else None
        fields[f"{fam}_kernel_ms"] = round(kernel_ms, 3)
        fields[f"{fam}_xla_ms"] = round(xla_ms, 3)
        fields[f"{fam}_speedup"] = (round(speedup, 3)
                                    if speedup is not None else None)
        if speedup:
            speedups.append(speedup)
        if reg.enabled:
            reg.event("kernel", "bench", kernel=fam,
                      kernel_ms=round(kernel_ms, 3),
                      xla_ms=round(xla_ms, 3))
    dt = time.perf_counter() - t_total0
    geomean = math.exp(sum(math.log(s) for s in speedups)
                       / len(speedups)) if speedups else 0.0
    # the int4 wire model next to int8/fp32 at a representative size
    n_model = 25_600_000
    world = int(os.environ.get("APEX_TPU_COMM_WORLD", "8"))
    fields["int4_comm_bytes_model"] = compression.estimate_allreduce_bytes(
        n_model, world=world, compress="int4")
    _emit("kernels_speedup_geomean", geomean, "x", 0, steps, dt,
          kernel_mode="pallas" if on_tpu else "interpret",
          **_comm_fields(training=False), **fields)


def bench_fused_cc(size, steps):
    """Fused computation-collective kernels (apex_tpu.kernels.fused_cc,
    round-20 capture contract): each family runs the SAME computation
    twice — fused gate on (Pallas; interpreter on this CPU container,
    same honesty caveat as the ``kernels`` config) and gate off (the
    unfused compute-then-collective oracle) — and emits
    ``fused_cc_<family>_fused_ms`` / ``_unfused_ms`` / ``_speedup``
    plus the headline geomean. Two invariants are ENFORCED, not just
    reported: the static auditor's wire bytes over the fused lowering
    must EQUAL the unfused lowering's (a fused op is priced, never
    dropped — the run raises otherwise), and the traced-jaxpr count of
    the eliminated HBM intermediates (pre-psum fp32 partial,
    dequantized KV tensor, int4 code tensor) must strictly drop
    (emitted as ``hbm_intermediates_{unfused,fused}_<family>``)."""
    import math

    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu import telemetry
    from apex_tpu.analysis.sharding import static_comm_bytes
    from apex_tpu.kernels import fused_cc
    from apex_tpu.kernels.registry import get_kernel_registry
    from apex_tpu.parallel import compression

    kreg = get_kernel_registry()
    on_tpu = _backend_verdict() == "tpu"
    devices = jax.devices()
    g = len(devices)
    mesh = Mesh(np.asarray(devices), ("model",))

    def sm(fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    rng = np.random.RandomState(0)
    rows, kdim, n = int(size), 128, 256
    x = jnp.asarray(rng.randn(rows, kdim).astype(np.float32))
    wfull = jnp.asarray(rng.randn(g * kdim, n).astype(np.float32))

    # family a: row-parallel matmul + TP psum (the mesh2d projection)
    def mm_make():
        def inner(xs, ws):
            return fused_cc.matmul_reduce_from(xs, ws, "model")
        return sm(inner, (P(), P("model")), P())
    mm_args = (x, wfull)

    # family b: int8-KV verify window (the speculative engine layout)
    T, wwin, gq, rep, d = 256, 5, 4, 2, 64
    feat = gq * d
    kq, ks = compression.quantize_rows_blockwise(
        jnp.asarray(rng.randn(T, feat).astype(np.float32)))
    vq, vs = compression.quantize_rows_blockwise(
        jnp.asarray(rng.randn(T, feat).astype(np.float32)))
    qwin = jnp.asarray(
        rng.randn(wwin, gq, rep, d).astype(np.float32))
    sm_scale = 1.0 / math.sqrt(d)

    def verify_make():
        def f(q, kq_, ks_, vq_, vs_):
            return fused_cc.spec_verify_attention(
                q, kq_, ks_, vq_, vs_, T - wwin, sm_scale, block_t=64)
        return f
    verify_args = (qwin, kq, ks, vq, vs)

    # family c: quantize-into-ring int4 gather (the ZeRO wire format)
    nflat = max(rows, 256) // 256 * 256 * 4
    gather_full = jnp.asarray(
        rng.randn(g * nflat).astype(np.float32))

    def ring_make():
        def inner(sh):
            return compression._all_gather_int4(sh, "model")
        return sm(inner, (P("model"),), P())
    ring_args = (gather_full,)

    def leg_env(fused_on):
        key = "APEX_TPU_KERNELS"
        old = os.environ.get(key)
        if not fused_on:
            os.environ[key] = "0"
        elif not on_tpu:
            kreg.force_interpret(True, ["fused_cc"])
        return old

    def leg_restore(old):
        key = "APEX_TPU_KERNELS"
        if old is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = old
        kreg.force_interpret(False, ["fused_cc"])

    def time_leg(make_fn, args, fused_on):
        old = leg_env(fused_on)
        try:
            fn = jax.jit(make_fn())
            out = fn(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = fn(*args)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / steps * 1e3
        finally:
            leg_restore(old)

    def static_leg(make_fn, args, fused_on):
        old = leg_env(fused_on)
        try:
            text = jax.jit(make_fn()).lower(*args).as_text()
            return static_comm_bytes(text)
        finally:
            leg_restore(old)

    def count_leg(make_fn, args, fused_on, predicate):
        old = leg_env(fused_on)
        try:
            closed = jax.make_jaxpr(make_fn())(*args)
            return fused_cc.count_jaxpr_avals(closed, predicate)
        finally:
            leg_restore(old)

    families = [
        ("matmul_psum", mm_make, mm_args, True,
         fused_cc.shape_predicate((rows, n), jnp.float32)),
        ("verify", verify_make, verify_args, False,
         fused_cc.shape_predicate((T, gq, d), jnp.float32)),
        ("int4_ring", ring_make, ring_args, True,
         fused_cc.dtype_predicate(jnp.int8)),
    ]
    reg = telemetry.get_registry()
    fields = {}
    speedups = []
    comm_fused_total = 0
    t_total0 = time.perf_counter()
    for fam, make, args, has_comm, pred in families:
        unfused_ms = time_leg(make, args, fused_on=False)
        fused_ms = time_leg(make, args, fused_on=True)
        speedup = unfused_ms / fused_ms if fused_ms > 0 else None
        fields[f"fused_cc_{fam}_fused_ms"] = round(fused_ms, 3)
        fields[f"fused_cc_{fam}_unfused_ms"] = round(unfused_ms, 3)
        fields[f"fused_cc_{fam}_speedup"] = (
            round(speedup, 3) if speedup is not None else None)
        if speedup:
            speedups.append(speedup)
        if has_comm:
            cb_unfused = static_leg(make, args, fused_on=False)
            cb_fused = static_leg(make, args, fused_on=True)
            if cb_fused != cb_unfused:
                raise RuntimeError(
                    f"fused_cc/{fam}: static comm bytes diverged — "
                    f"fused {cb_fused} vs unfused {cb_unfused} (a "
                    f"fused collective was mispriced or dropped)")
            fields[f"fused_cc_{fam}_comm_bytes"] = cb_fused
            comm_fused_total += cb_fused
        n_unfused = count_leg(make, args, False, pred)
        n_fused = count_leg(make, args, True, pred)
        if n_fused >= n_unfused:
            raise RuntimeError(
                f"fused_cc/{fam}: HBM intermediates not reduced "
                f"(fused {n_fused} vs unfused {n_unfused})")
        fields[f"hbm_intermediates_unfused_{fam}"] = n_unfused
        fields[f"hbm_intermediates_fused_{fam}"] = n_fused
        if reg.enabled:
            reg.event("kernel", "bench", kernel=f"fused_cc_{fam}",
                      kernel_ms=round(fused_ms, 3),
                      xla_ms=round(unfused_ms, 3))
    dt = time.perf_counter() - t_total0
    geomean = math.exp(sum(math.log(s) for s in speedups)
                       / len(speedups)) if speedups else 0.0
    fields["comm_bytes_per_step"] = comm_fused_total
    _emit("fused_cc_speedup_geomean", geomean, "x", 0, steps, dt,
          kernel_mode="pallas" if on_tpu else "interpret",
          world=g, **fields)


def bench_ddp_compressed(batch, steps, *, hidden=1024, depth=4):
    """DDP training step with block-quantized int8 gradient collectives
    + error feedback (parallel/compression.py) over ALL visible devices
    — the comm-compression capability capture. The emitted line carries
    the estimated per-step grad-sync bytes for the int8 payload
    (``comm_bytes_per_step``) next to the fp32 baseline
    (``comm_bytes_per_step_fp32``) and their ratio, so the byte win is
    visible even when the capture itself is compute-bound (or runs on
    a single chip, where the dp axis degenerates to 1).

    Model: a 4x1024 MLP regressor — big enough that the flat grad
    bucket spans thousands of quantization blocks, small enough to
    compile in seconds on the 1-core CPU host (the smoke path;
    ``hidden``/``depth`` shrink it further for the tier-1 telemetry
    test).
    """
    from apex_tpu.parallel import DistributedDataParallel, compression
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    world = len(devices)
    mesh = Mesh(np.asarray(devices), ("dp",))
    rng = np.random.RandomState(0)
    params = {}
    for i in range(depth):
        params[f"w{i}"] = jnp.asarray(
            rng.randn(hidden, hidden).astype(np.float32)
            / np.sqrt(hidden))
        params[f"b{i}"] = jnp.zeros((hidden,), jnp.float32)
    x = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))
    y = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))

    ddp = DistributedDataParallel(axis_name="dp", compress="int8")
    residual = ddp.init_residual(params)

    def loss_fn(p, xb, yb):
        h = xb
        for i in range(depth):
            h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
        return jnp.mean((h - yb) ** 2)

    def step_fn(p, res, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
        grads, res = ddp.sync(grads, res)
        p = jax.tree_util.tree_map(lambda w, g: w - 0.05 * g, p, grads)
        return p, res, loss

    sharded = jax.shard_map(step_fn, mesh=mesh,
                            in_specs=(P(), P(), P("dp"), P("dp")),
                            out_specs=(P(), P(), P()),
                            check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(p, res):
        return sharded(p, res, x, y)

    dt, _ = _time_steps(train_step, (params, residual), steps,
                        loss_index=2)
    n = _tree_size(params)
    fields = _comm_fields(params, compress="int8")
    world_model = int(os.environ.get("APEX_TPU_COMM_WORLD", "8"))
    fp32_bytes = compression.estimate_allreduce_bytes(n, world=world_model)
    # the round-19 int4 dual-quantization model (0.5 byte/elem + two-
    # level scales) next to the int8 payload this config actually runs
    int4_bytes = compression.estimate_allreduce_bytes(
        n, world=world_model, compress="int4")
    # fwd 2 flops/param-touch, train = 3x fwd
    flops = 6 * batch * world * depth * hidden * hidden
    _emit("ddp_compressed_int8_steps_per_sec",
          steps / dt, "steps/sec", flops, steps, dt,
          dp_world=world, grad_elements=n,
          comm_bytes_per_step_fp32=fp32_bytes,
          comm_bytes_reduction=round(
              fp32_bytes / max(fields["comm_bytes_per_step"], 1), 2),
          comm_bytes_per_step_int4=int4_bytes,
          comm_bytes_reduction_int4=round(
              fp32_bytes / max(int4_bytes, 1), 2),
          **fields)


def bench_ddp_overlapped(batch, steps, *, hidden=1024, depth=4,
                         segments=None):
    """Overlapped backward/collective DDP step (parallel/overlap.py) vs
    the ``ddp_compressed`` bucketed baseline — SAME model, SAME int8
    payload, SAME modeled ``comm_bytes_per_step`` — measured in one
    invocation so the delta is a real measured number, not a model.

    Three step variants run on the live device mesh:

    - **baseline**: full backward, then the bucketed int8 allreduce
      (exactly the ``ddp_compressed`` step);
    - **compute-only**: the same backward + SGD apply on LOCAL grads,
      no collectives — the serial decomposition's compute term;
    - **overlapped**: K per-layer-group segments, each segment's bucket
      psum emitted before the earlier segments' backward, bucket-domain
      EF residual, averaging folded into the dequant scales.

    ``comm_hidden_pct = (t_base - t_ovl) / (t_base - t_comp) * 100`` —
    the fraction of the baseline's comm cost that no longer appears on
    the overlapped step's critical path. On a multi-core/TPU backend
    that is latency hiding; on this 1-core CPU mesh it is eliminated
    marshalling work (docs/parallelism.md spells the mechanism out).
    The telemetry JSONL shows the interleaved
    ``ddp_overlap_segment_<k>`` / ``ddp_overlap_bucket_<n>`` spans;
    ``_measure_step_cost`` (comm bytes, lint, HBM) and the compile
    count are staged from the OVERLAPPED step.
    """
    from apex_tpu.parallel import (DistributedDataParallel,
                                   OverlappedDataParallel, compression)
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    world = len(devices)
    mesh = Mesh(np.asarray(devices), ("dp",))
    rng = np.random.RandomState(0)
    params = {}
    for i in range(depth):
        params[f"w{i}"] = jnp.asarray(
            rng.randn(hidden, hidden).astype(np.float32)
            / np.sqrt(hidden))
        params[f"b{i}"] = jnp.zeros((hidden,), jnp.float32)
    x = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))
    y = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))

    K = min(segments or depth, depth)
    groups = [list(g) for g in np.array_split(np.arange(depth), K)]
    # each timed variant donates its carry state — give every variant
    # its own copy of the (identical) initial params
    seg_params = [{k: jnp.copy(params[k]) for i in g
                   for k in (f"w{i}", f"b{i}")} for g in groups]
    comp_params = jax.tree_util.tree_map(jnp.copy, params)

    def loss_fn(p, xb, yb):
        h = xb
        for i in range(depth):
            h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
        return jnp.mean((h - yb) ** 2)

    # baseline: the ddp_compressed step, verbatim
    ddp = DistributedDataParallel(axis_name="dp", compress="int8")
    residual = ddp.init_residual(params)

    # commit every variant's carry state to the replicated sharding the
    # step outputs feed back, so call 1 and the steady state share ONE
    # compiled signature (compile_count == 1 — the ddp_memwatch lesson)
    from jax.sharding import NamedSharding

    replicated = NamedSharding(mesh, P())
    params, residual, seg_params, comp_params = jax.device_put(
        (params, residual, seg_params, comp_params), replicated)

    def base_fn(p, res, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
        grads, res = ddp.sync(grads, res)
        p = jax.tree_util.tree_map(lambda w, g: w - 0.05 * g, p, grads)
        return p, res, loss

    # batch data passed as proper ARGUMENTS (the lint-target idiom —
    # closing over a >= 1 MiB array is exactly what the
    # trace-constant-capture rule flags), committed to the dp sharding
    # so the steady state is one compiled signature
    base_step = functools.partial(jax.jit, donate_argnums=(0, 1))(
        jax.shard_map(base_fn, mesh=mesh,
                      in_specs=(P(), P(), P("dp"), P("dp")),
                      out_specs=(P(), P(), P()), check_vma=False))

    # compute-only: identical backward + apply, no collectives
    def comp_fn(p, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
        p = jax.tree_util.tree_map(lambda w, g: w - 0.05 * g, p, grads)
        return p, loss

    comp_step = functools.partial(jax.jit, donate_argnums=(0,))(
        jax.shard_map(comp_fn, mesh=mesh,
                      in_specs=(P(), P("dp"), P("dp")),
                      out_specs=(P(), P()), check_vma=False))

    # overlapped: segmented backward, per-bucket emission
    odp = OverlappedDataParallel(axis_name="dp", compress="int8")
    ores = jax.device_put(odp.init_residual(seg_params), replicated)
    n_buckets = sum(len(s) for s in odp.plan(seg_params))

    def ovl_fn(sp, res, xb, yb):
        segs = []
        for g in groups[:-1]:
            segs.append(lambda pk, h, g=tuple(g): functools.reduce(
                lambda hh, i: jnp.tanh(hh @ pk[f"w{i}"] + pk[f"b{i}"]),
                g, h))

        def last(pk, h, g=tuple(groups[-1])):
            for i in g:
                h = jnp.tanh(h @ pk[f"w{i}"] + pk[f"b{i}"])
            return jnp.mean((h - yb) ** 2)

        segs.append(last)
        loss, synced, res = odp.value_and_sync(segs, sp, xb,
                                               residual=res)
        sp = [jax.tree_util.tree_map(lambda w, g: w - 0.05 * g, pk, gk)
              for pk, gk in zip(sp, synced)]
        return sp, res, loss

    ovl_step = functools.partial(jax.jit, donate_argnums=(0, 1))(
        jax.shard_map(ovl_fn, mesh=mesh,
                      in_specs=(P(), P(), P("dp"), P("dp")),
                      out_specs=(P(), P(), P()), check_vma=False))

    x, y = jax.device_put((x, y), NamedSharding(mesh, P("dp")))

    def timed(step, state, loss_index):
        out = step(*state, x, y)
        float(out[loss_index])              # compile + first step
        out = step(*out[:loss_index], x, y)
        float(out[loss_index])              # one steady warmup
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step(*out[:loss_index], x, y)
        float(out[loss_index])              # completion barrier
        return (time.perf_counter() - t0) / steps

    # stage comm bytes / lint / HBM from the OVERLAPPED step (donated
    # buffers still live), then time all three variants
    _measure_step_cost(ovl_step, (seg_params, ores, x, y))
    from apex_tpu.telemetry import span

    with span("bench/timed_loop", steps=steps, variant="overlapped"):
        t_ovl = timed(ovl_step, (seg_params, ores), 2)
    _stage_compile_count(ovl_step)
    with span("bench/timed_loop", steps=steps, variant="baseline"):
        t_base = timed(base_step, (params, residual), 2)
    with span("bench/timed_loop", steps=steps, variant="compute_only"):
        t_comp = timed(comp_step, (comp_params,), 1)

    comm_hidden_pct = None
    if t_base > t_comp:
        comm_hidden_pct = round(
            (t_base - t_ovl) / (t_base - t_comp) * 100.0, 2)
    n = _tree_size(params)
    fields = _comm_fields(params, compress="int8")
    fp32_bytes = compression.estimate_allreduce_bytes(
        n, world=int(os.environ.get("APEX_TPU_COMM_WORLD", "8")))
    from apex_tpu import telemetry

    reg = telemetry.get_registry()
    if reg.enabled:
        reg.gauge("overlap/comm_hidden_pct").set(comm_hidden_pct or 0.0)
        reg.event("overlap", "summary", segments=K, buckets=n_buckets,
                  baseline_step_ms=round(t_base * 1e3, 3),
                  overlapped_step_ms=round(t_ovl * 1e3, 3),
                  compute_step_ms=round(t_comp * 1e3, 3),
                  comm_hidden_pct=comm_hidden_pct)
    flops = 6 * batch * world * depth * hidden * hidden
    ret = {
        "dp_world": world, "grad_elements": n,
        "overlap_segments": K, "overlap_buckets": n_buckets,
        "baseline_step_ms": round(t_base * 1e3, 3),
        "overlapped_step_ms": round(t_ovl * 1e3, 3),
        "compute_step_ms": round(t_comp * 1e3, 3),
        "comm_hidden_pct": comm_hidden_pct,
        "comm_bytes_per_step_fp32": fp32_bytes,
        "comm_bytes_reduction": round(
            fp32_bytes / max(fields["comm_bytes_per_step"], 1), 2),
    }
    _emit("ddp_overlapped_int8_steps_per_sec",
          steps / (t_ovl * steps), "steps/sec", flops, steps,
          t_ovl * steps, **ret, **fields)
    ret.update(fields)
    return ret


def bench_tp_dp(batch, steps, *, hidden=256, layers=4, heads=8,
                vocab=256, seq=32, data=2):
    """2-D ``(data, model)`` mesh composition (ROADMAP item 4): the
    GPT-2 column/row-parallel block stack (apex_tpu.parallel.mesh2d)
    trained with the production substrate — int8 DP gradient
    compression + EF residual scoped to the ``data`` axis, TP
    activation psums over ``model`` staying fp32 — measured two ways in
    one invocation at IDENTICAL comm bytes:

    - **baseline**: full backward, then the bucketed int8 DP sync;
    - **overlapped**: per-layer segments, each DP bucket's psum emitted
      mid-backward, interleaving with the remaining segments' TP psums
      (``parallel/overlap.py``).

    The proof obligations ride in-bench on a real (>= 2 device) mesh:
    all 13 lint rules clean with zero skips on the overlapped step
    (``overlap-serialization`` included, at a threshold between the TP
    activation-psum payload and the per-bucket gradient payload);
    static collective-graph wire bytes vs the trace-measured counters
    within the 25% gate PER AXIS (``comm/axis/data_bytes`` /
    ``comm/axis/model_bytes`` vs
    ``analysis.sharding.static_comm_bytes_by_axis``); the host-side
    elastic 2-D ZeRO reshard ``(data, tp) -> (data, tp//2) -> back``
    round-tripping bit-identically (``reshard_bitexact``); and
    ``compile_count == 1``.
    """
    from apex_tpu import analysis, telemetry
    from apex_tpu.analysis import sharding as _sharding
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.contrib.optimizers.distributed_fused_adam import (
        _flat_size as _zero_flat_size,
    )
    from apex_tpu.parallel import compression, mesh2d
    from apex_tpu.telemetry import span

    devices = jax.devices()
    multi = len(devices) >= 2 and len(devices) % 2 == 0
    mesh = mesh2d.mesh_2d(data if multi else 1,
                          None if multi else 1)
    dp_world = mesh.shape[mesh2d.DATA_AXIS]
    tp_world = mesh.shape[mesh2d.MODEL_AXIS]
    seg_params = mesh2d.gpt2_init(hidden=hidden, layers=layers,
                                  heads=heads, vocab=vocab,
                                  max_seq=seq)
    pdims = mesh2d.gpt2_partition_dims(seg_params)
    n_local = _tree_size(mesh2d.local_template(seg_params, tp_world))

    ovl_step, ovl_state = mesh2d.build_train_step(
        mesh, seg_params, hidden=hidden, heads=heads, mode="overlapped")
    base_step, base_state = mesh2d.build_train_step(
        mesh, seg_params, hidden=hidden, heads=heads, mode="baseline")
    tokens, labels = mesh2d.make_batch(mesh, batch_per_replica=batch,
                                       seq=seq, vocab=vocab)
    ovl_args = ovl_state + (tokens, labels)

    # per-axis static vs measured: snapshot the comm/axis counters
    # around _measure_step_cost's lowering — the FIRST trace of the
    # step, so the trace-time record_collective calls land inside the
    # delta (a later .trace()/.lower() reuses the cached trace and
    # records nothing) — then parse the same program's collective graph
    # with axes attached from the jaxpr
    _enable_bench_telemetry()
    reg = telemetry.get_registry()
    axes = (mesh2d.DATA_AXIS, mesh2d.MODEL_AXIS)
    before = {a: reg.counter_value(f"comm/axis/{a}_bytes")
              for a in axes}
    _measure_step_cost(ovl_step, ovl_args)
    measured_by_axis = {
        a: int(round(reg.counter_value(f"comm/axis/{a}_bytes")
                     - before[a]))
        for a in axes}
    traced = ovl_step.trace(*ovl_args)
    static_by_axis = _sharding.static_comm_bytes_by_axis(
        traced.lower().as_text(), traced.jaxpr)
    if multi and os.environ.get("APEX_TPU_COMM_GATE", "1") != "0":
        tol = float(os.environ.get("APEX_TPU_COMM_GATE_TOL", "0.25"))
        for a in axes:
            m, s = measured_by_axis[a], static_by_axis.get(a, 0)
            if m > 0 and abs(s - m) / m > tol:
                raise RuntimeError(
                    f"tp_dp axis '{a}' static/measured comm-bytes "
                    f"disagreement: static {s} vs measured {m} "
                    f"(> {tol * 100:.0f}% band)")

    # all 13 rules, zero skips, on the overlapped step — the
    # overlap-serialization threshold sits between the TP activation
    # psum payload and the per-bucket DP gradient payload so the rule
    # separates the inherent backward-chain TP psums from a genuine
    # bucket serialization (docs/parallelism.md)
    lint_violations = None
    if multi:
        # TP activation psum operand: fp32 [batch_local, seq, hidden];
        # smallest DP bucket operand: int32 partials of one segment's
        # local grads. The threshold = the bucket floor keeps the
        # inherent backward-chain TP psums below "big" while every DP
        # bucket is checked; a sizing where TP >= bucket would make
        # the rule fire on the inherent chain — fail loudly rather
        # than lint a vacuous threshold.
        tp_psum_bytes = batch * seq * hidden * 4
        min_bucket_bytes = 4 * min(
            int(sum(l.size for l in jax.tree_util.tree_leaves(seg)))
            for seg in mesh2d.local_template(seg_params, tp_world))
        if tp_psum_bytes >= min_bucket_bytes:
            raise RuntimeError(
                f"tp_dp sizing breaks the overlap-serialization "
                f"separation: TP psum payload {tp_psum_bytes} B >= "
                f"smallest DP bucket {min_bucket_bytes} B")
        cfg = analysis.LintConfig(overlap_min_bytes=min_bucket_bytes)
        report = analysis.lint_fn(ovl_step, *ovl_args,
                                  name="tp_dp/overlapped", config=cfg)
        if report.rules_skipped:
            raise RuntimeError(
                f"tp_dp lint skipped rules: {report.rules_skipped}")
        lint_violations = len(report.findings)
        if lint_violations:
            raise RuntimeError(
                f"tp_dp overlapped step lints dirty: "
                f"{[str(f) for f in report.findings]}")

    # elastic 2-D ZeRO reshard: synthetic full state in the canonical
    # form round-trips (data, tp) -> (data, max(1, tp//2)) -> back
    # bit-identically (host math; values copied, never re-rounded)
    opt = DistributedFusedAdam(compress=True)
    rng = np.random.RandomState(7)
    n_full = _zero_flat_size(seg_params)
    full0 = {"format": 2, "optimizer": "DistributedFusedAdam",
             "dp_world": dp_world, "tp_world": tp_world,
             "n_elements": n_full, "block_size": 256,
             "grad_compress": "int8", "param_compress": "bf16",
             "step": np.int32(11),
             "master": rng.randn(n_full).astype(np.float32),
             "exp_avg": rng.randn(n_full).astype(np.float32),
             "exp_avg_sq": np.abs(rng.randn(n_full)).astype(np.float32),
             "grad_residual": (rng.randn(n_full) * 1e-3)
             .astype(np.float32)}
    mid_tp = max(1, tp_world // 2)
    st_mid = opt.load_state_dict_resharded(
        full0, seg_params, world=(dp_world, mid_tp),
        partition_dims=pdims)
    mid = opt.state_dict_full(st_mid, seg_params,
                              world=(dp_world, mid_tp),
                              partition_dims=pdims)
    st_back = opt.load_state_dict_resharded(
        mid, seg_params, world=(dp_world, tp_world),
        partition_dims=pdims)
    back = opt.state_dict_full(st_back, seg_params,
                               world=(dp_world, tp_world),
                               partition_dims=pdims)
    reshard_bitexact = all(
        np.array_equal(np.asarray(back[k]), np.asarray(full0[k]))
        for k in ("master", "exp_avg", "exp_avg_sq", "grad_residual"))
    if not reshard_bitexact:
        raise RuntimeError(
            "tp_dp elastic 2-D reshard round-trip is not bit-exact")

    def timed(step, state):
        out = step(*state, tokens, labels)
        float(out[2])                   # compile + first step
        out = step(*out[:2], tokens, labels)
        float(out[2])                   # one steady warmup
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step(*out[:2], tokens, labels)
        float(out[2])                   # completion barrier
        return (time.perf_counter() - t0) / steps

    with span("bench/timed_loop", steps=steps, variant="overlapped"):
        t_ovl = timed(ovl_step, ovl_state)
    _stage_compile_count(ovl_step)
    compile_count = _PENDING_MEASURED.get("compile_count")
    _PENDING_MEASURED["lint_violations"] = lint_violations
    with span("bench/timed_loop", steps=steps, variant="baseline"):
        t_base = timed(base_step, base_state)

    fields = _comm_fields(n_elements=n_local, compress="int8")
    # the honest model for THIS config: the DP ring at the mesh's own
    # data-axis world over each (data, model) coordinate's local grads
    fields["comm_bytes_per_step"] = compression.estimate_allreduce_bytes(
        n_local, world=max(dp_world, 2), compress="int8")
    fields["comm_model"] = (f"ring allreduce, data={dp_world} x "
                            f"model={tp_world}, payload=int8 on the "
                            f"data axis only")
    if reg.enabled:
        reg.event("overlap", "summary", segments=layers,
                  baseline_step_ms=round(t_base * 1e3, 3),
                  overlapped_step_ms=round(t_ovl * 1e3, 3),
                  tp_dp=True)
    n_params = _tree_size(seg_params)
    tokens_per_step = batch * dp_world * seq
    flops = 6 * tokens_per_step * n_params
    ret = {
        "dp_world": dp_world, "tp_world": tp_world,
        "layers": layers, "grad_elements_local": n_local,
        "baseline_step_ms": round(t_base * 1e3, 3),
        "overlapped_step_ms": round(t_ovl * 1e3, 3),
        "measured_comm_bytes_per_axis": measured_by_axis,
        "static_comm_bytes_per_axis": static_by_axis,
        "reshard_bitexact": bool(reshard_bitexact),
    }
    _emit("tp_dp_steps_per_sec", 1.0 / t_ovl, "steps/sec", flops,
          steps, t_ovl * steps, **ret, **fields)
    ret.update(fields)
    ret["lint_violations"] = lint_violations
    ret["compile_count"] = compile_count
    return ret


def bench_pp_tp_dp(batch, steps, *, hidden=64, layers=2, heads=4,
                   vocab=64, seq=16, microbatches=4):
    """3-D ``(data, model, pipe)`` mesh composition (ISSUE 17): the
    stage-partitioned GPT-2 block stack under the host-unrolled 1F1B
    schedule (apex_tpu.parallel.pipeline) — per-tick
    ``collective_permute`` stage transfers over ``pipe``, TP activation
    psums over ``model``, the bucketed int8 DP grad sync over ``data``
    traced into the cooldown tail — measured against the substrate's
    proof obligations in one invocation:

    - **bubble fraction**: per-1F1B-slot cost from the M -> 2M
      microbatch delta (fixed dispatch overhead cancels), measured
      bubble ``1 - c*M/t(M)`` vs the analytic ``(pp-1)/(m+pp-1)``;
    - **overlapped vs baseline** step ms at IDENTICAL per-axis wire
      bytes (the baseline marshals the EF residual through the leaf
      domain; the buckets on the wire are the same);
    - per-axis static == measured comm bytes (``pipe`` included),
      all 13 lint rules clean with zero skips, ``compile_count == 1``,
      and the elastic 3-D ZeRO reshard 2x2x2 -> 2x2x1 -> back
      round-tripping bit-identically.
    """
    from apex_tpu import analysis, telemetry
    from apex_tpu.analysis import sharding as _sharding
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.contrib.optimizers.distributed_fused_adam import (
        _flat_size as _zero_flat_size,
    )
    from apex_tpu.parallel import compression, mesh2d, pipeline
    from apex_tpu.telemetry import span

    devices = jax.devices()
    multi = len(devices) >= 8 and len(devices) % 8 == 0
    mesh = (pipeline.mesh_3d(2, 2, 2) if multi
            else pipeline.mesh_3d(1, 1, 1, devices=devices[:1]))
    dp_world = mesh.shape[pipeline.DATA_AXIS]
    tp_world = mesh.shape[pipeline.MODEL_AXIS]
    pp_world = mesh.shape[pipeline.PIPE_AXIS]
    M = int(microbatches)
    seg_params = mesh2d.gpt2_init(hidden=hidden, layers=layers,
                                  heads=heads, vocab=vocab, max_seq=seq)
    zsegs, zdims = pipeline.pipeline_zero_segments(seg_params)
    lp = layers // pp_world
    seg_locals = [mesh2d.local_template(seg_params[:1], tp_world)[0]
                  ["layer"]] * lp
    edge_local = {"embed": seg_params[0]["embed"],
                  "ln_f": seg_params[-1]["ln_f"],
                  "head": seg_params[-1]["head"]}
    n_local = sum(_tree_size(t) for t in seg_locals + [edge_local])

    def build(mode, m):
        step, state = pipeline.build_pipeline_step(
            mesh, seg_params, hidden=hidden, heads=heads,
            microbatches=m, mode=mode)
        tokens, labels = pipeline.make_batch_3d(
            mesh, microbatches=m, batch_per_replica=batch, seq=seq,
            vocab=vocab)
        return step, state, tokens, labels

    ovl_step, ovl_state, tokens, labels = build("overlapped", M)
    ovl_args = ovl_state + (tokens, labels)

    # per-axis static vs measured around the FIRST trace (the tp_dp
    # counter-delta idiom, with the pipe axis now in the set)
    _enable_bench_telemetry()
    reg = telemetry.get_registry()
    axes = (pipeline.DATA_AXIS, pipeline.MODEL_AXIS, pipeline.PIPE_AXIS)
    before = {a: reg.counter_value(f"comm/axis/{a}_bytes")
              for a in axes}
    _measure_step_cost(ovl_step, ovl_args)
    measured_by_axis = {
        a: int(round(reg.counter_value(f"comm/axis/{a}_bytes")
                     - before[a]))
        for a in axes}
    traced = ovl_step.trace(*ovl_args)
    static_by_axis = _sharding.static_comm_bytes_by_axis(
        traced.lower().as_text(), traced.jaxpr)
    # all three axes always priced (the round-22 schema contract),
    # even when a size-1 axis lowers to no collectives
    static_by_axis = {a: int(static_by_axis.get(a, 0)) for a in axes}
    if multi and os.environ.get("APEX_TPU_COMM_GATE", "1") != "0":
        tol = float(os.environ.get("APEX_TPU_COMM_GATE_TOL", "0.25"))
        for a in axes:
            m_, s_ = measured_by_axis[a], static_by_axis.get(a, 0)
            if m_ > 0 and abs(s_ - m_) / m_ > tol:
                raise RuntimeError(
                    f"pp_tp_dp axis '{a}' static/measured comm-bytes "
                    f"disagreement: static {s_} vs measured {m_} "
                    f"(> {tol * 100:.0f}% band)")

    # all 13 rules, zero skips: the threshold sits between the stage
    # transfer payload (= the TP activation psum payload) and the
    # smallest DP bucket, so the inherent pipeline/TP chains stay
    # below "big" while every DP bucket is checked
    lint_violations = None
    if multi:
        xfer_bytes = batch * seq * hidden * 4
        min_bucket_bytes = 4 * min(
            int(sum(l.size for l in jax.tree_util.tree_leaves(t)))
            for t in seg_locals + [edge_local])
        if xfer_bytes >= min_bucket_bytes:
            raise RuntimeError(
                f"pp_tp_dp sizing breaks the overlap-serialization "
                f"separation: stage transfer payload {xfer_bytes} B >= "
                f"smallest DP bucket {min_bucket_bytes} B")
        cfg = analysis.LintConfig(overlap_min_bytes=min_bucket_bytes)
        report = analysis.lint_fn(ovl_step, *ovl_args,
                                  name="pp_tp_dp/overlapped",
                                  config=cfg)
        if report.rules_skipped:
            raise RuntimeError(
                f"pp_tp_dp lint skipped rules: {report.rules_skipped}")
        lint_violations = len(report.findings)
        if lint_violations:
            raise RuntimeError(
                f"pp_tp_dp overlapped step lints dirty: "
                f"{[str(f) for f in report.findings]}")

    # elastic 3-D ZeRO: synthetic canonical state round-trips
    # 2x2x2 -> 2x2x1 -> 2x2x2 bit-identically (host math)
    opt = DistributedFusedAdam(compress=True)
    rng = np.random.RandomState(17)
    n_full = _zero_flat_size(zsegs)
    full0 = {"format": 3, "optimizer": "DistributedFusedAdam",
             "dp_world": dp_world, "tp_world": tp_world,
             "pp_world": pp_world, "n_elements": n_full,
             "shared_tail_elements": _zero_flat_size(zsegs[-1:]),
             "block_size": 256, "grad_compress": "int8",
             "param_compress": "bf16", "step": np.int32(13),
             "master": rng.randn(n_full).astype(np.float32),
             "exp_avg": rng.randn(n_full).astype(np.float32),
             "exp_avg_sq": np.abs(rng.randn(n_full)).astype(np.float32),
             "grad_residual": (rng.randn(n_full) * 1e-3)
             .astype(np.float32)}
    shrunk = (dp_world, tp_world, 1)
    grown = (dp_world, tp_world, pp_world)
    st_mid = opt.load_state_dict_resharded(
        full0, zsegs, world=shrunk, partition_dims=zdims)
    mid = opt.state_dict_full(st_mid, zsegs, world=shrunk,
                              partition_dims=zdims)
    st_back = opt.load_state_dict_resharded(
        mid, zsegs, world=grown, partition_dims=zdims)
    back = opt.state_dict_full(st_back, zsegs, world=grown,
                               partition_dims=zdims)
    reshard_bitexact = all(
        np.array_equal(np.asarray(back[k]), np.asarray(full0[k]))
        for k in ("master", "exp_avg", "exp_avg_sq", "grad_residual"))
    if not reshard_bitexact:
        raise RuntimeError(
            "pp_tp_dp elastic 3-D reshard round-trip is not bit-exact")

    def timed(step, state, tok, lab):
        out = step(*state, tok, lab)
        float(out[3])                   # compile + first step
        out = step(*out[:3], tok, lab)
        float(out[3])                   # one steady warmup
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step(*out[:3], tok, lab)
        float(out[3])                   # completion barrier
        return (time.perf_counter() - t0) / steps

    with span("bench/timed_loop", steps=steps, variant="overlapped"):
        t_ovl = timed(ovl_step, ovl_state, tokens, labels)
    _stage_compile_count(ovl_step)
    compile_count = _PENDING_MEASURED.get("compile_count")
    _PENDING_MEASURED["lint_violations"] = lint_violations
    base_step, base_state, btok, blab = build("baseline", M)
    with span("bench/timed_loop", steps=steps, variant="baseline"):
        t_base = timed(base_step, base_state, btok, blab)
    # the M -> 2M delta prices one 1F1B slot; the fixed dispatch
    # overhead and the warmup/cooldown bubble cost cancel out of c
    ovl2_step, ovl2_state, tok2, lab2 = build("overlapped", 2 * M)
    with span("bench/timed_loop", steps=steps, variant="2m"):
        t_2m = timed(ovl2_step, ovl2_state, tok2, lab2)
    c = max((t_2m - t_ovl) / M, 1e-12)
    bubble_fraction = max(0.0, 1.0 - (c * M) / t_ovl)
    bubble_model = pipeline.analytic_bubble_fraction(pp_world, M)
    if multi and os.environ.get("APEX_TPU_BUBBLE_GATE", "1") != "0":
        tol = float(os.environ.get("APEX_TPU_BUBBLE_TOL", "0.35"))
        if abs(bubble_fraction - bubble_model) > tol:
            raise RuntimeError(
                f"pp_tp_dp measured bubble fraction "
                f"{bubble_fraction:.3f} is outside the +-{tol} band "
                f"around the 1F1B model {bubble_model:.3f}")

    fields = _comm_fields(n_elements=n_local, compress="int8")
    fields["comm_bytes_per_step"] = compression.estimate_allreduce_bytes(
        n_local, world=max(dp_world, 2), compress="int8")
    fields["comm_model"] = (f"ring allreduce, data={dp_world} x "
                            f"model={tp_world} x pipe={pp_world}, "
                            f"payload=int8 on the data axis only")
    if reg.enabled:
        reg.event("pipeline", "summary", stages=pp_world,
                  microbatches=M,
                  baseline_step_ms=round(t_base * 1e3, 3),
                  overlapped_step_ms=round(t_ovl * 1e3, 3),
                  bubble_fraction=round(bubble_fraction, 4),
                  bubble_fraction_model=round(bubble_model, 4))
    n_params = _tree_size(seg_params)
    tokens_per_step = batch * M * dp_world * seq
    flops = 6 * tokens_per_step * n_params
    ret = {
        "dp_world": dp_world, "tp_world": tp_world,
        "pp_world": pp_world, "pipeline_stages": pp_world,
        "microbatches": M, "layers": layers,
        "grad_elements_local": n_local,
        "baseline_step_ms": round(t_base * 1e3, 3),
        "overlapped_step_ms": round(t_ovl * 1e3, 3),
        "bubble_fraction": round(bubble_fraction, 4),
        "bubble_fraction_model": round(bubble_model, 4),
        "measured_comm_bytes_per_axis": measured_by_axis,
        "static_comm_bytes_per_axis": static_by_axis,
        "reshard_bitexact": bool(reshard_bitexact),
    }
    _emit("pp_tp_dp_steps_per_sec", 1.0 / t_ovl, "steps/sec", flops,
          steps, t_ovl * steps, **ret, **fields)
    ret.update(fields)
    ret["lint_violations"] = lint_violations
    ret["compile_count"] = compile_count
    return ret


def bench_ddp_resilience(batch, steps, *, hidden=256, depth=2,
                         nan_step=None):
    """DDP training under the full resilience spine: int8-compressed
    grad collectives with error feedback, deterministic NaN injection
    at ``nan_step`` (default ``$APEX_TPU_FAULT_NAN_STEP``; None = no
    fault), and ``resilience.guarded_update`` skipping poisoned steps
    in-graph — the poisoned step must cost one skip, never the run.

    The emitted line carries ``steps_skipped`` (from the device-side
    GuardState, reconciled into the ``guard/steps_skipped`` telemetry
    counter by ``check_guard``) and ``final_loss`` so a capture proves
    the guard fired AND training stayed finite. Timing includes the
    first-call compile — this is a robustness capture, not a perf
    flagship; the guard's cost shows up in ``ddp_compressed`` deltas.

    Returns ``{"steps_skipped", "final_loss", "nan_step"}`` for
    in-process callers (tier-1).
    """
    from apex_tpu import resilience
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.resilience import faults
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    world = len(devices)
    mesh = Mesh(np.asarray(devices), ("dp",))
    if nan_step is None:
        nan_step = faults.nan_step_from_env()
    rng = np.random.RandomState(0)
    params = {}
    for i in range(depth):
        params[f"w{i}"] = jnp.asarray(
            rng.randn(hidden, hidden).astype(np.float32)
            / np.sqrt(hidden))
        params[f"b{i}"] = jnp.zeros((hidden,), jnp.float32)
    x = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))
    y = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))

    ddp = DistributedDataParallel(axis_name="dp", compress="int8")
    residual = ddp.init_residual(params)
    gstate = resilience.init_guard_state()

    def loss_fn(p, xb, yb):
        h = xb
        for i in range(depth):
            h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
        return jnp.mean((h - yb) ** 2)

    def step_fn(p, res, gst, step, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
        grads = faults.inject_nan(grads, step, nan_step)
        # flag from the LOCAL pre-compression grads: int8 quantization
        # can launder a NaN into finite wire garbage, so the flag — not
        # the payload — is what crosses replicas (inside guarded_update)
        flag = resilience.nonfinite_flag(grads)
        synced, new_res = ddp.sync(grads, res)

        def commit(g, st):
            prev_p, _ = st
            new_p = jax.tree_util.tree_map(
                lambda w, gg: w - 0.05 * gg, prev_p, g)
            return (new_p, new_res)  # residual commits only with the step

        (p, res), gst = resilience.guarded_update(
            synced, commit, (p, res), gst, axis_name="dp", flag=flag)
        return p, res, gst, loss

    sharded = jax.shard_map(step_fn, mesh=mesh,
                            in_specs=(P(), P(), P(), P(), P("dp"),
                                      P("dp")),
                            out_specs=(P(), P(), P(), P()),
                            check_vma=False)

    @jax.jit
    def train_step(p, res, gst, step):
        return sharded(p, res, gst, step, x, y)

    _measure_step_cost(train_step,
                       (params, residual, gstate,
                        jnp.zeros((), jnp.int32)))
    from apex_tpu.telemetry import span

    p, res, gst = params, residual, gstate
    loss = None
    t0 = time.perf_counter()
    with span("bench/timed_loop", steps=steps):
        for i in range(steps):
            with span("bench/step"):
                p, res, gst, loss = train_step(
                    p, res, gst, jnp.asarray(i, jnp.int32))
            # host-side escalation poll (3 i32 scalars per step);
            # max=steps+1 records telemetry without ever escalating a
            # deliberate injection
            resilience.check_guard(gst, max_consecutive_skips=steps + 1)
        final_loss = float(loss)
    dt = time.perf_counter() - t0
    _stage_compile_count(train_step)
    skipped = int(gst.total_skips)

    n = _tree_size(params)
    fields = _comm_fields(params, compress="int8")
    flops = 6 * batch * world * depth * hidden * hidden
    _emit("ddp_resilience_steps_per_sec", steps / dt, "steps/sec",
          flops, steps, dt, dp_world=world, grad_elements=n,
          steps_skipped=skipped,
          nan_step=nan_step, final_loss=final_loss, **fields)
    return {"steps_skipped": skipped, "final_loss": final_loss,
            "nan_step": nan_step}


def bench_ddp_numerics(batch, steps, *, hidden=256, depth=2,
                       nan_step=None, ring=8):
    """DDP training with the full numerics-observability spine: per-
    layer in-graph stats on the local pre-compression grads + the
    dequantized synced grads (``DistributedDataParallel(numerics=1)``),
    a device-side :class:`~apex_tpu.telemetry.recorder.FlightRecorder`
    ring of the last ``ring`` steps threaded through the guarded step,
    and ``check_guard`` dumping ``numerics-postmortem-rank<N>.json``
    when a NaN injection (``nan_step`` / ``$APEX_TPU_FAULT_NAN_STEP``,
    targeted at the LAST layer only via ``inject_nan``'s path filter)
    trips the guard.

    The headline number is ``numerics_overhead_pct``: the timed-loop
    cost of stats+ring versus the identical guarded int8 DDP step with
    numerics off — the price of always-on per-layer observability.
    Timing excludes compiles (both variants warm first); the post-
    mortem dump (one small host fetch, only on an already-skipped
    step) stays inside the loop because that IS the integration under
    measurement.

    Returns ``{"steps_skipped", "final_loss", "nan_step",
    "numerics_overhead_pct", "postmortem_path",
    "first_nonfinite_prefix"}`` for in-process callers (tier-1).
    """
    from apex_tpu import resilience
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.resilience import faults
    from apex_tpu.telemetry import span
    from apex_tpu.telemetry.recorder import FlightRecorder
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    world = len(devices)
    mesh = Mesh(np.asarray(devices), ("dp",))
    if nan_step is None:
        nan_step = faults.nan_step_from_env()
    target_prefix = f"layer{depth - 1}"
    rng = np.random.RandomState(0)
    params = {}
    for i in range(depth):
        params[f"layer{i}"] = {
            "w": jnp.asarray(rng.randn(hidden, hidden).astype(np.float32)
                             / np.sqrt(hidden)),
            "b": jnp.zeros((hidden,), jnp.float32),
        }
    x = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))
    y = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))

    def loss_fn(p, xb, yb):
        h = xb
        for i in range(depth):
            lyr = p[f"layer{i}"]
            h = jnp.tanh(h @ lyr["w"] + lyr["b"])
        return jnp.mean((h - yb) ** 2)

    def make_step(numerics_on):
        ddp = DistributedDataParallel(
            axis_name="dp", compress="int8",
            numerics=1 if numerics_on else None)
        rec = FlightRecorder(length=ring, prefix_depth=1) \
            if numerics_on else None

        def step_fn(p, res, gst, rstate, step, xb, yb):
            loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
            grads = faults.inject_nan(grads, step, nan_step,
                                      path_filter=target_prefix)
            flag = resilience.nonfinite_flag(grads)
            if numerics_on:
                synced, new_res, stats = ddp.sync(grads, res)
            else:
                synced, new_res = ddp.sync(grads, res)

            def commit(g, st):
                prev_p, _ = st
                new_p = jax.tree_util.tree_map(
                    lambda w, gg: w - 0.05 * gg, prev_p, g)
                return (new_p, new_res)

            if numerics_on:
                (p, res), gst, rstate = resilience.guarded_update(
                    synced, commit, (p, res), gst, axis_name="dp",
                    flag=flag, recorder=rec, recorder_state=rstate,
                    stats=stats, step=step)
            else:
                (p, res), gst = resilience.guarded_update(
                    synced, commit, (p, res), gst, axis_name="dp",
                    flag=flag)
            return p, res, gst, rstate, loss

        sharded = jax.shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P("dp"), P("dp")),
            out_specs=(P(), P(), P(), P(), P()), check_vma=False)

        @jax.jit
        def train_step(p, res, gst, rstate, step):
            return sharded(p, res, gst, rstate, step, x, y)

        return ddp, rec, train_step

    ddp_base, _, base_step = make_step(False)
    ddp_num, rec, num_step = make_step(True)
    rstate0 = rec.init_state(params, prefixes=("grads", "synced"))

    def run(train_step, ddp, rstate, label, with_recorder):
        p = params
        res = ddp.init_residual(params)
        gst = resilience.init_guard_state()
        # warm: compile + one steady step, outside the timed window
        p, res, gst, rstate, loss = train_step(
            p, res, gst, rstate, jnp.asarray(-2, jnp.int32))
        float(loss)
        with span(f"bench/timed_loop_{label}", steps=steps):
            t0 = time.perf_counter()
            for i in range(steps):
                p, res, gst, rstate, loss = train_step(
                    p, res, gst, rstate, jnp.asarray(i, jnp.int32))
                resilience.check_guard(
                    gst, max_consecutive_skips=steps + 1,
                    recorder=rec if with_recorder else None,
                    recorder_state=rstate if with_recorder else None)
            final_loss = float(loss)
            dt = time.perf_counter() - t0
        return dt, final_loss, gst

    _measure_step_cost(num_step, (params, ddp_num.init_residual(params),
                                  resilience.init_guard_state(), rstate0,
                                  jnp.zeros((), jnp.int32)))
    dt_base, _, _ = run(base_step, ddp_base, rstate0, "plain", False)
    dt_num, final_loss, gst = run(num_step, ddp_num, rstate0, "numerics",
                                  True)
    _stage_compile_count(num_step)
    overhead_pct = (dt_num - dt_base) / dt_base * 100.0
    skipped = int(gst.total_skips)
    pm = rec.last_postmortem
    first_prefix = pm["first_nonfinite_prefix"] if pm else None

    n = _tree_size(params)
    fields = _comm_fields(params, compress="int8")
    flops = 6 * batch * world * depth * hidden * hidden
    _emit("ddp_numerics_steps_per_sec", steps / dt_num, "steps/sec",
          flops, steps, dt_num, dp_world=world, grad_elements=n,
          steps_skipped=skipped, nan_step=nan_step,
          final_loss=final_loss,
          numerics_overhead_pct=round(overhead_pct, 2),
          numerics_ring=ring,
          first_nonfinite_prefix=first_prefix, **fields)
    return {"steps_skipped": skipped, "final_loss": final_loss,
            "nan_step": nan_step,
            "numerics_overhead_pct": round(overhead_pct, 2),
            "postmortem_path": pm["path"] if pm else None,
            "first_nonfinite_prefix": first_prefix}


def bench_ddp_memwatch(batch, steps, *, hidden=256, depth=2,
                       alloc_step=None):
    """Guarded int8 DDP training under the full compile & memory
    observability spine: the train step runs watched by a
    :class:`~apex_tpu.telemetry.compile_watch.CompileWatcher` (every
    trace/compile counted and signature-diffed), its HBM budget is
    accounted up front (``preflight`` + ``step_memory`` -> the
    ``memory/hbm_headroom`` gauge and the per-device ZeRO-relevant
    census), and each dispatch goes through
    ``resilience.guarded_call`` so a RESOURCE_EXHAUSTED — real, or the
    deterministic ``faults.inject_alloc_failure`` at ``alloc_step``
    (default ``$APEX_TPU_FAULT_ALLOC_STEP``; None = no fault) — writes
    ``memory-postmortem-rank<N>.json`` (live-buffer census + headroom
    trend) instead of dying with a bare traceback. An injected OOM
    costs that one step: the loop records the post-mortem and
    continues, proving the handler path without killing the capture.

    The emitted line carries the round-10 fields ``peak_hbm_bytes`` /
    ``hbm_headroom_pct`` / ``compile_count`` (== 1 in a shape-stable
    run — the recompile-stability evidence) plus
    ``oom_postmortem_path``. The observation contract matches PR 4:
    everything here is host-side, so the lowered steady-state HLO is
    byte-identical with the watcher on or off (asserted in
    tests/L0/test_memory_watch.py).

    Returns ``{"compile_count", "recompiles", "peak_hbm_bytes",
    "hbm_headroom_pct", "oom_postmortem_path", "alloc_step",
    "steps_skipped", "final_loss"}`` for in-process callers (tier-1).
    """
    from apex_tpu import resilience, telemetry
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.resilience import faults
    from apex_tpu.telemetry import span
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    world = len(devices)
    mesh = Mesh(np.asarray(devices), ("dp",))
    if alloc_step is None:
        alloc_step = faults.alloc_step_from_env()
    rng = np.random.RandomState(0)
    params = {}
    for i in range(depth):
        params[f"w{i}"] = jnp.asarray(
            rng.randn(hidden, hidden).astype(np.float32)
            / np.sqrt(hidden))
        params[f"b{i}"] = jnp.zeros((hidden,), jnp.float32)
    x = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))
    y = jnp.asarray(rng.randn(batch * world, hidden).astype(np.float32))

    ddp = DistributedDataParallel(axis_name="dp", compress="int8")
    residual = ddp.init_residual(params)
    gstate = resilience.init_guard_state()
    # commit the carried state to the replicated sharding the step's
    # out_specs produce, so call 0 and call N share ONE abstract
    # signature — otherwise the warmup call (single-device inputs)
    # and the steady state (replicated outputs fed back) are two
    # signatures = two compiles, and compile_count could never be 1
    from jax.sharding import NamedSharding

    replicated = NamedSharding(mesh, P())
    params, residual, gstate = jax.device_put(
        (params, residual, gstate), replicated)

    def loss_fn(p, xb, yb):
        h = xb
        for i in range(depth):
            h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
        return jnp.mean((h - yb) ** 2)

    def step_fn(p, res, gst, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
        flag = resilience.nonfinite_flag(grads)
        synced, new_res = ddp.sync(grads, res)

        def commit(g, st):
            prev_p, _ = st
            new_p = jax.tree_util.tree_map(
                lambda w, gg: w - 0.05 * gg, prev_p, g)
            return (new_p, new_res)

        (p, res), gst = resilience.guarded_update(
            synced, commit, (p, res), gst, axis_name="dp", flag=flag)
        return p, res, gst, loss

    sharded = jax.shard_map(step_fn, mesh=mesh,
                            in_specs=(P(), P(), P(), P("dp"), P("dp")),
                            out_specs=(P(), P(), P(), P()),
                            check_vma=False)

    @jax.jit
    def train_step(p, res, gst):
        return sharded(p, res, gst, x, y)

    # the explicit opt-in: watch the step (host-side wrapper; the HLO
    # stays byte-identical) and account its HBM budget before dispatch.
    # A fresh watcher per run — the process-global get_watcher() would
    # diff this run's first compile against a previous run's signature
    watcher = telemetry.CompileWatcher(enabled=True)
    watched_step = watcher.watch(train_step, "ddp_memwatch/train_step")
    _measure_step_cost(train_step, (params, residual, gstate))
    mem = telemetry.memory.preflight(train_step, params, residual, gstate,
                                     name="ddp_memwatch/train_step")

    labels = {"params": params, "residual": residual, "batch": (x, y)}
    oom_path = None
    p, res, gst = params, residual, gstate
    loss = None
    # warmup (compile + one steady step) outside the timed window
    p, res, gst, loss = watched_step(p, res, gst)
    float(loss)

    def dispatch(step_i, *state):
        # the injector fires where a real HBM exhaustion would: on the
        # host, at dispatch, inside guarded_call's oom_guard
        faults.inject_alloc_failure(step_i, alloc_step)
        return watched_step(*state)

    t0 = time.perf_counter()
    with span("bench/timed_loop", steps=steps):
        for i in range(steps):
            try:
                with span("bench/step"):
                    p, res, gst, loss = resilience.guarded_call(
                        dispatch, i, p, res, gst, labels=labels)
            except resilience.HBMExhaustedError:
                # the post-mortem landed; an injected OOM costs one
                # step, never the capture
                pm = telemetry.memory.last_postmortem()
                oom_path = pm["path"] if pm else None
                continue
            resilience.check_guard(gst, max_consecutive_skips=steps + 1)
        final_loss = float(loss)
    dt = time.perf_counter() - t0
    _stage_compile_count(watched_step)
    compile_count = _PENDING_MEASURED.get("compile_count")
    skipped = int(gst.total_skips)

    n = _tree_size(params)
    fields = _comm_fields(params, compress="int8")
    flops = 6 * batch * world * depth * hidden * hidden
    _emit("ddp_memwatch_steps_per_sec", steps / dt, "steps/sec",
          flops, steps, dt, dp_world=world, grad_elements=n,
          steps_skipped=skipped, alloc_step=alloc_step,
          final_loss=final_loss, oom_postmortem_path=oom_path,
          **fields)
    return {"compile_count": compile_count,
            "recompiles": watcher.recompile_count(),
            "peak_hbm_bytes": mem["peak_bytes"] if mem else None,
            "hbm_headroom_pct":
                round(mem["headroom_frac"] * 100.0, 2)
                if mem and mem.get("headroom_frac") is not None else None,
            "oom_postmortem_path": oom_path, "alloc_step": alloc_step,
            "steps_skipped": skipped, "final_loss": final_loss}


def bench_ddp_recovery(batch, steps, *, hidden=24, depth=2):
    """Supervised-training chaos campaign (resilience.supervisor over
    guarded int8 DDP+ZeRO): ONE run takes a NaN-escalation streak, a
    synthetic OOM, a torn checkpoint write, and a simulated preemption
    — every class recovered automatically by the per-class
    RecoveryPolicy (hot-snapshot revert + loss-scale backoff,
    checkpoint-fallback restore, save-and-exit + resume), with the
    step ledger proving no step was lost or double-applied and the
    final loss matching an un-faulted baseline (tools/chaos_run.py
    owns the harness and the invariant asserts — a violated invariant
    is a bench crash, not a quietly wrong number).

    The emitted line carries the round-13 recovery contract:
    ``restarts``, ``mttr_steps`` (mean steps replayed per recovery —
    the snapshot cadence bound), ``snapshot_restores``,
    ``checkpoint_restores``, ``goodput_step_ratio`` (committed steps /
    total dispatches incl. replays), and ``final_loss_delta`` vs the
    clean run. Timing covers the whole campaign (clean + chaos +
    resume) — this is a robustness capture, not a perf flagship.
    """
    from tools.chaos_run import run_acceptance

    world = len(jax.devices())
    while world > 1 and batch % world:
        world //= 2  # an odd device count still gets a valid mesh
    t0 = time.perf_counter()
    out = run_acceptance(steps=steps, world=world, hidden=hidden,
                         depth=depth, global_batch=batch)
    dt = time.perf_counter() - t0
    if out["violations"]:
        raise RuntimeError("ddp_recovery invariants violated: "
                           + "; ".join(out["violations"]))
    n = depth * (hidden * hidden + hidden)
    fields = _comm_fields(n_elements=n, compress="int8")
    flops = 6 * batch * depth * hidden * hidden
    _emit("ddp_recovery_steps_per_sec", steps / dt, "steps/sec",
          flops, steps, dt, dp_world=out["world"], grad_elements=n,
          restarts=out["restarts"],
          mttr_steps=round(out["mttr_steps"], 3),
          snapshot_restores=out["snapshot_restores"],
          checkpoint_restores=out["checkpoint_restores"],
          goodput_step_ratio=round(out["goodput_step_ratio"], 4),
          final_loss_delta=out["final_loss_delta"],
          reshard_bitexact=out["reshard_bitexact"],
          cause_histogram=out["cause_histogram"], **fields)
    return {k: out[k] for k in (
        "restarts", "mttr_steps", "snapshot_restores",
        "checkpoint_restores", "goodput_step_ratio", "final_loss_delta",
        "reshard_bitexact", "cause_histogram", "steps_lost")}


def _serve_bench_setup():
    """Shared model/mesh setup for the serving benches: the llama-style
    decode shape (or the APEX_TPU_SERVE_SMOKE=1 tiny variant for the
    1-core CPU host), with num_query_groups * kv_channels = 256 so the
    K/V row is exactly one 256-lane quantization block per position.
    Returns ``(smoke, cfg, model, params, num_slots, mesh)``."""
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.transformer import parallel_state
    from jax.sharding import Mesh

    parallel_state.destroy_model_parallel()
    smoke = os.environ.get("APEX_TPU_SERVE_SMOKE") == "1"
    cfg = TransformerConfig(
        hidden_size=128 if smoke else 1024,
        num_layers=2 if smoke else 16,
        num_attention_heads=4 if smoke else 16,
        vocab_size=512 if smoke else 32000,
        max_position_embeddings=128 if smoke else 2048,
        compute_dtype=jnp.bfloat16, use_flash_attention=False,
        normalization="rmsnorm", position_embedding_type="rope",
        activation="swiglu",
        num_query_groups=4 if smoke else 4,
        ffn_hidden_size=256 if smoke else 2816)
    model = GPTModel(cfg, decode=True)
    rng = np.random.RandomState(0)
    params = GPTModel(cfg).init(
        jax.random.PRNGKey(0),
        jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8))))["params"]
    num_slots = 8
    devices = jax.devices()
    mesh = (Mesh(np.asarray(devices), ("data",))
            if len(devices) > 1 and num_slots % len(devices) == 0
            else None)
    return smoke, cfg, model, params, num_slots, mesh


def bench_serve_decode(requests, steps, *, cache_mode="bf16",
                       with_int8=True):
    """Continuous-batching serve bench (apex_tpu.serving): a
    ServeEngine AOT-compiles its whole (batch-bucket, seq-bucket)
    ladder at startup, then replays TWO deterministic synthetic
    many-user traces (Poisson arrivals in decode ticks, mixed
    prompt/output lengths, different seeds) through the SAME
    executables — the emitted ``compile_count`` is the ladder size and
    ``recompiles_trace_b`` must be 0: traffic shape changed, compiled
    code did not (the ROADMAP item-3 acceptance; the compile watcher
    counts process-wide backend compiles across trace B).

    The headline number is trace-B (warm-engine) tokens/sec; p50/p99
    TTFT and per-token latency come from the scheduler's wall-clock
    accounting (eligible -> first token, so queueing-for-a-slot counts).
    ``kv_cache_bytes`` is reported for the bf16 store next to the int8
    store (blockwise symmetric quantization with fp32 scales per block
    — parallel/compression.py pointed at the cache) and the
    scale-inclusive reduction vs an fp32 cache (docs/serving.md has the
    worked table; the int8 run also replays trace A so the quantized
    path is exercised, not just sized).

    ``requests`` sizes each trace; ``steps`` scales the per-request
    output lengths. APEX_TPU_SERVE_SMOKE=1 shrinks the model for the
    CPU host (the tier-1 e2e path; the on-chip run uses the llama-style
    decode shape). Returns a dict for in-process callers.
    """
    from apex_tpu.serving import ServeConfig, ServeEngine, synthetic_trace
    from apex_tpu.telemetry import CompileWatcher, compile_watch

    smoke, cfg, model, params, num_slots, mesh = _serve_bench_setup()
    serve_cfg = ServeConfig(
        batch_buckets=(2, 4, 8),
        prefill_buckets=(16, 32) if smoke else (32, 64, 128),
        num_slots=num_slots, cache_mode=cache_mode,
        eos_token_id=None, temperature=0.0)
    max_new = (max(steps // 2, 2), steps, steps * 2)
    plens = (4, 8, 12, 24) if smoke else (8, 24, 48, 96)

    def trace(seed, arrival_scale):
        return synthetic_trace(
            requests, seed=seed, mean_interarrival=arrival_scale,
            prompt_lens=plens, max_new=max_new,
            vocab_size=cfg.vocab_size)

    watcher = CompileWatcher(enabled=True)
    engine = ServeEngine(model, params, serve_cfg, mesh=mesh,
                         watcher=watcher)
    # trace A: engine warm-up traffic (bursty: short inter-arrival)
    engine.serve(trace(0, 0.25))
    # trace B: different arrival pattern through the SAME executables;
    # any backend compile here means shape discipline broke
    compiles_before = compile_watch.backend_compiles()[0]
    t0 = time.perf_counter()
    _, stats_b = engine.serve(trace(1, 1.0))
    dt = time.perf_counter() - t0
    recompiles_b = compile_watch.backend_compiles()[0] - compiles_before

    kv_bytes = engine.kv_cache_bytes()
    kv_fp32 = engine.spec.total_bytes(kv_itemsize=4)
    int8_fields = {}
    if with_int8 and cache_mode != "int8":
        import dataclasses as _dc

        eng8 = ServeEngine(
            model, params, _dc.replace(serve_cfg, cache_mode="int8"),
            mesh=mesh, watcher=watcher)
        _, stats8 = eng8.serve(trace(0, 0.25))
        int8_fields = {
            "kv_cache_bytes_int8": eng8.kv_cache_bytes(),
            "kv_cache_reduction_vs_fp32": round(
                kv_fp32 / eng8.kv_cache_bytes(), 3),
            "int8_tokens_per_sec": round(
                stats8["tokens_per_sec"] or 0.0, 2),
        }

    if engine.memory_report is not None:
        rep = engine.memory_report
        _PENDING_MEASURED["peak_hbm_bytes"] = rep["peak_bytes"]
        if rep.get("headroom_frac") is not None:
            _PENDING_MEASURED["hbm_headroom_pct"] = round(
                rep["headroom_frac"] * 100.0, 2)
    _stage_aot_compile_count(engine.compile_count)

    avg_len = float(np.mean(plens)) + steps
    flops = stats_b["tokens_generated"] * _transformer_fwd_flops_per_token(
        cfg, int(avg_len))
    tokens_per_sec = stats_b["tokens_per_sec"] or 0.0
    ret = {
        "tokens_per_sec": round(tokens_per_sec, 2),
        "compile_count": engine.compile_count,
        "recompiles_trace_b": int(recompiles_b),
        "ttft_p50_ms": round(stats_b["ttft_p50_ms"] or 0.0, 3),
        "ttft_p99_ms": round(stats_b["ttft_p99_ms"] or 0.0, 3),
        "tok_latency_p50_ms": round(
            stats_b["tok_latency_p50_ms"] or 0.0, 3),
        "tok_latency_p99_ms": round(
            stats_b["tok_latency_p99_ms"] or 0.0, 3),
        "kv_cache_bytes": kv_bytes,
        **int8_fields,
    }
    _emit("serve_decode_tokens_per_sec_per_chip", tokens_per_sec,
          "tokens/sec", flops, 1, dt,
          requests=requests, num_slots=num_slots,
          data_devices=int(mesh.devices.size) if mesh is not None else 1,
          cache_mode=cache_mode,
          kv_cache_bytes_fp32_equiv=kv_fp32,
          requests_completed=stats_b["requests_completed"],
          decode_steps=stats_b["decode_steps"],
          prefill_calls=stats_b["prefill_calls"],
          **{k: v for k, v in ret.items()
             if k not in ("tokens_per_sec", "compile_count")},
          **_comm_fields(training=False))
    return ret


def _serve_spec_setup():
    """Model pair for the speculative serving bench: a deeper target
    whose layers beyond the first are DAMPED (output contributions
    scaled by 0.25 — the residual stream stays backbone-dominated, the
    stand-in for a well-distilled draft/target pair; an undamped
    random-init deep stack gives ~0 draft agreement, which measures
    nothing) and a 1-layer draft sharing the target's embedding, first
    layer, and head. ``max_position_embeddings`` is larger than the
    serve_decode shape on purpose: speculative verification amortizes
    the per-step KV-cache read, so its win GROWS with context length.
    Returns ``(smoke, cfg, model, params, draft, dparams)``."""
    import dataclasses as _dc

    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    smoke = os.environ.get("APEX_TPU_SERVE_SMOKE") == "1"
    cfg = TransformerConfig(
        hidden_size=128 if smoke else 1024,
        num_layers=6 if smoke else 16,
        num_attention_heads=4 if smoke else 16,
        vocab_size=512 if smoke else 32000,
        max_position_embeddings=256 if smoke else 2048,
        compute_dtype=jnp.bfloat16, use_flash_attention=False,
        normalization="rmsnorm", position_embedding_type="rope",
        activation="swiglu", num_query_groups=4,
        ffn_hidden_size=256 if smoke else 2816)
    model = GPTModel(cfg, decode=True)
    rng = np.random.RandomState(0)
    params = dict(GPTModel(cfg).init(
        jax.random.PRNGKey(0),
        jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8))))["params"])
    params["transformer"] = {
        name: jax.tree_util.tree_map(
            lambda l: l * (1.0 if name == "layer_0" else 0.25), layer)
        for name, layer in params["transformer"].items()}
    dcfg = _dc.replace(cfg, num_layers=1)
    draft = GPTModel(dcfg, decode=True)
    dparams = {
        "word_embeddings": params["word_embeddings"],
        "final_layernorm": params["final_layernorm"],
        "lm_head": params["lm_head"],
        "transformer": {"layer_0": params["transformer"]["layer_0"]},
    }
    return smoke, cfg, model, params, draft, dparams


def bench_serve_spec(requests, steps):
    """Speculative + prefix-cached serving bench (ROADMAP item 1): ONE
    target model served two ways over the SAME shared-prefix Poisson
    trace (~80% of requests open with one system prompt — the
    realistic millions-of-users shape):

    (a) the plain continuous-batching engine — the ``serve_decode``
    baseline, measured in-invocation so the comparison shares the
    trace, the host, and the load; (b) a ``ServeConfig(draft_model=,
    prefix_cache=True)`` engine: every decode dispatch drafts
    ``num_draft_tokens`` greedily with the cheap draft, verifies the
    window in ONE chunked target forward (fused in-graph acceptance /
    rollback epilogue, per-slot mixed acceptance), and shared prefixes
    seed KV rows from the host-side prefix store so only the suffix
    bucket prefills.

    The headline value is the speculative engine's
    ``accepted_tokens_per_sec`` — every emitted token is a target
    argmax over its own prefix, so the streams are TOKEN-IDENTICAL to
    the baseline engine (emitted as ``token_identical``; the ISSUE-12
    acceptance asks >= 1.5x the baseline with ``compile_count`` still
    == the ladder size and zero warm-trace recompiles). The round-17
    contract fields ride along: ``acceptance_rate``,
    ``prefix_hit_rate``, ``ttft_p50_prefix_hit_ms``.
    """
    import dataclasses as _dc

    from apex_tpu.serving import ServeConfig, ServeEngine, synthetic_trace
    from apex_tpu.telemetry import CompileWatcher, compile_watch

    smoke, cfg, model, params, draft, dparams = _serve_spec_setup()
    num_slots = 8
    devices = jax.devices()
    from jax.sharding import Mesh

    mesh = (Mesh(np.asarray(devices), ("data",))
            if len(devices) > 1 and num_slots % len(devices) == 0
            else None)
    base_cfg = ServeConfig(
        batch_buckets=(2, 4, 8),
        prefill_buckets=(16, 32) if smoke else (64, 128),
        num_slots=num_slots, cache_mode="bf16",
        eos_token_id=None, temperature=0.0)
    shared_len = 12 if smoke else 40
    plens = (4, 8, 12) if smoke else (8, 16, 24)
    max_new = (steps * 4, steps * 6)

    def trace(seed):
        return synthetic_trace(
            requests, seed=seed, mean_interarrival=0.1,
            prompt_lens=plens, max_new=max_new,
            vocab_size=cfg.vocab_size, shared_prefix_len=shared_len,
            shared_frac=0.8)

    watcher = CompileWatcher(enabled=True)
    # (a) baseline: the plain engine (= serve_decode semantics)
    base_eng = ServeEngine(model, params, base_cfg, mesh=mesh,
                           watcher=watcher)
    base_eng.serve(trace(0))                      # warm-up trace
    done_base, stats_base = base_eng.serve(trace(1))
    base_tps = stats_base["tokens_per_sec"] or 0.0

    # (b) speculative + prefix-cached engine, same ladder shape
    spec_cfg = _dc.replace(
        base_cfg, draft_model=draft, draft_params=dparams,
        num_draft_tokens=4, prefix_cache=True, prefix_min_len=6,
        prefix_max_entries=16)
    spec_eng = ServeEngine(model, params, spec_cfg, mesh=mesh,
                           watcher=watcher)
    spec_eng.serve(trace(0))                      # warm-up trace
    compiles_before = compile_watch.backend_compiles()[0]
    t0 = time.perf_counter()
    done_spec, stats_spec = spec_eng.serve(trace(1))
    dt = time.perf_counter() - t0
    recompiles = compile_watch.backend_compiles()[0] - compiles_before

    base_tokens = {c.rid: np.asarray(c.tokens).tolist()
                   for c in done_base}
    spec_tokens = {c.rid: np.asarray(c.tokens).tolist()
                   for c in done_spec}
    identical = base_tokens == spec_tokens

    if spec_eng.memory_report is not None:
        rep = spec_eng.memory_report
        _PENDING_MEASURED["peak_hbm_bytes"] = rep["peak_bytes"]
        if rep.get("headroom_frac") is not None:
            _PENDING_MEASURED["hbm_headroom_pct"] = round(
                rep["headroom_frac"] * 100.0, 2)
    _stage_aot_compile_count(spec_eng.compile_count)

    accepted_tps = stats_spec["accepted_tokens_per_sec"] or 0.0
    avg_len = float(np.mean(plens)) + shared_len + float(
        np.mean(max_new))
    flops = stats_spec["tokens_generated"] * \
        _transformer_fwd_flops_per_token(cfg, int(avg_len))
    ret = {
        "accepted_tokens_per_sec": round(accepted_tps, 2),
        "baseline_tokens_per_sec": round(base_tps, 2),
        "speedup_vs_decode": round(accepted_tps / base_tps, 3)
        if base_tps else None,
        "acceptance_rate": stats_spec["acceptance_rate"],
        "spec_proposed": stats_spec["spec_proposed"],
        "spec_accepted": stats_spec["spec_accepted"],
        "num_draft_tokens": spec_cfg.num_draft_tokens,
        "prefix_hit_rate": stats_spec["prefix_hit_rate"],
        "prefix_hits": stats_spec["prefix_hits"],
        "prefix_store_bytes": stats_spec["prefix_store_bytes"],
        "ttft_p50_prefix_hit_ms": round(
            stats_spec["ttft_p50_prefix_hit_ms"], 3)
        if stats_spec["ttft_p50_prefix_hit_ms"] is not None else None,
        "ttft_p50_prefix_miss_ms": round(
            stats_spec["ttft_p50_prefix_miss_ms"], 3)
        if stats_spec["ttft_p50_prefix_miss_ms"] is not None else None,
        "token_identical": bool(identical),
        "kv_cache_bytes_draft": spec_eng.draft_kv_cache_bytes(),
        "compile_count": spec_eng.compile_count,
        "recompiles_spec": int(recompiles),
    }
    _emit("serve_spec_accepted_tokens_per_sec", accepted_tps,
          "tokens/sec", flops, 1, dt,
          requests=requests, num_slots=num_slots,
          data_devices=int(mesh.devices.size) if mesh is not None else 1,
          shared_prefix_len=shared_len,
          decode_steps=stats_spec["decode_steps"],
          prefill_calls=stats_spec["prefill_calls"],
          **{k: v for k, v in ret.items()
             if k not in ("accepted_tokens_per_sec", "compile_count")},
          **_comm_fields(training=False))
    return ret


def bench_serve_chaos(requests, steps):
    """Serving fault-tolerance chaos bench (apex_tpu.serving.robust):
    ONE engine serves (a) a clean Poisson trace — the goodput
    baseline, (b) the SAME trace with one slot-NaN injection (the
    per-slot quarantine evicts exactly one request as ``poisoned``
    while healthy slots keep decoding) and one transient decode
    failure (retried with capped backoff; zero requests fail), and
    (c) a request storm through a bounded pending queue (the overflow
    sheds with recorded ``serve/rejected`` events instead of growing
    the queue without bound).

    Headline value is the chaos-run goodput (tokens of ``length``/
    ``eos`` completions per second); ``goodput_ratio`` is chaos
    goodput tokens / clean goodput tokens (the ISSUE-7 acceptance
    floor is 0.9 — one quarantined request is the only loss).
    ``compile_count`` must still equal the bucket-ladder size and
    ``recompiles_chaos`` 0: every fault-tolerance path is host-side
    policy, so injected chaos compiles nothing.
    """
    import dataclasses as _dc

    from apex_tpu.resilience import faults
    from apex_tpu.serving import (RobustConfig, Scheduler, ServeConfig,
                                  ServeEngine, synthetic_trace)
    from apex_tpu.telemetry import CompileWatcher, compile_watch

    smoke, cfg, model, params, num_slots, mesh = _serve_bench_setup()
    serve_cfg = ServeConfig(
        batch_buckets=(2, 4, 8),
        prefill_buckets=(16, 32) if smoke else (32, 64, 128),
        num_slots=num_slots, cache_mode="bf16",
        eos_token_id=None, temperature=0.0)
    robust = RobustConfig(decode_retries=2, retry_backoff_s=0.01,
                          retry_backoff_cap_s=0.1)
    max_new = (max(steps // 2, 2), steps, steps * 2)
    plens = (4, 8, 12, 24) if smoke else (8, 24, 48, 96)

    def trace():
        return synthetic_trace(
            requests, seed=0, mean_interarrival=0.5,
            prompt_lens=plens, max_new=max_new,
            vocab_size=cfg.vocab_size)

    watcher = CompileWatcher(enabled=True)
    engine = ServeEngine(model, params, serve_cfg, mesh=mesh,
                         watcher=watcher)

    # (a) clean run: the goodput baseline
    _, clean = engine.serve(trace(), robust=robust)
    clean_goodput = clean["goodput_tokens"]

    # (b) chaos run: same trace, one slot-NaN + one transient decode
    # failure, driven step-by-step so the injections target a decode
    # call with >= 2 active slots (quarantine must leave healthy slots
    # decoding — and the whole-batch guard must NOT trip)
    compiles_before = compile_watch.backend_compiles()[0]
    sched = Scheduler(engine, robust=robust)
    for r in trace():
        sched.submit(r)
    nan_armed = fail_armed = False
    t0 = time.perf_counter()
    try:
        while sched.pending or sched.active:
            if not nan_armed and len(sched.active) >= 2:
                faults.arm_slot_nan(sorted(sched.active)[0],
                                    engine._decode_calls)
                nan_armed = True
            elif nan_armed and not fail_armed and sched.active:
                faults.arm_decode_failure(engine._decode_calls,
                                          transient=True)
                fail_armed = True
            if not sched.active and sched.pending and \
                    min(r.arrival for r in sched.pending) > sched.tick:
                sched.tick = min(r.arrival for r in sched.pending)
            sched.step()
    finally:
        faults.disarm_slot_nan()
        faults.disarm_decode_failure()
    dt = time.perf_counter() - t0
    sched._t_end = time.perf_counter()
    sched._census_event()
    chaos = sched.stats()
    recompiles = compile_watch.backend_compiles()[0] - compiles_before

    # (c) request storm through a bounded queue: shedding, not OOM
    storm_sched = Scheduler(engine, robust=_dc.replace(
        robust, max_pending=max(requests // 2, 2),
        admission_policy="shed_oldest"))
    for r in faults.request_storm(requests * 2,
                                  vocab_size=cfg.vocab_size):
        storm_sched.submit(r)
    storm_sched.run()
    storm = storm_sched.stats()

    _stage_aot_compile_count(engine.compile_count)
    goodput = chaos["goodput_tokens_per_sec"] or 0.0
    avg_len = float(np.mean(plens)) + steps
    flops = chaos["goodput_tokens"] * _transformer_fwd_flops_per_token(
        cfg, int(avg_len))
    ret = {
        "goodput_tokens_per_sec": round(goodput, 2),
        "goodput_ratio": round(
            chaos["goodput_tokens"] / clean_goodput, 4)
        if clean_goodput else None,
        "shed_rate": storm["shed_rate"],
        "poisoned_evictions": chaos["requests_quarantined"],
        "expired": chaos["requests_expired"],
        "failed_requests": chaos["requests_failed"],
        "decode_retries": chaos["decode_retries"],
        "ttft_p99_ms": round(chaos["ttft_p99_ms"] or 0.0, 3),
        "tok_latency_p99_ms": round(
            chaos["tok_latency_p99_ms"] or 0.0, 3),
        "compile_count": engine.compile_count,
        "recompiles_chaos": int(recompiles),
    }
    _emit("serve_chaos_goodput_tokens_per_sec", goodput,
          "tokens/sec", flops, 1, dt,
          requests=requests, num_slots=num_slots,
          clean_goodput_tokens=clean_goodput,
          chaos_goodput_tokens=chaos["goodput_tokens"],
          requests_ok=chaos["requests_ok"],
          storm_rejected=storm["requests_rejected"],
          **{k: v for k, v in ret.items()
             if k not in ("goodput_tokens_per_sec", "compile_count")},
          **_comm_fields(training=False))
    return ret


def bench_serve_fleet(requests, steps):
    """Multi-replica serving-fleet chaos bench (apex_tpu.serving.fleet):
    a 2-replica fleet (distinct mesh slices when the host has the
    devices; meshless shared-device replicas on the 1-core CPU smoke
    host) serves (a) a clean diurnal+burst trace — the goodput and
    token-stream baseline — and (b) the SAME trace with
    ``inject_replica_loss`` killing replica 0 mid-trace: every
    in-flight request of the dead replica must finish on the survivor
    (re-prefill from prompt + emitted tokens; greedy outputs
    token-identical to the clean leg), the dead replica respawns and
    re-registers its AOT ladder under a fresh generation name, and the
    rebalance latency (loss detection -> last migrated request
    re-dispatched) is measured.

    Headline value is the chaos-leg fleet tokens/sec; the emitted line
    carries the round-16 contract — per-tier p99 TTFT
    (``ttft_p99_ms_interactive`` / ``ttft_p99_ms_batch``),
    ``rebalance_latency_ms``, ``replicas_respawned`` — next to
    ``goodput_ratio`` (chaos goodput tokens / clean; the acceptance
    floor is 0.9), ``migrated_requests``, ``lost_requests`` (must be
    0), ``token_identical``, and ``compile_count`` == the PER-REPLICA
    ladder size with ``recompiles_chaos == 0`` (the respawned ladder
    registers under fresh watcher names, so any counted recompile is a
    real signature drift).
    """
    from apex_tpu.resilience import faults
    from apex_tpu.serving import (FleetConfig, ServeConfig, ServeFleet,
                                  diurnal_trace)
    from apex_tpu.telemetry import CompileWatcher

    smoke, cfg, model, params, _, _ = _serve_bench_setup()
    serve_cfg = ServeConfig(
        batch_buckets=(2, 4),
        prefill_buckets=(16, 32) if smoke else (32, 64, 128),
        num_slots=4, cache_mode="bf16",
        eos_token_id=None, temperature=0.0)
    fleet_cfg = FleetConfig(num_replicas=2, respawn_delay_ticks=1)
    # migration bound: the continuation prompt (orig + emitted) must
    # fit the widest prefill bucket, so cap max_new accordingly
    plens = (4, 8, 12) if smoke else (8, 24, 48)
    widest = serve_cfg.prefill_buckets[-1]
    max_new = tuple(min(m, widest - max(plens))
                    for m in (max(steps // 2, 2), steps, steps * 2))

    def trace():
        return diurnal_trace(
            requests, seed=0, prompt_lens=plens, max_new=max_new,
            vocab_size=cfg.vocab_size, base_interarrival=0.6,
            burst_at=1.0, burst_n=max(requests // 4, 2),
            batch_every=4)

    watcher = CompileWatcher(enabled=True)

    def build():
        return ServeFleet(model, params, serve_cfg, fleet_cfg,
                          watcher=watcher)

    # (a) clean leg: goodput + token-stream baseline
    fleet_a = build()
    clean_done = fleet_a.run(trace())
    clean = fleet_a.stats()
    clean_tokens = {c.rid: np.asarray(c.tokens).tolist()
                    for c in clean_done}

    # (b) chaos leg: kill replica 0 mid-trace
    fleet_b = build()
    recompiles_before = watcher.recompile_count()
    t0 = time.perf_counter()
    with faults.inject_replica_loss(0, 3):
        chaos_done = fleet_b.run(trace())
    dt = time.perf_counter() - t0
    chaos = fleet_b.stats()
    recompiles = watcher.recompile_count() - recompiles_before
    chaos_tokens = {c.rid: np.asarray(c.tokens).tolist()
                    for c in chaos_done}
    identical = chaos_tokens == clean_tokens

    ladder = (len(serve_cfg.batch_buckets)
              * len(serve_cfg.prefill_buckets)
              + len(serve_cfg.batch_buckets))
    _stage_aot_compile_count(ladder)
    tokens_per_sec = chaos["tokens_per_sec"] or 0.0
    avg_len = float(np.mean(plens)) + float(np.mean(max_new))
    flops = chaos["tokens_generated"] * _transformer_fwd_flops_per_token(
        cfg, int(avg_len))
    ret = {
        "tokens_per_sec": round(tokens_per_sec, 2),
        "goodput_ratio": round(
            chaos["goodput_tokens"] / clean["goodput_tokens"], 4)
        if clean["goodput_tokens"] else None,
        "ttft_p99_ms_interactive": round(
            chaos["ttft_p99_ms_interactive"], 3)
        if chaos["ttft_p99_ms_interactive"] is not None else None,
        "ttft_p99_ms_batch": round(chaos["ttft_p99_ms_batch"], 3)
        if chaos["ttft_p99_ms_batch"] is not None else None,
        "rebalance_latency_ms": chaos["rebalance_latency_ms"],
        "replicas_respawned": chaos["replicas_respawned"],
        "migrated_requests": chaos["migrated_requests"],
        "lost_requests": chaos["lost_requests"],
        "token_identical": bool(identical),
        "compile_count": ladder,
        "recompiles_chaos": int(recompiles),
    }
    _emit("serve_fleet_tokens_per_sec", tokens_per_sec,
          "tokens/sec", flops, 1, dt,
          requests=len(trace()), replicas=2,
          num_slots_per_replica=serve_cfg.num_slots,
          clean_goodput_tokens=clean["goodput_tokens"],
          chaos_goodput_tokens=chaos["goodput_tokens"],
          requests_ok=chaos["requests_ok"],
          replicas_quarantined=chaos["replicas_quarantined"],
          **{k: v for k, v in ret.items()
             if k not in ("tokens_per_sec", "compile_count")},
          **_comm_fields(training=False))
    return ret


def bench_serve_migrate(requests, steps):
    """KV-state migration cost bench (round-23 contract): measures the
    constant-cost claim of the fleet handoff path head-on.

    Leg 1 (microbench, the headline): a donor engine serves a request
    to a SHORT and a LONG context, then the exact survivor-side
    handoff sequence runs timed — ``extract_kv_state`` (host payload +
    crc32), checksum verify, prefix-store insert keyed by the
    continuation prefix, and the survivor's SEEDED prefill (1-token
    suffix = smallest seq bucket). Because the extracted rows are
    full-length slot buffers and the seeded suffix never grows, the
    wall clock is flat in context length: ``migration_ms_long_ctx /
    migration_ms_short_ctx`` must stay <= 1.25. The linear comparator
    is measured next to it: a cold token re-prefill of the same carry
    (prefix miss, bucket >= context), whose long/short ratio is
    emitted as ``reprefill_ratio`` — the cost curve migration avoids.

    Leg 2 (fleet counters): a 2-replica fleet with the shared prefix
    store serves a diurnal trace while ``inject_replica_loss`` kills
    replica 0 mid-trace; the emitted ``kv_handoff_bytes``,
    ``fallback_reprefills`` (must be 0 on the clean path), and
    ``fleet_prefix_hit_rate`` come from the fleet's own accounting of
    that chaos leg, with zero lost requests.
    """
    from apex_tpu.resilience import faults
    from apex_tpu.serving import (FleetConfig, ServeConfig, ServeEngine,
                                  ServeFleet, diurnal_trace)
    from apex_tpu.serving.engine import kv_payload_crc
    from apex_tpu.telemetry import CompileWatcher

    smoke, cfg, model, params, _, _ = _serve_bench_setup()
    buckets = (4, 16, 64) if smoke else (8, 64, 512)
    # carry = prompt + emitted must land exactly in the mid/widest
    # buckets so the re-prefill comparator prices the real ladder rungs
    emit_n = 4
    ctx_short = buckets[1] - emit_n
    ctx_long = buckets[2] - emit_n
    donor_cfg = ServeConfig(
        batch_buckets=(2,), prefill_buckets=buckets, num_slots=4,
        cache_mode="bf16", eos_token_id=None, temperature=0.0)
    surv_cfg = ServeConfig(
        batch_buckets=(2,), prefill_buckets=buckets, num_slots=6,
        cache_mode="bf16", eos_token_id=None, temperature=0.0,
        prefix_cache=True, prefix_min_len=2)
    watcher = CompileWatcher(enabled=True)
    donor = ServeEngine(model, params, donor_cfg, watcher=watcher)
    surv = ServeEngine(model, params, surv_cfg, watcher=watcher)
    rng = np.random.RandomState(0)

    def carry_for(ctx):
        """Serve a fresh prompt of length ``ctx`` on the donor for
        ``emit_n`` greedy tokens; returns (carry_tokens, payload)."""
        prompt = rng.randint(0, cfg.vocab_size, (ctx,)).astype(np.int32)
        toks = [int(donor.prefill([0], [prompt],
                                  pad_slot_ids=[1])[0])]
        for _ in range(emit_n - 1):
            nxt, _fin = donor.decode(
                [0], np.asarray([toks[-1]], np.int32),
                pad_slot_ids=[1])
            toks.append(int(nxt[0]))
        payload = donor.extract_kv_state([0])[0]
        return np.concatenate([prompt, np.asarray(toks, np.int32)]), \
            payload

    reps = 3
    t_total = time.perf_counter()

    def measure(ctx, slot):
        """Median timed handoff + cold-reprefill pair at one context
        length; also returns the handoff payload byte count."""
        mig, rep, nbytes = [], [], 0
        for r in range(reps):
            carry, payload = carry_for(ctx)
            t0 = time.perf_counter()
            if kv_payload_crc(payload) != payload["crc"]:
                raise AssertionError("kv payload checksum broke in "
                                     "transit — migration bench void")
            cut = min(int(payload["length"]), len(carry) - 1)
            surv.prefix_store.insert(carry[:cut], payload["rows"],
                                     payload.get("draft_rows"))
            jax.block_until_ready(surv.prefill([slot], [carry],
                                               pad_slot_ids=[5]))
            mig.append((time.perf_counter() - t0) * 1e3)
            if surv.last_prefill_hits[0] != cut:
                raise AssertionError(
                    "seeded prefill missed the handoff entry "
                    f"(hit={surv.last_prefill_hits[0]}, cut={cut})")
            nbytes = int(sum(
                l.nbytes for l in jax.tree_util.tree_leaves(
                    (payload["rows"], payload.get("draft_rows")))))
            # comparator: the same carry cold — a prefix miss pays the
            # full bucket >= context, the linear curve migration dodges
            cold = rng.randint(0, cfg.vocab_size,
                               (len(carry),)).astype(np.int32)
            t0 = time.perf_counter()
            jax.block_until_ready(surv.prefill([slot + 1], [cold],
                                               pad_slot_ids=[5]))
            rep.append((time.perf_counter() - t0) * 1e3)
        return sorted(mig)[reps // 2], sorted(rep)[reps // 2], nbytes

    mig_short, rep_short, _ = measure(ctx_short, 0)
    mig_long, rep_long, handoff_bytes_one = measure(ctx_long, 2)
    migration_ratio = mig_long / mig_short if mig_short else None
    reprefill_ratio = rep_long / rep_short if rep_short else None

    # leg 2: the fleet's own chaos-path accounting for the handoff
    # counters the schema carries
    fleet_cfg = FleetConfig(num_replicas=2, respawn_delay_ticks=1)
    plens = (4, 8, 12) if smoke else (8, 24, 48)
    widest = buckets[-1]
    max_new = tuple(min(m, widest - max(plens))
                    for m in (max(steps // 2, 2), steps, steps * 2))
    fleet_serve_cfg = ServeConfig(
        batch_buckets=(2,), prefill_buckets=buckets, num_slots=4,
        cache_mode="bf16", eos_token_id=None, temperature=0.0,
        prefix_cache=True, prefix_min_len=2)
    fleet = ServeFleet(model, params, fleet_serve_cfg, fleet_cfg,
                       watcher=watcher)
    with faults.inject_replica_loss(0, 3):
        fleet.run(diurnal_trace(
            requests, seed=0, prompt_lens=plens, max_new=max_new,
            vocab_size=cfg.vocab_size, base_interarrival=0.6,
            burst_at=1.0, burst_n=max(requests // 4, 2),
            batch_every=4))
    fl = fleet.stats()

    dt = time.perf_counter() - t_total
    ladder = (len(donor_cfg.batch_buckets) * len(buckets)
              + len(donor_cfg.batch_buckets))
    _stage_aot_compile_count(ladder)
    flops = emit_n * _transformer_fwd_flops_per_token(cfg, ctx_long)
    ret = {
        "migration_ms_short_ctx": round(mig_short, 3),
        "migration_ms_long_ctx": round(mig_long, 3),
        "migration_ratio": round(migration_ratio, 4)
        if migration_ratio is not None else None,
        "reprefill_ms_short_ctx": round(rep_short, 3),
        "reprefill_ms_long_ctx": round(rep_long, 3),
        "reprefill_ratio": round(reprefill_ratio, 4)
        if reprefill_ratio is not None else None,
        "kv_handoff_bytes": fl["kv_handoff_bytes"],
        "fallback_reprefills": fl["kv_fallback_reprefills"],
        "fleet_prefix_hit_rate": round(fl["fleet_prefix_hit_rate"], 4)
        if fl["fleet_prefix_hit_rate"] is not None else None,
        "kv_handoffs": fl["kv_handoffs"],
        "lost_requests": fl["lost_requests"],
        "compile_count": ladder,
    }
    _emit("serve_migrate_migration_ms", mig_long, "ms", flops, 1, dt,
          ctx_short=ctx_short + emit_n, ctx_long=ctx_long + emit_n,
          handoff_payload_bytes=handoff_bytes_one,
          migrated_requests=fl["migrated_requests"],
          requests_ok=fl["requests_ok"],
          **{k: v for k, v in ret.items() if k != "compile_count"},
          **_comm_fields(training=False))
    return ret


def bench_trace_overhead(batch, steps, *, hidden=128, layers=2,
                         heads=4, vocab=128, seq=16):
    """Causal-tracing tax (round-24 contract): the SAME compiled mesh2d
    train step driven through the supervisor-style host loop — a
    ``trace_context`` + ``train/step`` span per step, exactly what
    ``resilience.supervisor`` wraps around ``step_fn`` — twice:

    - **off**: a fresh disabled registry (the library default). The
      proof obligations ride in-bench: the disabled leg must record
      ZERO events (the registry's ``event`` is counted via a shim and
      must never fire), mint no span ids, and leave the ambient
      TraceContext untouched — the zero-overhead-off contract of
      docs/observability.md, asserted, not assumed;
    - **on**: a fresh registry with a JSONL sink. ``span_count`` is
      read back from the file it wrote (>= 2 events/step: span_begin +
      span), and ``tracing_overhead_pct`` is the on-vs-off per-step
      delta — the number the 'leave tracing on in production' claim
      rests on.

    Both legs execute the one compiled program (trace-time spans inside
    ``jit`` never re-fire at execution), so the delta prices only the
    host-side identity + event-write path.
    """
    import glob as _glob
    import tempfile

    from apex_tpu.parallel import mesh2d
    from apex_tpu.telemetry import current_trace, span, trace_context
    from apex_tpu.telemetry.registry import MetricsRegistry, use_registry
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    devices = jax.devices()
    multi = len(devices) >= 2 and len(devices) % 2 == 0
    mesh = mesh2d.mesh_2d(2 if multi else 1, None if multi else 1)
    seg_params = mesh2d.gpt2_init(hidden=hidden, layers=layers,
                                  heads=heads, vocab=vocab, max_seq=seq)
    step, state = mesh2d.build_train_step(
        mesh, seg_params, hidden=hidden, heads=heads, mode="baseline")
    tokens, labels = mesh2d.make_batch(mesh, batch_per_replica=batch,
                                       seq=seq, vocab=vocab)
    out = step(*state, tokens, labels)
    float(out[2])                       # compile, shared by both legs
    carry = out[:2]                     # state buffers are donated —
                                        # thread the carry through legs

    def timed_loop(reg, carry):
        o = step(*carry, tokens, labels)
        float(o[2])                     # steady warmup
        t0 = time.perf_counter()
        for i in range(steps):
            with trace_context(registry=reg), \
                    span("train/step", registry=reg, step=i):
                o = step(*o[:2], tokens, labels)
        float(o[2])                     # completion barrier
        return (time.perf_counter() - t0) / steps, o[:2]

    # off leg: disabled registry + an event-counting shim that must
    # stay silent, and one probe span proving no ids were minted
    off_reg = MetricsRegistry()
    off_events = []
    _orig_event = off_reg.event
    off_reg.event = lambda *a, **k: (off_events.append(a),
                                     _orig_event(*a, **k))
    with use_registry(off_reg):
        t_off, carry = timed_loop(off_reg, carry)
        probe = span("train/step", registry=off_reg)
        with probe:
            if current_trace() is not None:
                raise AssertionError(
                    "disabled tracing leaked a TraceContext")
    if off_events:
        raise AssertionError(
            f"disabled registry recorded {len(off_events)} event(s) — "
            f"the zero-overhead-off contract is broken")
    if probe.span_id is not None:
        raise AssertionError("disabled tracing minted a span id")

    # on leg: fresh registry with a JSONL sink; span_count read back
    # from what it actually wrote
    on_dir = tempfile.mkdtemp(prefix="apex_trace_overhead_")
    on_reg = MetricsRegistry()
    on_reg.enable(jsonl_dir=on_dir)
    with use_registry(on_reg):
        t_on, carry = timed_loop(on_reg, carry)
    on_reg.disable()
    span_count = 0
    for path in _glob.glob(os.path.join(on_dir, "*.jsonl")):
        with open(path) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") in ("span", "span_begin"):
                    span_count += 1
    if span_count < 2 * steps:
        raise AssertionError(
            f"enabled tracing wrote {span_count} span event(s) for "
            f"{steps} step(s) — expected >= {2 * steps}")

    overhead_pct = ((t_on - t_off) / t_off * 100.0) if t_off else None
    _stage_compile_count(step)
    compile_count = _PENDING_MEASURED.get("compile_count")
    n_params = _tree_size(seg_params)
    dp_world = mesh.shape[mesh2d.DATA_AXIS]
    flops = 6 * batch * dp_world * seq * n_params
    ret = {
        "untraced_step_ms": round(t_off * 1e3, 3),
        "traced_step_ms": round(t_on * 1e3, 3),
        "tracing_overhead_pct": round(overhead_pct, 2)
        if overhead_pct is not None else None,
        "span_count": span_count,
        "disabled_leg_events": len(off_events),
        "spans_per_step": round(span_count / steps, 2),
    }
    _emit("trace_overhead_step_ms", t_on * 1e3, "ms", flops, steps,
          t_on * steps, **ret,
          **_comm_fields(n_elements=n_params, compress=None))
    ret["compile_count"] = compile_count
    return ret


def bench_monitor_overhead(requests, steps):
    """Live-monitoring tax (round-25 contract): the SAME fleet chaos
    leg (2 replicas, ``inject_replica_loss`` killing replica 0
    mid-trace) run twice:

    - **unmonitored**: a fresh DISABLED registry — the library
      default. A :class:`~apex_tpu.telemetry.monitor.Monitor` is still
      constructed against it to prove the zero-overhead-off contract
      head-on: it must come up inert (``enabled`` False, ``poll()``
      -> None) and the registry's ``event`` — shimmed with a counter —
      must see ZERO ``monitor``/``alert`` kind events across the whole
      leg (AssertionError otherwise; lowered programs are untouched by
      construction — the monitor never enters jit);
    - **monitored**: a fresh registry with a JSONL sink, the stock
      rule table tapped in, a background poll loop at 20 ms, and a
      final deterministic ``poll()``. The replica loss must fire the
      ``replica_health`` rule and the respawn must resolve it —
      ``alerts_fired`` >= 1 and ``alerts_firing_final`` == 0 are
      emitted next to the headline ``monitor_overhead_pct``
      (monitored-vs-unmonitored wall-clock delta), the number the
      'leave the monitor on in production' claim rests on.
    """
    import tempfile

    from apex_tpu.resilience import faults
    from apex_tpu.serving import (FleetConfig, ServeConfig, ServeFleet,
                                  diurnal_trace)
    from apex_tpu.telemetry import CompileWatcher, Monitor, default_rules
    from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

    smoke, cfg, model, params, _, _ = _serve_bench_setup()
    serve_cfg = ServeConfig(
        batch_buckets=(2, 4),
        prefill_buckets=(16, 32) if smoke else (32, 64, 128),
        num_slots=4, cache_mode="bf16",
        eos_token_id=None, temperature=0.0)
    fleet_cfg = FleetConfig(num_replicas=2, respawn_delay_ticks=1)
    plens = (4, 8, 12) if smoke else (8, 24, 48)
    widest = serve_cfg.prefill_buckets[-1]
    max_new = tuple(min(m, widest - max(plens))
                    for m in (max(steps // 2, 2), steps, steps * 2))

    def trace():
        return diurnal_trace(
            requests, seed=0, prompt_lens=plens, max_new=max_new,
            vocab_size=cfg.vocab_size, base_interarrival=0.6,
            burst_at=1.0, burst_n=max(requests // 4, 2),
            batch_every=4)

    watcher = CompileWatcher(enabled=True)

    def chaos_leg(reg):
        fleet = ServeFleet(model, params, serve_cfg, fleet_cfg,
                           watcher=watcher)
        t0 = time.perf_counter()
        with faults.inject_replica_loss(0, 3):
            fleet.run(trace())
        return time.perf_counter() - t0, fleet.stats()

    # unmonitored leg: disabled registry, inert monitor, and a shim
    # counting any monitor-plane event that dares to fire
    off_reg = MetricsRegistry()
    off_events = []
    _orig_event = off_reg.event

    def _counting_event(kind, name, **fields):
        if kind in ("monitor", "alert"):
            off_events.append((kind, name))
        return _orig_event(kind, name, **fields)

    off_reg.event = _counting_event
    mon_off = Monitor(off_reg, rules=default_rules())
    if mon_off.enabled or mon_off.poll() is not None:
        raise AssertionError(
            "Monitor on a disabled registry came up live — the "
            "zero-overhead-off contract is broken")
    with use_registry(off_reg):
        t_off, stats_off = chaos_leg(off_reg)
    mon_off.close()
    if off_events:
        raise AssertionError(
            f"disabled leg emitted {len(off_events)} monitor/alert "
            f"event(s) — the zero-overhead-off contract is broken")

    # monitored leg: JSONL sink + stock rules + live poll loop
    on_dir = tempfile.mkdtemp(prefix="apex_monitor_overhead_")
    on_reg = MetricsRegistry()
    on_reg.enable(jsonl_dir=on_dir)
    mon = Monitor(on_reg, rules=default_rules())
    mon.start(interval_s=0.02)
    with use_registry(on_reg):
        t_on, stats_on = chaos_leg(on_reg)
    final = mon.poll()
    rows = mon.alerts()
    mon.close()
    on_reg.disable()
    alerts_fired = sum(r["fired_count"] for r in rows)
    firing_final = final["firing"] if final else None

    ladder = (len(serve_cfg.batch_buckets)
              * len(serve_cfg.prefill_buckets)
              + len(serve_cfg.batch_buckets))
    _stage_aot_compile_count(ladder)
    overhead_pct = ((t_on - t_off) / t_off * 100.0) if t_off else None
    avg_len = float(np.mean(plens)) + float(np.mean(max_new))
    flops = stats_on["tokens_generated"] * \
        _transformer_fwd_flops_per_token(cfg, int(avg_len))
    ret = {
        "unmonitored_run_s": round(t_off, 4),
        "monitored_run_s": round(t_on, 4),
        "monitor_overhead_pct": round(overhead_pct, 2)
        if overhead_pct is not None else None,
        "alerts_fired": int(alerts_fired),
        "alerts_firing_final": firing_final,
        "disabled_leg_monitor_events": len(off_events),
        "replicas_respawned": stats_on["replicas_respawned"],
        "lost_requests": stats_on["lost_requests"],
    }
    _emit("monitor_overhead_pct", overhead_pct or 0.0, "%", flops, 1,
          t_on, requests=requests, replicas=2,
          unmonitored_goodput_tokens=stats_off["goodput_tokens"],
          monitored_goodput_tokens=stats_on["goodput_tokens"],
          **{k: v for k, v in ret.items()
             if k != "monitor_overhead_pct"},
          **_comm_fields(training=False))
    ret["compile_count"] = ladder
    return ret


# The canonical (size, steps) per bench — the ONLY place these defaults
# live; the CLI dispatch below and the sweep tools read them, so a
# tuning change propagates to every caller. Functions resolve lazily so
# `python bench.py` via this table still defers heavy imports to the
# chosen bench.
BENCH_SPECS = {
    "bert": ((64, 30), bench_bert),
    "gpt": ((8192, 15), bench_gpt_long),
    "gpt2": ((8, 20), bench_gpt2),
    "t5": ((16, 20), bench_t5),
    "vit": ((128, 20), bench_vit),
    "whisper": ((8, 15), bench_whisper),
    "moe": ((4, 15), bench_moe),
    "moe_serve": ((2048, 20), bench_moe_serve),
    "mla_decode": ((4096, 64), bench_mla_decode),
    "llama": ((4, 15), bench_llama),
    "decode": ((8, 128), bench_decode),
    "serve_decode": ((24, 16), bench_serve_decode),
    "serve_spec": ((16, 16), bench_serve_spec),
    "serve_chaos": ((24, 16), bench_serve_chaos),
    "serve_fleet": ((16, 8), bench_serve_fleet),
    "serve_migrate": ((8, 6), bench_serve_migrate),
    "trace_overhead": ((4, 30), bench_trace_overhead),
    "monitor_overhead": ((12, 6), bench_monitor_overhead),
    "resnet": ((256, 50), bench_resnet),
    "kernels": ((1024, 5), bench_kernels),
    "fused_cc": ((512, 5), bench_fused_cc),
    "ddp_compressed": ((64, 30), bench_ddp_compressed),
    "ddp_overlapped": ((64, 30), bench_ddp_overlapped),
    "tp_dp": ((4, 10), bench_tp_dp),
    "pp_tp_dp": ((2, 10), bench_pp_tp_dp),
    "ddp_resilience": ((32, 12), bench_ddp_resilience),
    "ddp_numerics": ((32, 12), bench_ddp_numerics),
    "ddp_memwatch": ((32, 12), bench_ddp_memwatch),
    "ddp_recovery": ((32, 18), bench_ddp_recovery),
}


def main():
    from apex_tpu._compile_cache import enable_compile_cache

    _require_tpu_unless_cpu_asked()
    enable_compile_cache()
    _enable_bench_telemetry()

    name = sys.argv[1] if len(sys.argv) > 1 and sys.argv[1] in BENCH_SPECS \
        else None
    if name is not None:
        (size, steps), fn = BENCH_SPECS[name]
        size = int(sys.argv[2]) if len(sys.argv) > 2 else size
        steps = int(sys.argv[3]) if len(sys.argv) > 3 else steps
        return fn(size, steps)

    # default (the driver's metric): resnet, with bare-number argv
    # compatibility (`python bench.py 128 20`)
    (size, steps), fn = BENCH_SPECS["resnet"]
    size = int(sys.argv[1]) if len(sys.argv) > 1 else size
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else steps
    return fn(size, steps)


if __name__ == "__main__":
    try:
        main()
    except (SystemExit, KeyboardInterrupt):
        # operator interrupts are not bench crashes — don't emit the
        # parseable crash line for them
        raise
    except BaseException as e:  # noqa: BLE001 — whoever parses stdout
        # gets a JSON line for a crash too, not an empty stdout with the
        # traceback lost to stderr
        import traceback

        traceback.print_exc()
        _emit_bench_error(f"{type(e).__name__}: {str(e)[:300]}", "crash")
        sys.exit(2)
