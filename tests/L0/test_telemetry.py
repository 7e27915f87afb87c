"""apex_tpu.telemetry: registry/span/xla_cost basics, measured-vs-
modeled collective bytes (ISSUE 2 acceptance), zero-overhead-off.

The comm tests are trace-only where possible: ``record_collective``
fires at trace time (once per compilation == once per step of the
compiled program), so ``jit(...).lower(...)`` is enough to measure a
step's collective bytes without compiling or executing anything —
which keeps the tier-1 wall-clock cost of this file near zero.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import telemetry
from apex_tpu.parallel import compression, distributed
from apex_tpu.telemetry import MetricsRegistry, use_registry
from apex_tpu.telemetry.registry import ENV_DIR

ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_disabled_by_default_records_nothing(monkeypatch,
                                                      tmp_path):
    """The zero-overhead-off contract: with APEX_TPU_TELEMETRY_DIR unset
    (and no programmatic enable), nothing is recorded — instruments are
    no-ops, spans don't land, events don't write."""
    monkeypatch.delenv(ENV_DIR, raising=False)
    reg = MetricsRegistry(jsonl_dir=os.environ.get(ENV_DIR) or None)
    assert not reg.enabled
    with use_registry(reg):
        reg.counter("comm/bytes").inc(123)
        reg.gauge("mfu").set(0.5)
        reg.histogram("h").observe(1.0)
        reg.event("span", "x", duration_s=1.0)
        with telemetry.span("nothing"):
            pass
        # a traced DDP sync records nothing either
        jax.jit(lambda g: distributed._psum_with_policy(
            g, (), False, True, 1.0)).lower(jnp.ones((8,)))
    snap = reg.snapshot()
    snap.pop("ts")  # the capture timestamp is present even when empty
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    assert list(tmp_path.iterdir()) == []


def test_registry_instruments_and_jsonl_sink(tmp_path):
    reg = MetricsRegistry(jsonl_dir=str(tmp_path))
    assert reg.enabled  # a sink dir implies enabled
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)
    reg.gauge("g").set(7)
    for v in (1.0, 3.0):
        reg.histogram("h").observe(v)
    reg.event("custom", "hello", detail=42)
    reg.flush()
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 7.0
    h = snap["histograms"]["h"]
    assert (h["count"], h["min"], h["max"], h["mean"]) == (2, 1.0, 3.0, 2.0)

    files = list(tmp_path.glob("telemetry-rank*.jsonl"))
    assert len(files) == 1
    events = [json.loads(l) for l in files[0].read_text().splitlines()]
    kinds = [e["kind"] for e in events]
    # every sink opens with the clock-anchor header (epoch_unix = wall
    # time at this registry's monotonic ts == 0) — the contract
    # tools/trace_export.py uses to align ranks on one absolute axis
    assert kinds == ["trace_epoch", "custom", "summary"]
    assert events[0]["epoch_unix"] > 0
    assert events[1]["detail"] == 42
    assert events[2]["counters"]["c"] == 3.5


def test_use_registry_scopes_process_wide(tmp_path):
    outer = telemetry.get_registry()
    inner = MetricsRegistry(enabled=True)
    with use_registry(inner):
        assert telemetry.get_registry() is inner
    assert telemetry.get_registry() is outer


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_records_histogram_and_event(tmp_path):
    reg = MetricsRegistry(jsonl_dir=str(tmp_path))
    with use_registry(reg):
        with telemetry.span("unit/test", sync=True, tag="t"):
            pass
        sp = telemetry.Span("unit/manual").start()
        elapsed = sp.stop()
    assert elapsed >= 0.0
    snap = reg.snapshot()
    assert snap["histograms"]["span/unit/test"]["count"] == 1
    assert snap["histograms"]["span/unit/manual"]["count"] == 1
    files = list(tmp_path.glob("*.jsonl"))
    events = [json.loads(l) for l in files[0].read_text().splitlines()]
    span_ev = [e for e in events if e["kind"] == "span"]
    assert span_ev[0]["name"] == "unit/test"
    assert span_ev[0]["tag"] == "t"
    assert span_ev[0]["duration_s"] >= 0.0


def test_span_timing_works_with_telemetry_off():
    """_timers shims onto Span — elapsed must be measured even when the
    registry is disabled."""
    with use_registry(MetricsRegistry()):
        sp = telemetry.Span("off/span").start()
        assert sp.stop() >= 0.0


# ---------------------------------------------------------------------------
# xla cost accounting
# ---------------------------------------------------------------------------

def test_step_cost_and_utilization():
    a = jnp.ones((32, 32), jnp.float32)
    f = jax.jit(lambda x: x @ x)
    cost = telemetry.xla_cost.step_cost(f, a)
    assert cost is not None
    # 2*n^3 matmul flops
    assert cost["flops"] >= 2 * 32 ** 3
    assert cost["bytes_accessed"] > 0
    util = telemetry.xla_cost.utilization(
        cost["flops"], 1e-3, bytes_per_step=cost["bytes_accessed"])
    peak_flops, peak_hbm = telemetry.xla_cost.peak_table()
    assert util["mfu"] == pytest.approx(cost["flops"] / 1e-3 / peak_flops)
    assert util["hbm_util"] == pytest.approx(
        cost["bytes_accessed"] / 1e-3 / peak_hbm)


def test_record_step_cost_sets_mfu_gauge():
    reg = MetricsRegistry(enabled=True)
    with use_registry(reg):
        util = telemetry.xla_cost.record_step_cost(
            {"flops": 1e9, "bytes_accessed": 1e6}, 0.01, registry=reg)
    assert util is not None
    snap = reg.snapshot()
    assert snap["gauges"]["mfu"] == pytest.approx(util["mfu"])
    assert snap["gauges"]["model_flops_per_step_xla"] == 1e9


def test_peak_table_is_keyed_by_device_kind():
    """Published v5e peaks for the kind jax reports on that chip; an
    unknown kind is an error, never another chip's row."""
    assert telemetry.xla_cost.peak_table("TPU v5 lite") == (197e12, 819e9)
    for unknown in ("TPU v4", "tpu", "NVIDIA H100"):
        with pytest.raises(ValueError, match="no published peaks"):
            telemetry.xla_cost.peak_table(unknown)


# ---------------------------------------------------------------------------
# measured vs modeled collective bytes (ISSUE 2 acceptance)
# ---------------------------------------------------------------------------

def _trace_sync_bytes(mesh, n, mode):
    """Trace (never compile/execute) one DDP grad allreduce of n fp32
    elements under ``mode`` and return the comm-counter delta — the
    measured per-step wire bytes."""
    g = jnp.zeros((n,), jnp.float32)
    reg = MetricsRegistry(enabled=True)
    with use_registry(reg):
        def f(x):
            out = distributed.all_reduce_gradients({"w": x}, "dp",
                                                   compress=mode)
            return out[0]["w"] if mode == "int8" else out["w"]

        sharded = jax.shard_map(f, mesh=mesh, in_specs=P(),
                                out_specs=P(), check_vma=False)
        jax.jit(sharded).lower(g)
        return reg.counter_value("comm/bytes"), reg.snapshot()


@pytest.mark.multi_device
def test_measured_psum_bytes_match_estimate(dp_mesh):
    """int8 < bf16 < fp32 measured wire bytes, each within 25% of
    compression.estimate_allreduce_bytes's ring model."""
    mesh = dp_mesh(8)
    n = 4096
    measured = {}
    for mode in (None, "bf16", "int8"):
        measured[mode], snap = _trace_sync_bytes(mesh, n, mode)
        assert snap["counters"]["comm/calls"] >= 1
    assert measured["int8"] < measured["bf16"] < measured[None]
    for mode in (None, "bf16", "int8"):
        est = compression.estimate_allreduce_bytes(n, world=8,
                                                   compress=mode)
        assert abs(measured[mode] / est - 1.0) < 0.25, (
            f"mode={mode}: measured {measured[mode]} vs modeled {est}")
    # fp32/bf16 carry no scale exchange, so the model is exact
    assert measured[None] == compression.estimate_allreduce_bytes(n,
                                                                  world=8)


@pytest.mark.multi_device
def test_zero_optimizer_collectives_recorded(dp_mesh):
    """The ZeRO grad reduce-scatter + param all-gather sites record
    their actual payloads (trace-only through the real optimizer)."""
    from apex_tpu.contrib.optimizers import DistributedFusedAdam

    mesh = dp_mesh(8)
    reg = MetricsRegistry(enabled=True)
    with use_registry(reg):
        opt = DistributedFusedAdam(lr=1e-3, axis_name="dp")

        def f(params, grads):
            state = opt.init(params)
            new_p, _ = opt.step(grads, state, params)
            return new_p

        tree = {"w": jnp.zeros((1024,), jnp.float32)}
        jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                              out_specs=P(), check_vma=False)).lower(
            tree, tree)
    snap = reg.snapshot()
    # per-rank, 1024 fp32 elements (already world*4-aligned): scatter
    # ships (w-1)/w of the full 4096 B, gather (w-1) x the 512 B shard
    assert snap["counters"]["comm/psum_scatter_bytes"] == \
        pytest.approx(7 / 8 * 4096)
    assert snap["counters"]["comm/all_gather_bytes"] == \
        pytest.approx(7 * 512)
    assert snap["histograms"]["span/zero/grad_reduce_scatter"]["count"] \
        == 1
    assert snap["histograms"]["span/zero/param_all_gather"]["count"] == 1


def test_no_host_callbacks_in_compiled_step():
    """Telemetry never inserts callbacks into compiled programs: the
    lint of a telemetry-enabled traced sync (spans + comm recording
    both firing) finds no host-callback custom calls — the
    assert_clean_hlo rule matches actual custom_call targets, not the
    old '"callback" not in text' substring."""
    from jax.sharding import Mesh

    from apex_tpu.analysis import assert_clean_hlo

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    ddp = distributed.DistributedDataParallel(axis_name="dp")
    reg = MetricsRegistry(enabled=True)
    with use_registry(reg):
        sharded = jax.shard_map(lambda g: ddp.sync(g), mesh=mesh,
                                in_specs=P(), out_specs=P(),
                                check_vma=False)
        assert_clean_hlo(jax.jit(sharded), {"w": jnp.ones((16,))},
                         rules="no-host-callback")
        # the span + record_collective DID run at trace time
        assert reg.snapshot()["histograms"]["span/ddp/sync"]["count"] == 1


# ---------------------------------------------------------------------------
# DDP bench emission (spans + counters + mfu gauge in the JSONL)
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
def test_ddp_bench_emits_telemetry_jsonl(monkeypatch, tmp_path, capsys):
    """With APEX_TPU_TELEMETRY_DIR set, a (tiny) DDP bench config lands
    step spans, collective counters, and the cost_analysis()-derived
    mfu gauge in the JSONL, and the emitted bench JSON carries the new
    measured fields."""
    sys.path.insert(0, ROOT)
    try:
        import bench
    finally:
        sys.path.remove(ROOT)

    tel_dir = tmp_path / "tel"
    monkeypatch.setenv(ENV_DIR, str(tel_dir))
    prev = telemetry.set_registry(None)  # force re-resolution from env
    try:
        bench.bench_ddp_compressed(2, 2, hidden=64, depth=2)
    finally:
        telemetry.set_registry(prev)

    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "ddp_compressed_int8_steps_per_sec"
    assert "measured_comm_bytes_per_step" in line
    assert line["model_flops_per_step_xla"] is not None
    assert "mfu" in line

    events = []
    for f in tel_dir.glob("*.jsonl"):
        events.extend(json.loads(l) for l in f.read_text().splitlines())
    assert [e for e in events if e["kind"] == "span"
            and e["name"] == "bench/step"]
    colls = [e for e in events if e["kind"] == "collective"]
    assert {c["name"] for c in colls} >= {"psum", "pmax"}
    assert any(c.get("emulated") for c in colls if c["name"] == "psum")
    summary = [e for e in events if e["kind"] == "summary"][-1]
    assert "mfu" in summary["gauges"]
    assert summary["counters"]["comm/calls"] >= 2
    # dp spans the 8 virtual devices, so measured bytes are real
    assert line["measured_comm_bytes_per_step"] > 0
    assert summary["counters"]["comm/bytes"] > 0
