"""HBM budget accounting + OOM post-mortems (ISSUE 5 tentpole):
step_memory reports, capacity resolution, the live-buffer census,
preflight, the oom_guard/guarded_call post-mortem path with the
deterministic alloc-failure injector, ZeRO state-bytes accounting, the
ddp_memwatch bench e2e, and the tools/memory_report.py renderer."""

import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import resilience
from apex_tpu.resilience import faults
from apex_tpu.telemetry import memory
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))


# -- step_memory ------------------------------------------------------------

class TestStepMemory:
    def test_report_fields(self):
        f = jax.jit(lambda x: jnp.tanh(x @ x))
        rep = memory.step_memory(f, jnp.ones((32, 32)))
        assert rep is not None
        for key in ("argument_bytes", "output_bytes", "temp_bytes",
                    "generated_code_bytes", "alias_bytes", "peak_bytes",
                    "capacity_bytes", "headroom_frac", "backend"):
            assert key in rep
        assert rep["argument_bytes"] == 32 * 32 * 4
        assert rep["output_bytes"] == 32 * 32 * 4
        assert rep["peak_bytes"] >= rep["argument_bytes"]
        assert 0.0 < rep["headroom_frac"] <= 1.0

    def test_traceable_fn_is_jitted_on_the_fly(self):
        rep = memory.step_memory(lambda x: x * 2, jnp.ones((8,)))
        assert rep is not None and rep["argument_bytes"] == 32

    def test_capacity_env_override(self, monkeypatch):
        monkeypatch.setenv(memory.ENV_HBM_GB, "2.5")
        assert memory.hbm_capacity_bytes() == int(2.5e9)
        monkeypatch.delenv(memory.ENV_HBM_GB)
        assert memory.hbm_capacity_bytes("cpu") == \
            memory._CPU_CAPACITY_BYTES

    def test_accelerator_without_bytes_limit_raises(self, monkeypatch):
        """No platform-wide TPU default: the device's own figure or an
        error."""
        monkeypatch.delenv(memory.ENV_HBM_GB, raising=False)
        monkeypatch.setattr(memory, "_device_bytes_limit", lambda: None)
        with pytest.raises(RuntimeError, match="bytes_limit"):
            memory.hbm_capacity_bytes("tpu")
        monkeypatch.setattr(memory, "_device_bytes_limit",
                            lambda: int(15.75e9))
        assert memory.hbm_capacity_bytes("tpu") == int(15.75e9)

    def test_gauge_and_event_and_trend(self, tmp_path):
        memory.reset_trend()
        reg = MetricsRegistry(jsonl_dir=str(tmp_path))
        with use_registry(reg):
            f = jax.jit(lambda x: x + 1)
            memory.step_memory(f, jnp.ones((16,)))
        snap = reg.snapshot()
        assert "memory/hbm_headroom" in snap["gauges"]
        assert "memory/peak_hbm_bytes" in snap["gauges"]
        assert len(memory.headroom_trend()) == 1
        events = []
        for path in glob.glob(str(tmp_path / "*.jsonl")):
            with open(path) as f_:
                events.extend(json.loads(l) for l in f_ if l.strip())
        mems = [e for e in events if e["kind"] == "memory"
                and e["name"] == "step_memory"]
        assert mems and mems[0]["peak_bytes"] > 0

    def test_record_false_leaves_no_trace(self):
        memory.reset_trend()
        f = jax.jit(lambda x: x - 1)
        memory.step_memory(f, jnp.ones((8,)), record=False)
        assert memory.headroom_trend() == []

    def test_donated_args_discount_alias_bytes(self):
        @jax.jit
        def plain(x):
            return x * 2

        import functools

        @functools.partial(jax.jit, donate_argnums=(0,))
        def donated(x):
            return x * 2

        x = jnp.ones((256,))
        rep_p = memory.step_memory(plain, x, record=False)
        rep_d = memory.step_memory(donated, x, record=False)
        assert rep_d["alias_bytes"] > 0
        assert rep_d["peak_bytes"] < rep_p["peak_bytes"]


# -- census -----------------------------------------------------------------

class TestCensus:
    def test_labels_and_grouping(self):
        params = {"w": jnp.ones((64, 64)), "b": jnp.ones((64,))}
        # top_k=0 = untruncated: this test pins label MATCHING — under
        # a full suite run enough unrelated arrays are live (compiled
        # executables' constants, cached engines) that a 16 KiB labeled
        # group cannot be guaranteed a top-10-by-bytes seat
        census = memory.live_buffer_census(top_k=0,
                                           labels={"params": params})
        assert census["total_arrays"] >= 2
        assert census["total_bytes"] > 0
        labeled = [g for g in census["groups"] if g["label"] == "params"]
        assert labeled, census["groups"]
        assert labeled[0]["bytes"] >= labeled[0]["count"]

    def test_top_k_truncation_accounts_dropped(self):
        arrays = [jnp.full((i + 1,), 1.0) for i in range(6)]  # noqa: F841
        census = memory.live_buffer_census(top_k=2)
        assert len(census["groups"]) == 2
        assert census["dropped_groups"] >= 1
        # top-K is by bytes, descending
        assert census["groups"][0]["bytes"] >= census["groups"][1]["bytes"]


# -- preflight --------------------------------------------------------------

class TestPreflight:
    def test_within_budget_is_quiet(self):
        rep = memory.preflight(jax.jit(lambda x: x + 1), jnp.ones((8,)))
        assert rep is not None and not rep["over_budget"]

    def test_over_budget_warns(self, monkeypatch):
        monkeypatch.setenv(memory.ENV_HBM_GB, "1e-6")  # 1000 bytes
        with pytest.warns(UserWarning, match="exceeds"):
            rep = memory.preflight(jax.jit(lambda x: x @ x),
                                   jnp.ones((64, 64)))
        assert rep["over_budget"]

    def test_strict_raises_before_dispatch(self, monkeypatch):
        monkeypatch.setenv(memory.ENV_HBM_GB, "1e-6")
        with pytest.raises(memory.MemoryBudgetError, match="RESOURCE"):
            memory.preflight(jax.jit(lambda x: x @ x),
                             jnp.ones((64, 64)), strict=True)


# -- the OOM post-mortem path -----------------------------------------------

class TestOomPostmortem:
    def test_is_oom_error_matches_xla_and_synthetic(self):
        assert memory.is_oom_error(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                         "1073741824 bytes"))
        with pytest.raises(faults.SyntheticResourceExhausted) as ei:
            faults.inject_alloc_failure(3, 3)
        assert memory.is_oom_error(ei.value)
        assert not memory.is_oom_error(ValueError("shape mismatch"))

    def test_injector_is_identity_when_unarmed(self, monkeypatch):
        monkeypatch.delenv(faults.ENV_ALLOC_STEP, raising=False)
        faults.inject_alloc_failure(3)          # env unarmed: no-op
        faults.inject_alloc_failure(3, 5)       # wrong step: no-op

    def test_injector_env_gating(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_ALLOC_STEP, "2")
        faults.inject_alloc_failure(1)
        with pytest.raises(faults.SyntheticResourceExhausted,
                           match="RESOURCE_EXHAUSTED"):
            faults.inject_alloc_failure(2)

    def test_oom_guard_writes_postmortem_and_reraises(self, tmp_path):
        memory.reset_trend()
        params = {"w": jnp.ones((32, 32))}
        memory.step_memory(jax.jit(lambda p: p["w"] * 2), params,
                           record=True)  # seed the trend
        with pytest.raises(memory.HBMExhaustedError) as ei:
            with memory.oom_guard(str(tmp_path),
                                  labels={"params": params}):
                faults.inject_alloc_failure(0, 0)
        assert isinstance(ei.value.__cause__,
                          faults.SyntheticResourceExhausted)
        path = tmp_path / "memory-postmortem-rank0.json"
        assert path.exists()
        with open(path) as f:
            pm = json.load(f)
        assert pm["reason"] == "resource_exhausted"
        assert pm["census"]["total_bytes"] > 0
        assert len(pm["headroom_trend"]) == 1
        assert pm["last_step_memory"]["peak_bytes"] > 0
        assert "RESOURCE_EXHAUSTED" in pm["error"]
        assert memory.last_postmortem()["path"] == str(path)

    def test_oom_guard_passes_other_errors_through(self, tmp_path):
        with pytest.raises(ValueError, match="not an OOM"):
            with memory.oom_guard(str(tmp_path)):
                raise ValueError("not an OOM")
        assert not (tmp_path / "memory-postmortem-rank0.json").exists()

    def test_guarded_call_wires_through_resilience(self, tmp_path):
        def dispatch(i):
            faults.inject_alloc_failure(i, 1)
            return i * 2

        assert resilience.guarded_call(dispatch, 0,
                                       oom_dir=str(tmp_path)) == 0
        with pytest.raises(resilience.HBMExhaustedError,
                           match="post-mortem"):
            resilience.guarded_call(dispatch, 1, oom_dir=str(tmp_path))
        assert (tmp_path / "memory-postmortem-rank0.json").exists()

    def test_postmortem_event_lands_in_registry(self, tmp_path):
        reg = MetricsRegistry(jsonl_dir=str(tmp_path))
        with use_registry(reg):
            memory.oom_postmortem(RuntimeError("RESOURCE_EXHAUSTED: x"),
                                  str(tmp_path))
        events = []
        for path in glob.glob(str(tmp_path / "*.jsonl")):
            with open(path) as f:
                events.extend(json.loads(l) for l in f if l.strip())
        pms = [e for e in events if e["kind"] == "memory"
               and e["name"] == "postmortem"]
        assert pms and pms[0]["path"].endswith(
            "memory-postmortem-rank0.json")


# -- ZeRO state bytes -------------------------------------------------------

class TestZeroStateBytes:
    def _params(self):
        rng = np.random.RandomState(0)
        return {"w": jnp.asarray(rng.randn(300, 4), jnp.float32),
                "b": jnp.zeros((4,), jnp.float32)}

    def test_adam_sharded_vs_unsharded(self):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        opt = DistributedFusedAdam()
        rep = opt.state_bytes(self._params(), world=8)
        n, padded = rep["n_elements"], rep["padded_elements"]
        assert n == 1204 and padded % 8 == 0
        assert rep["unsharded_state_bytes"] == 3 * padded * 4
        assert rep["sharded_state_bytes"] == 3 * (padded // 8) * 4
        assert rep["residual_bytes"] == 0
        assert rep["savings_ratio"] == pytest.approx(8.0)

    def test_int8_residual_is_full_length_and_honest(self):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        opt = DistributedFusedAdam(compress=True)
        rep = opt.state_bytes(self._params(), world=8)
        padded = rep["padded_elements"]
        assert padded % (8 * opt.compress_block_size) == 0
        assert rep["residual_bytes"] == padded * 4
        assert rep["sharded_state_bytes"] == \
            3 * (padded // 8) * 4 + padded * 4
        # the residual floors the saving below the clean 8x
        assert 1.0 < rep["savings_ratio"] < 8.0

    def test_lamb_matches_adam_layout(self):
        from apex_tpu.contrib.optimizers import (
            DistributedFusedAdam,
            DistributedFusedLAMB,
        )

        p = self._params()
        adam = DistributedFusedAdam().state_bytes(p, world=4)
        lamb = DistributedFusedLAMB().state_bytes(p, world=4)
        for key in ("padded_elements", "unsharded_state_bytes",
                    "sharded_state_bytes", "savings_ratio"):
            assert adam[key] == lamb[key]
        assert lamb["optimizer"] == "DistributedFusedLAMB"

    def test_records_memory_event(self, tmp_path):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        reg = MetricsRegistry(jsonl_dir=str(tmp_path))
        with use_registry(reg):
            DistributedFusedAdam().state_bytes(self._params(), world=8)
        assert reg.snapshot()["gauges"][
            "memory/zero_state_sharded_bytes"] > 0
        events = []
        for path in glob.glob(str(tmp_path / "*.jsonl")):
            with open(path) as f:
                events.extend(json.loads(l) for l in f if l.strip())
        assert [e for e in events if e["kind"] == "memory"
                and e["name"] == "zero_state_bytes"]


# -- DDP wiring -------------------------------------------------------------

class TestDdpMemoryReport:
    def test_report_tagged_with_sync_config(self):
        from apex_tpu.parallel import DistributedDataParallel

        ddp = DistributedDataParallel(axis_name="dp", compress="int8")
        f = jax.jit(lambda x: x * 2)
        rep = ddp.memory_report(f, jnp.ones((16,)))
        assert rep["compress"] == "int8"
        assert rep["axis_name"] == "dp"
        assert rep["peak_bytes"] > 0


# -- the ddp_memwatch bench e2e (ISSUE 5 acceptance) ------------------------

@pytest.mark.multi_device
class TestDdpMemwatchBench:
    def test_injected_alloc_failure_produces_postmortem(
            self, tmp_path, monkeypatch, capsys):
        import bench

        memory.reset_trend()
        monkeypatch.setenv(memory.ENV_DIR, str(tmp_path))
        ret = bench.bench_ddp_memwatch(2, 6, hidden=32, depth=2,
                                       alloc_step=3)
        capsys.readouterr()
        path = ret["oom_postmortem_path"]
        assert path and os.path.exists(path)
        with open(path) as f:
            pm = json.load(f)
        assert pm["census"]["total_bytes"] > 0
        assert pm["census"]["groups"]
        assert len(pm["headroom_trend"]) >= 1
        # the injected OOM cost one step, not the run
        assert np.isfinite(ret["final_loss"])

    def test_uninjected_run_reports_headroom_and_one_compile(
            self, tmp_path, monkeypatch, capsys):
        import bench

        memory.reset_trend()
        monkeypatch.setenv(memory.ENV_DIR, str(tmp_path))
        ret = bench.bench_ddp_memwatch(2, 5, hidden=32, depth=2,
                                       alloc_step=-1)
        line = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert ret["oom_postmortem_path"] is None
        assert ret["compile_count"] == 1
        assert ret["recompiles"] == 0
        assert line["compile_count"] == 1
        assert line["hbm_headroom_pct"] is not None
        assert line["peak_hbm_bytes"] > 0
        # round-10 capture contract
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import bench_schema_check as schema

        assert schema.check_metric_line(line, round_n=10, errors=[]) == []


# -- tools/memory_report.py -------------------------------------------------

class TestMemoryReportTool:
    def _seed_dir(self, d):
        pm = {"t": 1.0, "reason": "resource_exhausted", "rank": 0,
              "error": "RESOURCE_EXHAUSTED: injected",
              "census": {"total_arrays": 2, "total_bytes": 4096,
                         "groups": [{"label": "params",
                                     "shape": [32, 32],
                                     "dtype": "float32", "count": 1,
                                     "bytes": 4096}],
                         "dropped_groups": 0, "dropped_bytes": 0},
              "last_step_memory": {"peak_bytes": 4096,
                                   "capacity_bytes": 16000000000},
              "headroom_trend": [{"t": 1.0, "peak_bytes": 4096,
                                  "headroom_frac": 0.99}]}
        with open(os.path.join(d, "memory-postmortem-rank0.json"),
                  "w") as f:
            json.dump(pm, f)
        events = [
            {"t": 1.0, "kind": "memory", "name": "step_memory",
             "peak_bytes": 4096, "headroom_frac": 0.99, "step": "s"},
            {"t": 1.1, "kind": "memory", "name": "zero_state_bytes",
             "optimizer": "DistributedFusedAdam", "world": 8,
             "unsharded_state_bytes": 800, "sharded_state_bytes": 100,
             "savings_ratio": 8.0},
            {"t": 1.2, "kind": "compile", "name": "train_step",
             "compiles": 2, "recompile": True, "call_seconds": 0.5,
             "changed": [{"arg": "args/0", "old": "f32[4]",
                          "new": "f32[8]"}]},
        ]
        with open(os.path.join(d, "telemetry-rank0.jsonl"), "w") as f:
            for ev in events:
                f.write(json.dumps(ev) + "\n")

    def test_human_report(self, tmp_path, capsys):
        import memory_report

        self._seed_dir(str(tmp_path))
        assert memory_report.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "train_step" in out
        assert "args/0: f32[4] -> f32[8]" in out
        assert "live buffers at death" in out
        assert "DistributedFusedAdam" in out
        assert "headroom trend" in out

    def test_json_report(self, tmp_path, capsys):
        import memory_report

        self._seed_dir(str(tmp_path))
        assert memory_report.main(["--json", str(tmp_path)]) == 0
        agg = json.loads(capsys.readouterr().out)
        assert agg["postmortems"][0]["census"]["total_bytes"] == 4096
        assert agg["compiles"]["train_step"]["recompiles"] == 1
        assert agg["zero_state"][0]["savings_ratio"] == 8.0

    def test_empty_dir_is_not_fatal(self, tmp_path, capsys):
        import memory_report

        assert memory_report.main([str(tmp_path)]) == 0
        assert "nothing to report" in capsys.readouterr().out


# -- telemetry_report learns the new kinds (ISSUE 5 satellite) --------------

class TestTelemetryReportNewKinds:
    def test_compile_and_memory_kinds_not_unknown(self, tmp_path, capsys):
        import telemetry_report

        events = [
            {"t": 1.0, "kind": "compile", "name": "step", "compiles": 2,
             "recompile": True, "call_seconds": 1.5,
             "changed": [{"arg": "args/1", "old": "f32[2]",
                          "new": "f32[3]"}]},
            {"t": 1.1, "kind": "memory", "name": "step_memory",
             "peak_bytes": 1024, "headroom_frac": 0.5},
            {"t": 1.2, "kind": "memory", "name": "postmortem",
             "path": "/tmp/memory-postmortem-rank0.json"},
            {"t": 1.3, "kind": "memory", "name": "zero_state_bytes",
             "optimizer": "DistributedFusedLAMB", "world": 4,
             "unsharded_state_bytes": 400, "sharded_state_bytes": 100,
             "savings_ratio": 4.0},
            {"t": 1.4, "kind": "memory", "name": "preflight_over_budget",
             "peak_bytes": 99, "budget_bytes": 10},
        ]
        path = tmp_path / "telemetry-rank0.jsonl"
        with open(path, "w") as f:
            for ev in events:
                f.write(json.dumps(ev) + "\n")
        report = telemetry_report.aggregate(
            telemetry_report.load_events([str(path)]))
        assert report["unknown_kinds"] == {}
        assert report["malformed_events"] == 0
        assert report["compiles"]["step"]["recompiles"] == 1
        assert report["memory"]["headroom_trend"] == [
            {"peak_bytes": 1024, "headroom_frac": 0.5}]
        assert report["memory"]["postmortems"][0]["path"].endswith(
            "rank0.json")
        assert report["memory"]["preflight_warnings"] == 1
        assert report["memory"]["zero_state"][0]["world"] == 4
        telemetry_report.print_report(report)
        out = capsys.readouterr().out
        assert "compiles (watched functions)" in out
        assert "args/1: f32[2] -> f32[3]" in out
        assert "50.00% headroom" in out
        assert "OOM postmortem" in out
