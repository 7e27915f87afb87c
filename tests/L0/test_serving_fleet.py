"""Serving fleet (apex_tpu.serving.fleet + ISSUE 11).

Covers:

- fleet config validation + tier resolution (tier defaults fill the
  PR-7 per-request deadline fields; request-level overrides win);
- load-aware dispatch over stub replicas (most-free-slots routing,
  per-replica queue caps, interactive-before-batch priority,
  impossible shapes rejected at the fleet, not retried forever);
- the replica health state machine: healthy -> degraded ->
  quarantined -> respawning -> healthy off ServeHealth counter
  deltas, with drain + migration on quarantine;
- request migration bookkeeping: tokens emitted on a dead replica are
  carried into the continuation (re-prefill from prompt + emitted),
  stitched back on completion, zero silent losses — a continuation
  too long for every prefill ladder is a LOUD loss;
- ``inject_replica_loss`` (hard loss): everything migrates at once,
  the replica respawns with a fresh generation name;
- elastic autoscale: sustained pending depth spawns into idle slots,
  sustained idle retires the least-loaded replica gracefully;
- the 8-device chaos e2e acceptance (tier-1, cheap): a 2-replica x
  4-device fleet, one replica killed mid-trace -> every in-flight
  request of the dead replica finishes on the survivor with greedy
  outputs token-identical to the unkilled run, goodput >= 90% of
  clean, zero watcher recompiles, per-replica compile_count == the
  ladder size;
- the ``bench.py serve_fleet`` contract (slow — two fleets on the
  smoke model) + round-16 schema gating (cheap, dict-level).

Pure-policy paths run against stub engines via ``engine_factory`` (no
compiles — the router is host-side by design); the acceptance shares
one tiny real model per module scope.
"""

import io
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.resilience import faults
from apex_tpu.serving import (
    FleetConfig,
    Request,
    RobustConfig,
    Scheduler,
    ServeConfig,
    ServeFleet,
    TierConfig,
    diurnal_trace,
)
from apex_tpu.telemetry import CompileWatcher
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry
from apex_tpu.transformer import parallel_state

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def tiny():
    parallel_state.destroy_model_parallel()
    cfg = TransformerConfig(
        hidden_size=32, num_layers=2, num_attention_heads=4,
        vocab_size=64, max_position_embeddings=128,
        compute_dtype=jnp.float32, use_flash_attention=False)
    model = GPTModel(cfg, decode=True)
    params = GPTModel(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 4), jnp.int32))["params"]
    return cfg, model, params


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.disarm_replica_loss()


class _StubEngine:
    """Duck-typed engine for pure router-policy tests: no jax, no
    compiles. ``finite_fn(slot_ids, call)`` shapes the quarantine
    flags so health-counter transitions can be scripted."""

    def __init__(self, num_slots=4, finite_fn=None, prefill_buckets=(64,),
                 batch_buckets=(2, 4)):
        self.config = types.SimpleNamespace(
            num_slots=num_slots, batch_buckets=tuple(batch_buckets),
            prefill_buckets=tuple(prefill_buckets),
            eos_token_id=None, pad_token_id=0)
        self.max_len = 10_000
        self.decode_retries_total = 0
        self._decode_calls = 0
        self.compile_count = 6
        self.spec = types.SimpleNamespace(
            bytes_per_slot=lambda: 0, cache_dtype_name=lambda: "stub")
        self._finite_fn = finite_fn

    def kv_cache_bytes(self):
        return 0

    def prefill(self, slot_ids, prompts, *, pad_slot_ids=None):
        return np.ones(len(prompts), np.int32)

    def decode(self, slot_ids, tokens, *, pad_slot_ids=None,
               retries=0, backoff_s=0.0, backoff_cap_s=0.0):
        call = self._decode_calls
        self._decode_calls += 1
        n = len(slot_ids)
        finite = (np.ones(n, bool) if self._finite_fn is None
                  else np.asarray(self._finite_fn(slot_ids, call)))
        return np.ones(n, np.int32), finite


def _stub_fleet(config=None, *, num_slots=4, finite_fns=None,
                prefill_buckets=(64,), batch_buckets=(2, 4),
                registry=None):
    """Fleet over stub engines; ``finite_fns[idx]`` scripts replica
    idx's quarantine flags (consulted per spawn generation)."""
    finite_fns = finite_fns or {}
    generations = {}

    def factory(idx, mesh, name):
        gen = generations.get(idx, 0)
        generations[idx] = gen + 1
        fn = finite_fns.get(idx) if gen == 0 else None
        return _StubEngine(num_slots=num_slots, finite_fn=fn,
                           prefill_buckets=prefill_buckets,
                           batch_buckets=batch_buckets)

    return ServeFleet(engine_factory=factory,
                      config=config or FleetConfig(),
                      registry=registry)


def _req(rid, plen=3, max_new=4, arrival=0.0, **kw):
    return Request(rid=rid, prompt=np.arange(plen, dtype=np.int32) % 7,
                   max_new_tokens=max_new, arrival=arrival, **kw)


# ---------------------------------------------------------------------------
# config + tier resolution
# ---------------------------------------------------------------------------

class TestFleetConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="num_replicas"):
            FleetConfig(num_replicas=0)
        with pytest.raises(ValueError, match="max_replicas"):
            FleetConfig(num_replicas=3, max_replicas=2)
        with pytest.raises(ValueError, match="min_replicas"):
            FleetConfig(num_replicas=2, min_replicas=3)
        with pytest.raises(ValueError, match="unknown tier"):
            FleetConfig(tiers={"premium": TierConfig()})
        with pytest.raises(ValueError, match="quarantine_after"):
            FleetConfig(degraded_after=3, quarantine_after=1)
        with pytest.raises(ValueError, match="oscillate"):
            FleetConfig(scale_up_pending=2, scale_down_pending=4)
        assert FleetConfig(num_replicas=2).resolved_max_replicas == 2
        assert FleetConfig(num_replicas=2,
                           max_replicas=4).resolved_max_replicas == 4

    def test_tier_defaults_fill_deadlines(self):
        fleet = _stub_fleet(FleetConfig(num_replicas=1, tiers={
            "interactive": TierConfig(ttft_deadline_s=5.0,
                                      total_deadline_s=20.0),
            "batch": TierConfig(total_deadline_s=500.0)}))
        assert fleet.submit(_req(0))                        # default tier
        assert fleet.submit(_req(1, tier="batch"))
        assert fleet.submit(_req(2, tier="interactive",
                                 ttft_deadline_s=1.0))      # override wins
        by_rid = {r.rid: r for r in fleet.pending}
        assert by_rid[0].tier == "interactive"
        assert by_rid[0].ttft_deadline_s == 5.0
        assert by_rid[1].ttft_deadline_s is None
        assert by_rid[1].total_deadline_s == 500.0
        assert by_rid[2].ttft_deadline_s == 1.0

    def test_unknown_tier_and_duplicate_rid_reject(self):
        fleet = _stub_fleet(FleetConfig(num_replicas=1))
        assert not fleet.submit(_req(0, tier="premium"))
        assert fleet.submit(_req(1))
        assert not fleet.submit(_req(1))
        assert [r.reason for r in fleet.rejected] == \
            ["unknown_tier", "duplicate_rid"]

    def test_per_tier_accounting(self):
        fleet = _stub_fleet(FleetConfig(num_replicas=2))
        reqs = [_req(i, tier="batch" if i % 2 else "interactive",
                     max_new=3) for i in range(6)]
        fleet.run(reqs)
        s = fleet.stats()
        assert s["by_tier"]["interactive"]["requests"] == 3
        assert s["by_tier"]["batch"]["requests"] == 3
        assert s["by_tier"]["interactive"]["ok"] == 3
        assert s["ttft_p99_ms_interactive"] is not None
        assert s["ttft_p99_ms_batch"] is not None


# ---------------------------------------------------------------------------
# dispatch policy
# ---------------------------------------------------------------------------

class TestDispatch:
    def test_load_aware_spread(self):
        """A burst spreads across replicas instead of piling onto
        one: the router picks the replica with the most free slots."""
        fleet = _stub_fleet(FleetConfig(num_replicas=2), num_slots=4)
        fleet.run([_req(i, max_new=3) for i in range(8)])
        s = fleet.stats()
        dispatched = [r["dispatched"] for r in s["replicas"]]
        assert sorted(dispatched) == [4, 4]

    def test_queue_cap_leaves_backlog_at_fleet(self):
        fleet = _stub_fleet(
            FleetConfig(num_replicas=1, replica_queue_depth=2),
            num_slots=2)
        for i in range(12):
            assert fleet.submit(_req(i, max_new=4))
        fleet._dispatch()
        rep = fleet.replicas[0]
        # capacity this tick: 2 free slots + queue cap 2 — the other 8
        # wait at the fleet, where autoscale can see them
        assert len(rep.sched.pending) == 4
        assert len(fleet.pending) == 8
        done = fleet.run()
        assert len(done) == 12
        assert all(c.finish_reason == "length" for c in done)

    def test_impossible_prompt_rejects_at_fleet(self):
        fleet = _stub_fleet(FleetConfig(num_replicas=1),
                            prefill_buckets=(8,))
        assert fleet.submit(_req(0, plen=99))    # fleet can't know yet
        done = fleet.run(max_steps=10)
        assert done == []
        assert [r.reason for r in fleet.rejected] == ["prompt_too_long"]

    def test_interactive_jumps_batch_when_capacity_contended(self):
        """Capacity for 2 dispatches this tick, 3 requests queued:
        the interactive one makes the cut even though it was
        submitted last; a batch request waits at the fleet."""
        fleet = _stub_fleet(
            FleetConfig(num_replicas=1, replica_queue_depth=1),
            num_slots=1, batch_buckets=(1,))
        fleet.submit(_req(0, tier="batch", max_new=2))
        fleet.submit(_req(1, tier="batch", max_new=2))
        fleet.submit(_req(2, tier="interactive", max_new=2))
        fleet._dispatch()
        dispatched = {r.rid for r in fleet.replicas[0].sched.pending}
        assert 2 in dispatched
        assert [r.rid for r in fleet.pending] in ([0], [1])
        assert fleet.pending[0].tier == "batch"


# ---------------------------------------------------------------------------
# health state machine
# ---------------------------------------------------------------------------

class TestHealthStateMachine:
    def test_degraded_then_recovered(self):
        """One poisoned slot degrades the replica; clean ticks heal
        it back to healthy."""
        def finite_fn(slot_ids, call):
            ok = np.ones(len(slot_ids), bool)
            if call == 0:
                ok[0] = False
            return ok

        fleet = _stub_fleet(
            FleetConfig(num_replicas=1, degraded_after=1,
                        quarantine_after=10, recover_after_ticks=2),
            finite_fns={0: finite_fn})
        fleet.run([_req(i, max_new=8, arrival=float(i))
                   for i in range(6)])
        rep = fleet.replicas[0]
        assert rep.state == "healthy"            # recovered by run end
        s = fleet.stats()
        assert s["requests_by_reason"].get("poisoned") == 1
        assert s["replicas_quarantined"] == 0

    def test_bad_counters_quarantine_and_respawn(self):
        """Accumulated poisoned-slot evictions cross quarantine_after:
        the replica drains, migrates, respawns with a fresh
        generation — and the poisoned terminals stay non-silent."""
        def finite_fn(slot_ids, call):
            ok = np.ones(len(slot_ids), bool)
            if call < 3:
                ok[0] = False
            return ok

        fleet = _stub_fleet(
            FleetConfig(num_replicas=2, degraded_after=1,
                        quarantine_after=3, respawn_delay_ticks=1),
            finite_fns={0: finite_fn})
        done = fleet.run([_req(i, max_new=8, arrival=float(i) * 0.3)
                          for i in range(10)])
        s = fleet.stats()
        assert s["replicas_quarantined"] >= 1
        assert s["replicas_respawned"] >= 1
        assert s["lost_requests"] == 0
        reasons = [c.finish_reason for c in done]
        assert reasons.count("poisoned") == 3
        assert s["requests_ok"] == 7
        # the respawned replica slot is serving again
        assert fleet.replicas[0].state == "healthy"
        assert fleet.replicas[0].generation == 2

    def test_replica_state_events_land(self, tmp_path):
        with use_registry(MetricsRegistry(jsonl_dir=str(tmp_path))) \
                as reg:
            fleet = _stub_fleet(FleetConfig(num_replicas=1))
            with faults.inject_replica_loss(0, 1):
                fleet.run([_req(i, max_new=6) for i in range(3)])
            reg.flush()
        events = []
        for p in tmp_path.glob("telemetry-rank*.jsonl"):
            events += [json.loads(l) for l in p.read_text().splitlines()]
        fe = [e for e in events if e["kind"] == "fleet"]
        names = {e["name"] for e in fe}
        assert {"fleet_start", "replica_state", "migration",
                "respawn", "fleet_report"} <= names
        states = [(e["old"], e["new"]) for e in fe
                  if e["name"] == "replica_state"]
        assert ("idle", "healthy") in states
        assert ("healthy", "quarantined") in states
        assert ("quarantined", "respawning") in states
        assert ("respawning", "healthy") in states


# ---------------------------------------------------------------------------
# replica loss + migration bookkeeping
# ---------------------------------------------------------------------------

class TestReplicaLossMigration:
    def test_loss_migrates_and_stitches_tokens(self):
        fleet = _stub_fleet(FleetConfig(num_replicas=2,
                                        respawn_delay_ticks=1))
        with faults.inject_replica_loss(0, 2) as st:
            done = fleet.run([_req(i, max_new=5, arrival=float(i) * 0.4)
                              for i in range(8)])
        assert st["fired"] == 1
        s = fleet.stats()
        assert s["lost_requests"] == 0
        assert s["migrated_requests"] >= 1
        assert s["replicas_respawned"] == 1
        assert s["rebalance_latency_ms"] is not None
        assert len(done) == 8
        # every request got its FULL token budget despite the kill —
        # the continuation carried the emitted prefix
        assert all(len(c.tokens) == 5 for c in done)
        assert all(c.finish_reason == "length" for c in done)

    def test_loss_without_respawn_leaves_survivors_serving(self):
        fleet = _stub_fleet(FleetConfig(num_replicas=2, respawn=False))
        with faults.inject_replica_loss(0, 1):
            done = fleet.run([_req(i, max_new=4, arrival=float(i) * 0.2)
                              for i in range(6)])
        assert len(done) == 6
        assert fleet.stats()["replicas_respawned"] == 0
        assert fleet.replicas[0].state == "quarantined"
        assert fleet.replicas[1].state == "healthy"

    def test_oversized_continuation_is_loud_loss(self):
        """A continuation prompt (orig + emitted) that no ladder can
        re-prefill lands terminal ``failed`` + fleet/lost_requests —
        never a silent disappearance."""
        fleet = _stub_fleet(FleetConfig(num_replicas=2,
                                        respawn_delay_ticks=1),
                            prefill_buckets=(8,))
        # plen 6 + a few emitted tokens > bucket 8 once decode started
        with faults.inject_replica_loss(0, 3):
            done = fleet.run([_req(i, plen=6, max_new=8,
                                   arrival=0.0) for i in range(4)])
        s = fleet.stats()
        assert len(done) == 4
        failed = [c for c in done if c.finish_reason == "failed"]
        assert len(failed) == s["lost_requests"] >= 1
        # the partial tokens ride on the failed record (evidence)
        assert all(len(c.tokens) > 0 for c in failed)

    def test_extract_unfinished_scopes(self):
        """The scheduler migration seam: active-only extraction leaves
        the queue for the drain window and vice versa."""
        sched = Scheduler(_StubEngine(num_slots=2))
        for i in range(4):
            sched.submit(_req(i, max_new=8))
        sched.step()                              # 2 admitted, 2 queued
        assert len(sched.active) == 2 and len(sched.pending) == 2
        pending = sched.extract_unfinished(which="pending")
        assert [r["where"] for r in pending] == ["pending"] * 2
        assert [r["tokens"] for r in pending] == [[], []]
        assert len(sched.active) == 2
        active = sched.extract_unfinished(which="active")
        assert [r["where"] for r in active] == ["active"] * 2
        assert all(len(r["tokens"]) >= 1 for r in active)
        assert sorted(sched.free) == [0, 1]
        assert not sched.active and not sched.pending
        with pytest.raises(ValueError, match="which"):
            sched.extract_unfinished(which="everything")

    def test_replica_loss_plan_arming(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_FAULT_PLAN, "replica_loss@4:1")
        faults.disarm_replica_loss()
        assert faults.replica_loss_for(3) is None
        assert faults.replica_loss_for(4) == 1
        assert faults.replica_loss_for(4) is None   # one-shot


# ---------------------------------------------------------------------------
# elastic autoscale
# ---------------------------------------------------------------------------

class TestAutoscale:
    def test_scale_up_on_sustained_depth_and_down_when_idle(self):
        fleet = _stub_fleet(
            FleetConfig(num_replicas=1, max_replicas=3, min_replicas=1,
                        scale_up_pending=3, scale_down_pending=0,
                        scale_sustain_ticks=2),
            num_slots=2)
        fleet.run([_req(i, max_new=6) for i in range(16)])
        s = fleet.stats()
        assert s["scale_ups"] >= 1
        assert s["requests_ok"] == 16
        # the spawned replicas actually took traffic
        assert sum(1 for r in s["replicas"] if r["dispatched"]) >= 2
        # the tail of the run retired back toward min_replicas
        assert s["scale_downs"] >= 1

    def test_no_thresholds_no_scaling(self):
        fleet = _stub_fleet(FleetConfig(num_replicas=1, max_replicas=3),
                            num_slots=2)
        fleet.run([_req(i, max_new=4) for i in range(10)])
        s = fleet.stats()
        assert s["scale_ups"] == 0 and s["scale_downs"] == 0
        assert [r["state"] for r in s["replicas"]] == \
            ["healthy", "idle", "idle"]

    def test_scale_events_land(self, tmp_path):
        with use_registry(MetricsRegistry(jsonl_dir=str(tmp_path))) \
                as reg:
            fleet = _stub_fleet(
                FleetConfig(num_replicas=1, max_replicas=2,
                            scale_up_pending=2, scale_sustain_ticks=2),
                num_slots=2)
            fleet.run([_req(i, max_new=6) for i in range(12)])
            reg.flush()
        events = []
        for p in tmp_path.glob("telemetry-rank*.jsonl"):
            events += [json.loads(l) for l in p.read_text().splitlines()]
        ups = [e for e in events if e["kind"] == "fleet"
               and e["name"] == "scale_up"]
        assert ups and ups[0]["pending_depth"] > 2


# ---------------------------------------------------------------------------
# the 8-device chaos e2e acceptance (tier-1: the cheap one)
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
class TestFleetChaosE2E:
    @pytest.mark.slow  # tier-1 budget (14s)
    def test_kill_replica_mid_trace_token_identity(self, tiny):
        """ISSUE-11 acceptance: a 2-replica x 4-device fleet on the
        8-device CPU mesh, replica 0 killed mid-Poisson-trace ->
        every in-flight request of the dead replica finishes on the
        survivor, greedy outputs token-identical to an unkilled run,
        fleet goodput >= 90% of clean, zero watcher recompiles in
        steady state (the respawned ladder registers under a fresh
        generation name), per-replica compile_count == the ladder."""
        cfg, model, params = tiny
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        serve_cfg = ServeConfig(batch_buckets=(2, 4),
                                prefill_buckets=(16,), num_slots=4)

        def trace():
            return diurnal_trace(
                10, seed=5, prompt_lens=(3, 5), max_new=(4, 6),
                vocab_size=cfg.vocab_size, burst_at=0.0, burst_n=3,
                base_interarrival=0.6)

        def build():
            watcher = CompileWatcher(enabled=True)
            fleet = ServeFleet(
                model, params, serve_cfg,
                FleetConfig(num_replicas=2, devices_per_replica=4,
                            respawn_delay_ticks=1),
                watcher=watcher)
            return fleet, watcher

        fleet_a, _ = build()
        # the two replicas genuinely sit on distinct device slices
        devs0 = {d.id for d in fleet_a.replicas[0].devices}
        devs1 = {d.id for d in fleet_a.replicas[1].devices}
        assert len(devs0) == len(devs1) == 4 and not (devs0 & devs1)
        clean = fleet_a.run(trace())
        stats_a = fleet_a.stats()
        assert stats_a["requests_ok"] == 13       # 10 + 3 burst
        assert stats_a["lost_requests"] == 0
        clean_tokens = {c.rid: list(map(int, c.tokens)) for c in clean}

        fleet_b, watcher = build()
        with faults.inject_replica_loss(0, 3) as st:
            chaos = fleet_b.run(trace())
        stats_b = fleet_b.stats()
        assert st["fired"] == 1
        assert stats_b["lost_requests"] == 0
        assert stats_b["migrated_requests"] >= 1
        assert stats_b["replicas_respawned"] == 1
        assert stats_b["rebalance_latency_ms"] is not None
        chaos_tokens = {c.rid: list(map(int, c.tokens)) for c in chaos}
        assert chaos_tokens == clean_tokens       # greedy identity
        assert stats_b["goodput_tokens"] >= 0.9 * stats_a["goodput_tokens"]
        assert watcher.recompile_count() == 0
        ladder = 2 * 1 + 2                        # (2,4) x (16,) + decode
        for row in stats_b["replicas"]:
            if row["compile_count"] is not None:
                assert row["compile_count"] == ladder
        # per-tier SLO rollup present for both tiers (diurnal trace
        # mixes interactive/batch)
        assert stats_b["ttft_p99_ms_interactive"] is not None
        assert stats_b["ttft_p99_ms_batch"] is not None


# ---------------------------------------------------------------------------
# bench + schema contract
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestServeFleetBench:
    def test_serve_fleet_bench_contract(self, monkeypatch, capsys):
        monkeypatch.setenv("APEX_TPU_SERVE_SMOKE", "1")
        monkeypatch.syspath_prepend(ROOT)
        import bench

        ret = bench.bench_serve_fleet(8, 3)
        line = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert line["metric"] == "serve_fleet_tokens_per_sec"
        assert line["value"] > 0
        assert ret["lost_requests"] == 0
        assert ret["token_identical"]
        assert ret["replicas_respawned"] >= 1
        assert ret["goodput_ratio"] >= 0.9
        assert ret["recompiles_chaos"] == 0
        assert line["rebalance_latency_ms"] is not None
        for key in ("ttft_p99_ms_interactive", "ttft_p99_ms_batch",
                    "rebalance_latency_ms", "replicas_respawned"):
            assert key in line
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import bench_schema_check as bsc

        assert bsc.check_metric_line(line, round_n=16, errors=[]) == []
        errs = bsc.check_metric_line(line, round_n=15, errors=[])
        assert any("only defined from round 16" in e for e in errs)


class TestSchemaGateRound16:
    def test_fleet_fields_gated_at_round16(self):
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import bench_schema_check as bsc

        base = {"metric": "serve_fleet_tokens_per_sec",
                "value": 1.0, "unit": "tokens/sec", "vs_baseline": 1.0,
                "tflops_per_sec": 0.0, "mfu": 0.0,
                "comm_bytes_per_step": 0,
                "measured_comm_bytes_per_step": None,
                "model_flops_per_step_xla": None,
                "peak_hbm_bytes": None, "hbm_headroom_pct": None,
                "compile_count": 4, "lint_violations": None,
                "backend": "cpu-mesh"}
        errs = bsc.check_metric_line(dict(base), round_n=16, errors=[])
        assert sum("serve_fleet line missing" in e for e in errs) == 4
        full = dict(base, ttft_p99_ms_interactive=2.0,
                    ttft_p99_ms_batch=5.0, rebalance_latency_ms=1.5,
                    replicas_respawned=1)
        assert bsc.check_metric_line(dict(full), round_n=16,
                                     errors=[]) == []
        # nullable: a clean run with no rebalance is still valid
        assert bsc.check_metric_line(
            dict(full, rebalance_latency_ms=None, ttft_p99_ms_batch=None),
            round_n=16, errors=[]) == []
        # a pre-16 record carrying them is flagged
        errs = bsc.check_metric_line(dict(full), round_n=15, errors=[])
        assert any("only defined from round 16" in e for e in errs)
        # typed when present
        errs = bsc.check_metric_line(
            dict(full, replicas_respawned="one"), round_n=16, errors=[])
        assert any("must be numeric or null" in e for e in errs)
        # other configs never need them
        other = dict(base, metric="gpt2_345m_tokens_per_sec_per_chip")
        assert bsc.check_metric_line(other, round_n=16, errors=[]) == []


# ---------------------------------------------------------------------------
# telemetry_report: the fleet kind
# ---------------------------------------------------------------------------

class TestFleetReportKind:
    def test_report_aggregates_fleet_events(self, tmp_path, capsys):
        """tools/telemetry_report learns ``kind: fleet``: replica
        table + per-tier rollup + migration/respawn timeline from a
        real fleet run's JSONL."""
        with use_registry(MetricsRegistry(jsonl_dir=str(tmp_path))) \
                as reg:
            fleet = _stub_fleet(FleetConfig(num_replicas=2,
                                            respawn_delay_ticks=1))
            with faults.inject_replica_loss(0, 2):
                fleet.run([_req(i, max_new=5,
                                tier="batch" if i % 4 == 3 else None,
                                arrival=float(i) * 0.4)
                           for i in range(8)])
            reg.flush()
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import telemetry_report

        paths = [str(p) for p in tmp_path.glob("telemetry-rank*.jsonl")]
        report = telemetry_report.aggregate(
            telemetry_report.load_events(paths))
        f = report["fleet"]
        assert f["respawns"] == 1
        assert f["migrated_requests"] >= 1
        assert f["lost_requests"] == 0
        assert f["last_report"] is not None
        assert f["last_report"]["requests_ok"] == 8
        rows = f["last_report"]["replicas"]
        assert [r["replica"] for r in rows] == [0, 1]
        assert f["last_report"]["by_tier"]["batch"]["requests"] == 2
        events = [row["event"] for row in f["timeline"]]
        assert "replica_state" in events and "migration" in events
        assert "respawn" in events and "rebalance" in events
        # unknown-kind forward-compat footer untouched
        assert report["unknown_kinds"] == {}
        buf = io.StringIO()
        telemetry_report.print_report(report, out=buf)
        text = buf.getvalue()
        assert "serving fleet (apex_tpu.serving.fleet):" in text
        assert "tier batch" in text
        assert "event timeline" in text

    def test_report_rolls_up_kv_handoff_events(self, tmp_path):
        """ISSUE-18: the fleet kind learns the KV-state handoff
        events — kv_handoff totals (count + bytes carried), the
        per-reason kv_fallback split, and the injector's
        kv_corrupt_injected — in the rollup, the timeline, and the
        rendered report."""
        with use_registry(MetricsRegistry(jsonl_dir=str(tmp_path))) \
                as reg:
            reg.event("fleet", "kv_handoff", rid=1, replica=1, slot=0,
                      length=14, cut=13, bytes=65540, tick=3)
            reg.event("fleet", "kv_handoff", rid=2, replica=1, slot=1,
                      length=10, cut=9, bytes=65540, tick=3)
            reg.event("fleet", "kv_fallback", rid=3, replica=1,
                      reason="checksum_mismatch", tick=3)
            reg.event("fleet", "kv_corrupt_injected", replica=0,
                      slot=0, tick=3)
            reg.flush()
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import telemetry_report

        paths = [str(p) for p in tmp_path.glob("telemetry-rank*.jsonl")]
        report = telemetry_report.aggregate(
            telemetry_report.load_events(paths))
        f = report["fleet"]
        assert f["kv_handoffs"] == 2
        assert f["kv_handoff_bytes"] == 131080
        assert f["kv_fallbacks"] == {"checksum_mismatch": 1}
        assert f["kv_corrupt_injected"] == 1
        events = [row["event"] for row in f["timeline"]]
        assert "kv_handoff" in events and "kv_fallback" in events
        row = next(r for r in f["timeline"]
                   if r["event"] == "kv_handoff")
        assert row["detail"]["bytes"] == 65540
        assert row["detail"]["cut"] == 13
        buf = io.StringIO()
        telemetry_report.print_report(report, out=buf)
        text = buf.getvalue()
        assert "kv handoffs: 2" in text
        assert "checksum_mismatch=1" in text
        assert "1 corrupt injection(s)" in text


# ---------------------------------------------------------------------------
# misc edges
# ---------------------------------------------------------------------------

class TestFleetEdges:
    def test_max_steps_exhaustion_is_non_silent(self):
        fleet = _stub_fleet(FleetConfig(num_replicas=1), num_slots=2)
        for i in range(4):
            fleet.submit(_req(i, max_new=1000))
        with pytest.warns(UserWarning, match="max_steps"):
            done = fleet.run(max_steps=3)
        assert len(done) == 4
        assert all(c.finish_reason == "max_steps" for c in done)

    def test_needs_model_or_factory(self):
        with pytest.raises(ValueError, match="engine_factory"):
            ServeFleet(config=FleetConfig(num_replicas=1))

    def test_robust_config_passes_through(self):
        """The per-replica scheduler inherits the fleet's
        RobustConfig (decode retries, quarantine policy)."""
        rc = RobustConfig(decode_retries=7)
        fleet = _stub_fleet(FleetConfig(num_replicas=1, robust=rc))
        assert fleet.replicas[0].sched.robust.decode_retries == 7

    def test_diurnal_trace_is_deterministic_and_tiered(self):
        a = diurnal_trace(12, seed=3, burst_at=2.0, burst_n=3)
        b = diurnal_trace(12, seed=3, burst_at=2.0, burst_n=3)
        assert len(a) == len(b) == 15
        for x, y in zip(a, b):
            assert x.arrival == y.arrival and x.rid == y.rid
            np.testing.assert_array_equal(x.prompt, y.prompt)
        tiers = {r.tier for r in a}
        assert tiers == {"interactive", "batch"}
        assert a[0].arrival == 0.0
        assert all(a[i].arrival <= a[i + 1].arrival
                   for i in range(len(a) - 1))
        burst = [r for r in a if r.rid >= 12]
        assert len(burst) == 3
        assert len({r.arrival for r in burst}) == 1

    def test_health_counters_and_gauges(self, tmp_path):
        with use_registry(MetricsRegistry(jsonl_dir=str(tmp_path))) \
                as reg:
            fleet = _stub_fleet(FleetConfig(num_replicas=2))
            with faults.inject_replica_loss(1, 1):
                fleet.run([_req(i, max_new=4, arrival=float(i) * 0.2)
                           for i in range(6)])
            assert reg.counter_value("fleet/dispatched") >= 6
            assert reg.counter_value("fleet/migrated") >= 0
            assert reg.counter_value("fleet/respawns") == 1
            assert reg.counter_value("fleet/replicas_quarantined") == 1


# ---------------------------------------------------------------------------
# KV-state migration (ISSUE 18): capture, handoff, corruption fallback
# ---------------------------------------------------------------------------

class TestKVMigrationPolicy:
    def test_stats_carry_migration_fields(self):
        fleet = _stub_fleet(FleetConfig(num_replicas=2))
        fleet.run([_req(0), _req(1)])
        s = fleet.stats()
        assert s["kv_handoffs"] == 0
        assert s["kv_handoff_bytes"] == 0
        assert s["kv_fallback_reprefills"] == 0
        # stubs have no prefix cache -> no fleet-wide store
        assert s["fleet_prefix_hit_rate"] is None

    def test_capture_is_empty_for_stub_engines(self):
        """Engines without ``extract_kv_state`` (stubs, legacy)
        degrade to the token re-prefill migration — no handoff, no
        crash, zero lost."""
        fleet = _stub_fleet(FleetConfig(num_replicas=2,
                                        respawn_delay_ticks=1))
        with faults.inject_replica_loss(0, 1) as st:
            fleet.run([_req(i, max_new=6) for i in range(4)])
        s = fleet.stats()
        assert st["fired"] == 1
        assert s["lost_requests"] == 0
        assert s["kv_handoffs"] == 0

    def test_model_parallel_fleet_partition(self):
        with pytest.raises(ValueError, match="model_parallel"):
            FleetConfig(model_parallel=0)
        fleet = _stub_fleet(FleetConfig(num_replicas=2,
                                        model_parallel=2))
        for rep in fleet.replicas:
            assert rep.mesh is not None
            assert rep.mesh.axis_names == ("data", "tp")
            assert dict(zip(rep.mesh.axis_names,
                            rep.mesh.devices.shape))["tp"] == 2


@pytest.mark.multi_device
@pytest.mark.slow
class TestFleetTPMigrationE2E:
    """ISSUE-18 chaos acceptance: TP-sharded replicas under the fleet,
    constant-cost KV-state migration, loud checksum fallback."""

    def _cfg(self):
        return TransformerConfig(
            hidden_size=64, num_layers=2, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            compute_dtype=jnp.bfloat16, use_flash_attention=False,
            normalization="rmsnorm", position_embedding_type="rope",
            activation="swiglu", num_query_groups=4,
            ffn_hidden_size=128)

    def _params(self, cfg):
        parallel_state.destroy_model_parallel()
        return GPTModel(cfg).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32))["params"]

    def _serve_cfg(self):
        return ServeConfig(batch_buckets=(2,), prefill_buckets=(4, 16),
                           num_slots=4, eos_token_id=None,
                           temperature=0.0, prefix_cache=True,
                           prefix_min_len=2)

    def _trace(self, vocab):
        rs = np.random.RandomState(7)
        return [Request(rid=i,
                        prompt=rs.randint(0, vocab, 12).astype(np.int32),
                        max_new_tokens=8, arrival=0.0)
                for i in range(4)]

    def _run(self, cfg, params, *, kill=None, corrupt=None,
             jsonl_dir=None):
        parallel_state.destroy_model_parallel()
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=2, devices=jax.devices()[:2])
        model = GPTModel(cfg, decode=True)
        reg = MetricsRegistry(enabled=True, jsonl_dir=jsonl_dir)
        watcher = CompileWatcher(enabled=True)
        fleet = ServeFleet(model, params, self._serve_cfg(),
                           FleetConfig(num_replicas=2, model_parallel=2,
                                       respawn_delay_ticks=1),
                           registry=reg, watcher=watcher)
        try:
            if kill is not None:
                faults.arm_replica_loss(*kill)
            if corrupt is not None:
                faults.arm_kv_corrupt(*corrupt)
            done = fleet.run(self._trace(cfg.vocab_size))
        finally:
            faults.disarm_replica_loss()
            faults.disarm_kv_corrupt()
            parallel_state.destroy_model_parallel()
        return ({c.rid: list(map(int, c.tokens)) for c in done},
                fleet.stats(), watcher)

    def test_tp_kill_migrates_kv_token_identical(self, tmp_path):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        cfg = self._cfg()
        params = self._params(cfg)
        clean, s0, _ = self._run(cfg, params)
        assert s0["lost_requests"] == 0
        chaos, s1, watcher = self._run(
            cfg, params, kill=(0, 3), jsonl_dir=str(tmp_path))
        assert s1["lost_requests"] == 0
        assert s1["migrated_requests"] >= 1
        assert s1["kv_handoffs"] >= 1
        assert s1["kv_handoff_bytes"] > 0
        assert s1["kv_fallback_reprefills"] == 0
        assert chaos == clean                     # greedy identity
        assert s1["fleet_prefix_hit_rate"] is not None
        assert watcher.recompile_count() == 0
        events = []
        for p in tmp_path.glob("*.jsonl"):
            events += [json.loads(l) for l in p.open()]
        handoffs = [e for e in events if e.get("name") == "kv_handoff"]
        assert len(handoffs) == s1["kv_handoffs"]
        for e in handoffs:
            assert e["bytes"] > 0 and e["cut"] > 0

    def test_kv_corrupt_falls_back_loudly_once(self, tmp_path):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        cfg = self._cfg()
        params = self._params(cfg)
        got, s, _ = self._run(cfg, params, kill=(0, 3),
                              corrupt=(0, 3), jsonl_dir=str(tmp_path))
        assert s["lost_requests"] == 0
        assert s["requests_ok"] == 4              # streams complete
        assert s["kv_fallback_reprefills"] == 1   # exactly one, loud
        events = []
        for p in tmp_path.glob("*.jsonl"):
            events += [json.loads(l) for l in p.open()]
        fb = [e for e in events if e.get("name") == "kv_fallback"]
        assert len(fb) == 1
        assert fb[0]["reason"] == "checksum_mismatch"
        assert any(e.get("name") == "kv_corrupt_injected"
                   for e in events)

    def test_fleet_wide_prefix_beats_single_replica(self):
        """A system prompt prefilled by one replica hits on the other:
        the shared store's fleet-wide hit rate is never below what a
        single replica achieves on the same trace."""
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        cfg = self._cfg()
        params = self._params(cfg)
        rs = np.random.RandomState(11)
        system = rs.randint(0, cfg.vocab_size, 8).astype(np.int32)

        def trace():
            return [Request(
                rid=i,
                prompt=np.concatenate(
                    [system,
                     rs.randint(0, cfg.vocab_size, 3).astype(np.int32)]),
                max_new_tokens=4, arrival=0.0) for i in range(6)]

        def run(n_replicas):
            parallel_state.destroy_model_parallel()
            parallel_state.initialize_model_parallel(
                tensor_model_parallel_size_=2,
                devices=jax.devices()[:2])
            model = GPTModel(cfg, decode=True)
            fleet = ServeFleet(model, params, self._serve_cfg(),
                               FleetConfig(num_replicas=n_replicas,
                                           model_parallel=2))
            rs.seed(11); rs.randint(0, cfg.vocab_size, 8)  # re-sync tails
            done = fleet.run(trace())
            s = fleet.stats()
            parallel_state.destroy_model_parallel()
            assert len(done) == 6
            return s["fleet_prefix_hit_rate"]

        single = run(1)
        fleet_wide = run(2)
        assert single is not None and fleet_wide is not None
        assert fleet_wide >= single > 0
