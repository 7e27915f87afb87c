"""Serving-path fault tolerance (apex_tpu.serving.robust + ISSUE 7).

Covers:

- admission control: bounded queue, reject-newest vs shed-oldest,
  impossible-shape/duplicate-rid rejection (recorded ``serve/rejected``
  events, never exceptions), request storms;
- per-request deadlines: TTFT expiry from the queue, total-latency
  expiry from a slot, per-request overrides (fake clock — no sleeps);
- per-slot NaN quarantine: injected slot-NaN evicts exactly one
  request as ``poisoned`` with its KV rows reset in-graph while
  healthy slots keep decoding; the whole-batch guard escalates only
  when EVERY slot is non-finite;
- decode retry: a transient injected dispatch failure is absorbed
  with backoff, a persistent one exhausts the budget and fails only
  the implicated requests;
- graceful drain: PreemptionGuard -> admissions closed, in-flight
  finished inside the deadline, drain report emitted;
- scheduler edge cases: zero-slot config, duplicate request ids,
  ``run(max_steps=)`` exhaustion leaving non-silent terminal statuses;
- OOM census labels: the engine's post-mortem labels name the KV
  cache, not anonymous buffers;
- the 8-device chaos e2e acceptance: one slot-NaN + one transient
  decode failure over a Poisson trace -> exactly one ``poisoned``
  eviction, zero healthy-request failures, goodput >= 90% of the
  uninjected run, ``assert_no_recompiles`` across the entire run;
- the ``bench.py serve_chaos`` contract + round-12 schema gating.

Pure-policy paths run against a stub engine (no compiles — the
scheduler is host-side by design); integration paths share one real
tiny engine per module scope.
"""

import json
import os
import sys
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.resilience import NonFiniteError, faults
from apex_tpu.resilience.preemption import PreemptionGuard
from apex_tpu.serving import (
    DecodeFailedError,
    Request,
    RobustConfig,
    Scheduler,
    ServeConfig,
    ServeEngine,
    synthetic_trace,
)
from apex_tpu.serving import robust as robust_mod
from apex_tpu.telemetry import CompileWatcher, assert_no_recompiles
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


@pytest.fixture(scope="module")
def eng4(tiny):
    """One shared tiny engine (4 slots, small ladder) — AOT compiles
    once per module; schedulers are cheap and isolated per test."""
    cfg, model, params = tiny
    return ServeEngine(model, params, ServeConfig(
        batch_buckets=(1, 2, 4), prefill_buckets=(8, 16), num_slots=4))


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.disarm_slot_nan()
    faults.disarm_decode_failure()


def _req(rid, plen=3, max_new=4, arrival=0.0, **kw):
    return Request(rid=rid, prompt=np.arange(plen, dtype=np.int32) % 7,
                   max_new_tokens=max_new, arrival=arrival, **kw)


class _StubEngine:
    """Duck-typed engine for pure scheduler-policy tests: no jax, no
    compiles. ``finite_fn(chunk, call_idx)`` shapes the quarantine
    flags; ``decode_error`` raises from decode."""

    def __init__(self, num_slots=4, finite_fn=None, decode_error=None):
        self.config = types.SimpleNamespace(
            num_slots=num_slots, batch_buckets=(2, 4),
            prefill_buckets=(8,), eos_token_id=None, pad_token_id=0)
        self.max_len = 10_000
        self.decode_retries_total = 0
        self._decode_calls = 0
        self.spec = types.SimpleNamespace(
            bytes_per_slot=lambda: 0, cache_dtype_name=lambda: "stub")
        self._finite_fn = finite_fn
        self._decode_error = decode_error

    def kv_cache_bytes(self):
        return 0

    def prefill(self, slot_ids, prompts, *, pad_slot_ids=None):
        return np.ones(len(prompts), np.int32)

    def decode(self, slot_ids, tokens, *, pad_slot_ids=None,
               retries=0, backoff_s=0.0, backoff_cap_s=0.0):
        call = self._decode_calls
        self._decode_calls += 1
        if self._decode_error is not None:
            raise self._decode_error
        n = len(slot_ids)
        finite = (np.ones(n, bool) if self._finite_fn is None
                  else np.asarray(self._finite_fn(slot_ids, call)))
        return np.ones(n, np.int32), finite


# ---------------------------------------------------------------------------
# robust module: config + classification units
# ---------------------------------------------------------------------------

class TestRobustConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="admission_policy"):
            RobustConfig(admission_policy="drop_table")
        with pytest.raises(ValueError, match="max_pending"):
            RobustConfig(max_pending=-1)
        with pytest.raises(ValueError, match="decode_retries"):
            RobustConfig(decode_retries=-1)
        with pytest.raises(ValueError, match="ttft_deadline_s"):
            RobustConfig(ttft_deadline_s=0.0)
        with pytest.raises(ValueError, match="drain_deadline_s"):
            RobustConfig(drain_deadline_s=-1.0)

    def test_backoff_is_capped_exponential(self):
        b = [robust_mod.retry_backoff_s(a, 0.1, 0.5) for a in range(5)]
        assert b == [0.1, 0.2, 0.4, 0.5, 0.5]

    def test_retryable_classification(self):
        assert robust_mod.is_retryable_decode_error(
            faults.InjectedDecodeFailure("UNAVAILABLE: x"))
        assert robust_mod.is_retryable_decode_error(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
        assert robust_mod.is_retryable_decode_error(
            RuntimeError("UNAVAILABLE: connection reset"))
        assert not robust_mod.is_retryable_decode_error(
            ValueError("duplicate slot ids"))
        assert not robust_mod.is_retryable_decode_error(
            TypeError("bad argument"))


# ---------------------------------------------------------------------------
# admission control & load shedding (stub engine: pure policy)
# ---------------------------------------------------------------------------

class TestAdmissionControl:
    def test_reject_newest_bounds_the_queue(self):
        sched = Scheduler(_StubEngine(), robust=RobustConfig(max_pending=2))
        assert sched.submit(_req(0))
        assert sched.submit(_req(1))
        assert not sched.submit(_req(2))
        assert len(sched.pending) == 2
        assert [r.rid for r in sched.rejected] == [2]
        assert sched.rejected[0].reason == "queue_full"
        assert sched.stats()["shed_rate"] == pytest.approx(1 / 3, abs=1e-4)

    def test_shed_oldest_makes_room_for_newcomers(self):
        sched = Scheduler(_StubEngine(), robust=RobustConfig(
            max_pending=2, admission_policy="shed_oldest"))
        for i in range(5):
            assert sched.submit(_req(i))    # newcomers always accepted
        assert [r.rid for r in sched.pending] == [3, 4]
        assert [r.rid for r in sched.rejected] == [0, 1, 2]
        assert all(r.reason == "shed" for r in sched.rejected)

    def test_impossible_shapes_and_duplicates_reject_not_raise(self):
        sched = Scheduler(_StubEngine())
        assert sched.submit(_req(0))
        assert not sched.submit(_req(0))                 # duplicate rid
        assert not sched.submit(_req(1, plen=99))        # > largest bucket
        assert not sched.submit(_req(2, max_new=20_000))  # > max_len
        assert [r.reason for r in sched.rejected] == \
            ["duplicate_rid", "prompt_too_long", "budget_too_long"]
        assert len(sched.pending) == 1

    def test_rejections_land_counter_and_events(self, tmp_path):
        with use_registry(MetricsRegistry(jsonl_dir=str(tmp_path))) \
                as reg:
            sched = Scheduler(_StubEngine(),
                              robust=RobustConfig(max_pending=1))
            sched.submit(_req(0))
            sched.submit(_req(1))
            reg.flush()
            assert reg.counter_value("serve/rejected") == 1.0
        events = []
        for p in tmp_path.glob("telemetry-rank*.jsonl"):
            events += [json.loads(l) for l in p.read_text().splitlines()]
        rej = [e for e in events if e["kind"] == "serve"
               and e["name"] == "rejected"]
        assert len(rej) == 1 and rej[0]["rid"] == 1
        assert rej[0]["reason"] == "queue_full"

    def test_request_storm_sheds_through_bounded_queue(self):
        storm = faults.request_storm(12, seed=3, vocab_size=64)
        assert len({r.rid for r in storm}) == 12
        assert all(r.arrival == 0.0 for r in storm)
        sched = Scheduler(_StubEngine(), robust=RobustConfig(
            max_pending=3, admission_policy="shed_oldest"))
        for r in storm:
            sched.submit(r)
        assert len(sched.pending) == 3
        assert sched.health.rejected == 9
        done = sched.run()
        ok = [c for c in done
              if c.finish_reason in robust_mod.OK_STATUSES]
        assert len(ok) == 3                  # survivors all complete


# ---------------------------------------------------------------------------
# deadlines (stub engine + fake clock: no sleeps)
# ---------------------------------------------------------------------------

class TestDeadlines:
    def _clocked(self, robust, num_slots=2):
        t = [0.0]
        sched = Scheduler(_StubEngine(num_slots=num_slots),
                          robust=robust, clock=lambda: t[0])
        return sched, t

    def test_ttft_deadline_expires_queued_requests(self):
        sched, t = self._clocked(RobustConfig(ttft_deadline_s=5.0))
        for i in range(6):                    # 6 requests, 2 slots
            sched.submit(_req(i, max_new=50))
        for _ in range(4):
            t[0] += 3.0
            sched.step()
        expired = [c for c in sched.completed
                   if c.finish_reason == "deadline_exceeded"]
        assert expired, "queued requests never expired"
        for c in expired:
            assert len(c.tokens) == 0 and not np.isfinite(c.ttft_s)
        assert sched.health.expired == len(expired)

    def test_total_deadline_evicts_active_requests(self):
        sched, t = self._clocked(RobustConfig(total_deadline_s=4.0))
        sched.submit(_req(0, max_new=100))
        for _ in range(5):
            t[0] += 2.0
            sched.step()
        assert not sched.active
        (c,) = [c for c in sched.completed if c.rid == 0]
        assert c.finish_reason == "deadline_exceeded"
        assert len(c.tokens) > 0              # it WAS decoding

    def test_per_request_override_beats_config_default(self):
        sched, t = self._clocked(
            RobustConfig(total_deadline_s=1000.0), num_slots=4)
        sched.submit(_req(0, max_new=100, total_deadline_s=3.0))
        sched.submit(_req(1, max_new=5))
        for _ in range(8):
            t[0] += 2.0
            sched.step()
        reasons = {c.rid: c.finish_reason for c in sched.completed}
        assert reasons[0] == "deadline_exceeded"
        assert reasons[1] == "length"

    def test_no_deadline_means_no_expiry(self):
        sched, t = self._clocked(RobustConfig())
        sched.submit(_req(0, max_new=10))
        while sched.pending or sched.active:
            t[0] += 100.0
            sched.step()
        (c,) = sched.completed
        assert c.finish_reason == "length"


# ---------------------------------------------------------------------------
# quarantine policy + whole-batch guard (stub engine)
# ---------------------------------------------------------------------------

class TestQuarantinePolicy:
    def test_single_bad_slot_is_quarantined_healthy_continue(self):
        bad_slot = []

        def finite_fn(slot_ids, call):
            ok = np.ones(len(slot_ids), bool)
            if call == 1 and len(slot_ids) >= 2:
                bad_slot.append(int(slot_ids[0]))
                ok[0] = False
            return ok

        sched = Scheduler(_StubEngine(finite_fn=finite_fn))
        for i in range(3):
            sched.submit(_req(i, max_new=4))
        done = sched.run()
        reasons = sorted(c.finish_reason for c in done)
        assert reasons.count("poisoned") == 1
        assert reasons.count("length") == 2
        assert sched.health.quarantined == 1
        # the quarantined slot was freed and is reusable
        assert sorted(sched.free) == list(range(4))

    def test_whole_batch_nonfinite_escalates(self):
        sched = Scheduler(_StubEngine(
            finite_fn=lambda ids, call: np.zeros(len(ids), bool)))
        for i in range(3):
            sched.submit(_req(i, max_new=4))
        with pytest.raises(NonFiniteError, match="every slot"):
            sched.run()
        # quarantine bookkeeping landed BEFORE the escalation
        assert sched.health.all_slots_nonfinite == 1
        assert all(c.finish_reason == "poisoned" for c in sched.completed)

    def test_single_slot_batch_stays_per_slot_quarantine(self):
        # 1 active slot going non-finite cannot distinguish poisoned
        # weights from a poisoned request: quarantine wins, no raise
        sched = Scheduler(_StubEngine(
            finite_fn=lambda ids, call: np.zeros(len(ids), bool)))
        sched.submit(_req(0, max_new=4))
        done = sched.run()
        assert [c.finish_reason for c in done] == ["poisoned"]

    def test_quarantine_off_ignores_flags(self):
        sched = Scheduler(
            _StubEngine(finite_fn=lambda ids, c: np.zeros(len(ids), bool)),
            robust=RobustConfig(quarantine=False))
        sched.submit(_req(0, max_new=3))
        done = sched.run()
        assert [c.finish_reason for c in done] == ["length"]


# ---------------------------------------------------------------------------
# decode failure: retry exhaustion fails only the implicated chunk
# ---------------------------------------------------------------------------

class TestDecodeFailurePolicy:
    def test_decode_failed_error_fails_chunk_only(self):
        sched = Scheduler(_StubEngine(decode_error=DecodeFailedError(
            "boom", attempts=3, last_error=RuntimeError("UNAVAILABLE"))))
        for i in range(2):
            sched.submit(_req(i, max_new=4))
        done = sched.run()
        assert all(c.finish_reason == "failed" for c in done)
        assert sched.health.decode_failures >= 1
        assert sched.health.failed == 2
        assert sorted(sched.free) == list(range(4))  # slots recovered

    def test_non_retryable_error_propagates(self):
        sched = Scheduler(_StubEngine(decode_error=ValueError("bug")))
        sched.submit(_req(0, max_new=4))
        with pytest.raises(ValueError, match="bug"):
            sched.run()


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

class TestDrain:
    def test_preemption_guard_drains_inflight_and_cancels_pending(self):
        guard = PreemptionGuard()
        sched = Scheduler(_StubEngine(num_slots=2),
                          robust=RobustConfig(drain_deadline_s=1000.0),
                          guard=guard)
        for i in range(6):
            sched.submit(_req(i, max_new=3))
        real_step = Scheduler.step
        calls = []

        def step_then_preempt(self_):
            real_step(self_)
            calls.append(1)
            if len(calls) == 1:
                guard.trigger()
        sched.step = types.MethodType(step_then_preempt, sched)
        done = sched.run()
        rep = sched.drain_report
        assert rep is not None and rep.reason == "preempted"
        reasons = {c.rid: c.finish_reason for c in done}
        # the two admitted requests finished; the queue was cancelled
        assert sorted(r for r in reasons.values()) == \
            ["drained"] * 4 + ["length"] * 2
        assert rep.completed_in_drain >= 1
        assert rep.cancelled_pending == 4
        assert not rep.deadline_hit
        # admissions are closed post-drain
        assert not sched.submit(_req(99))
        assert sched.rejected[-1].reason == "draining"

    def test_drain_deadline_cancels_stragglers(self):
        t = [0.0]
        sched = Scheduler(_StubEngine(num_slots=2),
                          robust=RobustConfig(drain_deadline_s=1.0),
                          clock=lambda: t[0])
        sched.submit(_req(0, max_new=1000))
        sched.step()
        t[0] += 0.5
        sched.drain("requested")
        t[0] += 5.0                          # blow the drain window
        done = sched.run()
        rep = sched.drain_report
        assert rep.deadline_hit and rep.cancelled_active == 1
        assert [c.finish_reason for c in done] == ["drained"]

    def test_drain_report_event_lands(self, tmp_path):
        with use_registry(MetricsRegistry(jsonl_dir=str(tmp_path))) \
                as reg:
            sched = Scheduler(_StubEngine())
            sched.submit(_req(0, max_new=2))
            sched.drain("requested")
            sched.run()
            reg.flush()
        events = []
        for p in tmp_path.glob("telemetry-rank*.jsonl"):
            events += [json.loads(l) for l in p.read_text().splitlines()]
        names = [e["name"] for e in events if e["kind"] == "serve"]
        assert "drain_start" in names and "drain_report" in names


# ---------------------------------------------------------------------------
# scheduler edge cases (satellite): zero slots, max_steps, health
# ---------------------------------------------------------------------------

class TestSchedulerEdges:
    def test_zero_slot_config_is_rejected_loudly(self, tiny):
        cfg, model, params = tiny
        with pytest.raises(ValueError):
            ServeEngine(model, params, ServeConfig(num_slots=0))
        from apex_tpu.serving import KVCacheSpec

        with pytest.raises(ValueError, match="num_slots"):
            KVCacheSpec(model, 0)

    def test_max_steps_exhaustion_is_non_silent(self):
        sched = Scheduler(_StubEngine(num_slots=2))
        for i in range(4):
            sched.submit(_req(i, max_new=1000))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            done = sched.run(max_steps=3)
        assert any("max_steps" in str(x.message) for x in w)
        assert len(done) == 4
        assert all(c.finish_reason == "max_steps" for c in done)
        assert not sched.pending and not sched.active
        assert sched.health.max_steps == 4

    def test_health_snapshot_events(self, tmp_path):
        with use_registry(MetricsRegistry(jsonl_dir=str(tmp_path))) \
                as reg:
            sched = Scheduler(_StubEngine(),
                              robust=RobustConfig(health_every=1))
            for i in range(3):
                sched.submit(_req(i, max_new=3))
            sched.run()
            reg.flush()
        events = []
        for p in tmp_path.glob("telemetry-rank*.jsonl"):
            events += [json.loads(l) for l in p.read_text().splitlines()]
        health = [e for e in events if e["kind"] == "serve"
                  and e["name"] == "health"]
        assert len(health) >= 2               # periodic + end of run
        last = health[-1]
        assert last["completed_ok"] == 3 and last["pending"] == 0
        assert "shed_rate" in last and "quarantined" in last

    def test_stats_reports_goodput_and_reasons(self):
        def finite_fn(ids, call):
            ok = np.ones(len(ids), bool)
            if call == 0 and len(ids) >= 2:
                ok[-1] = False
            return ok
        sched = Scheduler(_StubEngine(finite_fn=finite_fn),
                          robust=RobustConfig(max_pending=2))
        for i in range(4):
            sched.submit(_req(i, max_new=3))
        sched.run()
        s = sched.stats()
        assert s["requests_rejected"] == 2
        assert s["requests_quarantined"] == 1
        assert s["requests_ok"] == s["requests_by_reason"].get("length", 0)
        assert s["goodput_tokens"] == sum(
            len(c.tokens) for c in sched.completed
            if c.finish_reason in robust_mod.OK_STATUSES)
        assert s["shed_rate"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# real engine integration: quarantine in-graph, retry, census labels
# ---------------------------------------------------------------------------

class TestEngineIntegration:
    def test_slot_nan_quarantines_and_resets_kv(self, tiny, eng4):
        cfg, model, params = tiny
        sched = Scheduler(eng4, robust=RobustConfig())
        for r in synthetic_trace(5, seed=11, mean_interarrival=0.2,
                                 prompt_lens=(3, 5), max_new=(6, 8),
                                 vocab_size=cfg.vocab_size):
            sched.submit(r)
        target = []
        checked = []
        while sched.pending or sched.active:
            if not target and len(sched.active) >= 2:
                target.append(sorted(sched.active)[0])
                faults.arm_slot_nan(target[0], eng4._decode_calls)
            sched.step()
            if target and not checked and sched.health.quarantined == 1:
                # right after the poisoning step, before the slot can
                # be reused as admission or padding: its KV rows were
                # reset IN the same dispatch, so the fill level is 0
                checked.append(int(eng4.slot_lengths()[target[0]]))
        assert target, "never reached 2 active slots"
        assert checked == [0], checked
        s = sched.stats()
        assert s["requests_quarantined"] == 1
        assert s["requests_ok"] == 4
        assert s["requests_failed"] == 0

    def test_transient_decode_failure_retries(self, tiny, eng4):
        cfg, model, params = tiny
        trace = synthetic_trace(3, seed=2, prompt_lens=(3, 5),
                                max_new=(3, 4),
                                vocab_size=cfg.vocab_size)
        with faults.inject_decode_failure(
                eng4._decode_calls, transient=True) as st:
            completed, stats = eng4.serve(
                trace, robust=RobustConfig(decode_retries=2,
                                           retry_backoff_s=0.001,
                                           retry_backoff_cap_s=0.01))
        assert st["fired"] == 1
        assert stats["decode_retries"] == 1
        assert stats["requests_ok"] == 3 and stats["requests_failed"] == 0

    def test_persistent_decode_failure_fails_chunk(self, tiny, eng4):
        cfg, model, params = tiny
        # both requests arrive together -> one prefill group -> the
        # armed (persistent) failure takes out exactly that chunk
        trace = [Request(rid=i, prompt=np.arange(3, dtype=np.int32),
                         max_new_tokens=4) for i in range(2)]
        with faults.inject_decode_failure(
                eng4._decode_calls, transient=False) as st:
            completed, stats = eng4.serve(
                trace, robust=RobustConfig(decode_retries=1,
                                           retry_backoff_s=0.001,
                                           retry_backoff_cap_s=0.01))
        assert st["fired"] == 2               # initial + 1 retry
        assert stats["requests_failed"] == len(completed) == 2
        assert all(c.finish_reason == "failed" for c in completed)

    def test_census_labels_name_kv_cache(self, eng4):
        from apex_tpu.telemetry import memory as tmemory

        labels = eng4.census_labels()
        assert set(labels) == {"params", "kv_cache"}
        census = tmemory.live_buffer_census(top_k=0, labels=labels)
        got = {row["label"] for row in census["groups"]}
        assert "kv_cache" in got, got
        kv_bytes = sum(r["bytes"] for r in census["groups"]
                       if r["label"] == "kv_cache")
        assert kv_bytes > 0


# ---------------------------------------------------------------------------
# the 8-device chaos e2e acceptance
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
class TestChaosE2E:
    def test_chaos_acceptance_8dev(self, tiny, dp_mesh):
        """ISSUE-7 acceptance: a Poisson trace on the 8-device mesh
        with ONE slot-NaN injection and ONE transient decode failure
        completes with exactly one ``poisoned`` eviction, zero
        healthy-request failures, goodput >= 90% of the uninjected
        run, and ``assert_no_recompiles`` holding across the entire
        chaos run."""
        cfg, model, params = tiny
        mesh = dp_mesh(8, axis_name="data")
        watcher = CompileWatcher(enabled=True)
        eng = ServeEngine(model, params, ServeConfig(
            batch_buckets=(2, 4, 8), prefill_buckets=(8, 16),
            num_slots=8), mesh=mesh, watcher=watcher)
        robust = RobustConfig(decode_retries=2, retry_backoff_s=0.002,
                              retry_backoff_cap_s=0.01)

        def trace():
            return synthetic_trace(
                13, seed=5, mean_interarrival=0.5,
                prompt_lens=(3, 6, 10), max_new=(8,),
                vocab_size=cfg.vocab_size)

        _, clean = eng.serve(trace(), robust=robust)
        assert clean["requests_ok"] == 13
        clean_goodput = clean["goodput_tokens"]

        sched = Scheduler(eng, robust=robust)
        for r in trace():
            sched.submit(r)
        nan_armed = fail_armed = False
        with assert_no_recompiles(watcher):
            while sched.pending or sched.active:
                if not nan_armed and len(sched.active) >= 2:
                    faults.arm_slot_nan(sorted(sched.active)[0],
                                        eng._decode_calls)
                    nan_armed = True
                elif nan_armed and not fail_armed and sched.active:
                    faults.arm_decode_failure(eng._decode_calls,
                                              transient=True)
                    fail_armed = True
                if not sched.active and sched.pending and \
                        min(r.arrival for r in sched.pending) \
                        > sched.tick:
                    sched.tick = min(r.arrival for r in sched.pending)
                sched.step()
        assert nan_armed and fail_armed
        stats = sched.stats()
        assert stats["requests_quarantined"] == 1, \
            stats["requests_by_reason"]
        assert stats["requests_failed"] == 0
        assert stats["requests_ok"] == 12
        assert stats["decode_retries"] >= 1
        assert stats["goodput_tokens"] >= 0.9 * clean_goodput
        assert watcher.recompile_count() == 0


# ---------------------------------------------------------------------------
# bench + schema contract
# ---------------------------------------------------------------------------

class TestServeChaosBench:
    # tier-1 budget (ISSUE 12): the in-process 8-dev chaos acceptance
    # above stays in tier-1 — same precedent as the fleet bench e2e
    @pytest.mark.slow
    def test_serve_chaos_bench_contract(self, monkeypatch, capsys):
        monkeypatch.setenv("APEX_TPU_SERVE_SMOKE", "1")
        monkeypatch.syspath_prepend(ROOT)
        import bench

        ret = bench.bench_serve_chaos(6, 3)
        line = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert line["metric"] == "serve_chaos_goodput_tokens_per_sec"
        assert line["value"] > 0
        assert ret["poisoned_evictions"] == 1
        assert ret["failed_requests"] == 0
        assert ret["decode_retries"] >= 1
        assert ret["shed_rate"] > 0
        assert ret["compile_count"] == 9      # (2,4,8)x(16,32) + 3 decode
        assert ret["recompiles_chaos"] == 0
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import bench_schema_check as bsc

        assert bsc.check_metric_line(line, round_n=12, errors=[]) == []
        errs = bsc.check_metric_line(line, round_n=11, errors=[])
        assert any("only defined from round 12" in e for e in errs)

    def test_schema_gate_round_12(self):
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import bench_schema_check as bsc

        base = {"metric": "serve_chaos_goodput_tokens_per_sec",
                "value": 1.0, "unit": "tokens/sec", "vs_baseline": 1.0,
                "tflops_per_sec": 0.0, "mfu": 0.0,
                "comm_bytes_per_step": 0,
                "measured_comm_bytes_per_step": None,
                "model_flops_per_step_xla": None,
                "peak_hbm_bytes": None, "hbm_headroom_pct": None,
                "compile_count": 9}
        errs = bsc.check_metric_line(dict(base), round_n=12, errors=[])
        assert any("serve_chaos line missing" in e for e in errs)
        full = dict(base, goodput_ratio=0.95, shed_rate=0.1,
                    poisoned_evictions=1, decode_retries=1,
                    ttft_p99_ms=2.0)
        assert bsc.check_metric_line(full, round_n=12, errors=[]) == []
        errs = bsc.check_metric_line(full, round_n=11, errors=[])
        assert any("only defined from round 12" in e for e in errs)
        # a round-11 serve_decode line with ttft fields is NOT flagged
        # by the chaos gate (shared field, scoped presence check)
        serve11 = dict(base, metric="serve_decode_tokens_per_sec_per_chip",
                       ttft_p50_ms=1.0, ttft_p99_ms=2.0,
                       tok_latency_p50_ms=0.5, tok_latency_p99_ms=0.9,
                       kv_cache_bytes=1024)
        assert bsc.check_metric_line(serve11, round_n=11, errors=[]) == []
