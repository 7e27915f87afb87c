"""apex_tpu.serving — AOT-compiled continuous-batching decode.

Covers the ISSUE-6 acceptance surface:

- int8 KV-cache parity against bf16 within the documented per-block
  quantization bound (store-level exact bound + the no-drift invariant
  of single-position updates + a 64-token end-to-end decode),
- scheduler admit/evict/slot-reuse invariants under a randomized
  arrival trace,
- an 8-device engine run under ``assert_no_recompiles`` while batch
  occupancy varies across the bucket ladder,
- greedy-decode token identity between ``ServeEngine`` and plain
  ``generation.generate`` for the bf16 cache,
- the ``bench.py serve_decode`` e2e contract (tokens/sec, p50/p99,
  kv_cache_bytes, flat compile_count across two traces, int8 bytes
  reduction >= 3.5x vs the fp32-equivalent model).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.generation import generate
from apex_tpu.parallel import compression
from apex_tpu.serving import (
    KVCacheSpec,
    Request,
    Scheduler,
    ServeConfig,
    ServeEngine,
    store_lengths,
    synthetic_trace,
    zero_row,
)
from apex_tpu.telemetry import CompileWatcher, assert_no_recompiles
from apex_tpu.telemetry.registry import MetricsRegistry, use_registry
from apex_tpu.transformer import parallel_state

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(**kw):
    base = dict(hidden_size=32, num_layers=2, num_attention_heads=4,
                vocab_size=64, max_position_embeddings=128,
                compute_dtype=jnp.float32, use_flash_attention=False)
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def tiny():
    """One tiny decode model + params shared across the module (the
    engine AOT-compiles per test, but params/model init once)."""
    parallel_state.destroy_model_parallel()
    cfg = _cfg()
    model = GPTModel(cfg, decode=True)
    params = GPTModel(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 4), jnp.int32))["params"]
    return cfg, model, params


def _engine(model, params, *, mode="bf16", mesh=None, watcher=None,
            **kw):
    defaults = dict(batch_buckets=(1, 2, 4), prefill_buckets=(8, 16),
                    num_slots=4, cache_mode=mode)
    defaults.update(kw)
    return ServeEngine(model, params, ServeConfig(**defaults),
                       mesh=mesh, watcher=watcher)


# ---------------------------------------------------------------------------
# kv_cache: layout, quantization bound, no-drift updates
# ---------------------------------------------------------------------------

class TestKVCache:
    def test_rows_blockwise_roundtrip_bound(self, rng):
        """The compression primitive the cache rides on: per-row
        blockwise int8 round-trip error <= absmax_block / 254."""
        x = jnp.asarray(rng.randn(16, 3, 100).astype(np.float32))
        q, s = compression.quantize_rows_blockwise(x, 32)
        out = compression.dequantize_rows_blockwise(q, s, n=100)
        x2 = np.asarray(x).reshape(16, 3, -1)
        # per-32-lane-block bound
        for blk in range(4):
            sl = np.s_[..., blk * 32:(blk + 1) * 32]
            bound = np.abs(x2[sl]).max(axis=-1, keepdims=True) / 254.0
            err = np.abs(np.asarray(out)[sl] - x2[sl])
            assert (err <= bound + 1e-7).all()

    def test_store_roundtrip_within_bound(self, tiny):
        cfg, model, params = tiny
        spec = KVCacheSpec(model, 2, mode="int8")
        rows = zero_row(spec.template)
        rows = jax.tree_util.tree_map(
            lambda l: jnp.asarray(
                np.random.RandomState(0).randn(*l.shape) * 0.1,
                l.dtype) if l.ndim >= 3 else l, rows)
        stacked = jax.tree_util.tree_map(
            lambda l: jnp.stack([l, l]), rows)
        q = spec.quantize_rows(stacked)
        back = spec.materialize_rows(q)
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(stacked)[0],
                jax.tree_util.tree_flatten_with_path(back)[0]):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            names = [str(getattr(e, "key", e)) for e in pa]
            if not names[-1].startswith("cached_"):
                np.testing.assert_array_equal(a, b)
                continue
            flat = a.reshape(a.shape[0], -1, int(np.prod(a.shape[-3:])))
            bound = np.abs(flat).max(-1) / 254.0  # one block per pos
            err = np.abs((a - b).reshape(flat.shape)).max(-1)
            assert (err <= bound + 1e-7).all()

    def test_update_rows_at_is_drift_free(self, tiny):
        """A decode append re-quantizes ONLY its own position: every
        other block's int8 payload and scale must be bit-identical."""
        cfg, model, params = tiny
        spec = KVCacheSpec(model, 2, mode="int8")
        rs = np.random.RandomState(1)
        mk = jax.tree_util.tree_map(
            lambda sd: jnp.asarray(rs.randn(2, *sd.shape) * 0.1,
                                   sd.dtype), spec.template)
        store_rows = spec.quantize_rows(mk)
        new_rows = jax.tree_util.tree_map(
            lambda l: l + jnp.asarray(rs.randn(*l.shape) * 0.1,
                                      l.dtype), mk)
        positions = jnp.asarray([3, 7], jnp.int32)
        updated = spec.update_rows_at(store_rows, new_rows, positions)
        flat_old = jax.tree_util.tree_flatten_with_path(
            store_rows,
            is_leaf=lambda l: isinstance(l, dict) and "q" in l)[0]
        flat_new = jax.tree_util.tree_flatten_with_path(
            updated,
            is_leaf=lambda l: isinstance(l, dict) and "q" in l)[0]
        checked = 0
        for (path, old), (_, new) in zip(flat_old, flat_new):
            if not (isinstance(old, dict) and "q" in old):
                continue
            qo, qn = np.asarray(old["q"]), np.asarray(new["q"])
            so, sn = np.asarray(old["scale"]), np.asarray(new["scale"])
            t = qo.shape[-3]
            for row, pos in enumerate((3, 7)):
                keep = [i for i in range(t) if i != pos]
                np.testing.assert_array_equal(qo[row][keep],
                                              qn[row][keep])
                np.testing.assert_array_equal(so[row][keep],
                                              sn[row][keep])
                assert not np.array_equal(qo[row][pos], qn[row][pos])
            checked += 1
        assert checked >= 2  # cached_key + cached_value per layer

    def test_int8_bytes_reduction_vs_fp32(self, tiny):
        """The scale-inclusive int8 store is >= 3.5x smaller than the
        fp32-equivalent cache (docs/serving.md worked example)."""
        cfg, model, params = tiny
        spec = KVCacheSpec(model, 8, mode="int8")
        ratio = spec.total_bytes(kv_itemsize=4) / spec.total_bytes()
        assert ratio >= 3.5

    def test_bad_mode_and_lengths(self, tiny):
        cfg, model, params = tiny
        with pytest.raises(ValueError, match="mode"):
            KVCacheSpec(model, 2, mode="fp8")
        spec = KVCacheSpec(model, 3)
        lens = store_lengths(spec.allocate())
        np.testing.assert_array_equal(np.asarray(lens), [0, 0, 0])


# ---------------------------------------------------------------------------
# engine: token identity, int8 end-to-end, guard rails
# ---------------------------------------------------------------------------

class TestEngineParity:
    @pytest.mark.slow  # duplicate coverage: the int8 64-token decode
    # parity below pins the same greedy stream (tier-1 budget, 14s)
    def test_greedy_token_identity_vs_generate(self, tiny):
        """bf16(-mode) engine greedy output == generate() greedy, per
        request, across mixed prompt lengths sharing one batch."""
        cfg, model, params = tiny
        eng = _engine(model, params)
        rs = np.random.RandomState(0)
        prompts = [rs.randint(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (3, 7, 5, 4)]
        reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        completed, stats = eng.serve(reqs)
        assert len(completed) == 4
        for c in completed:
            ref = generate(model, params,
                           jnp.asarray(prompts[c.rid])[None, :],
                           max_new_tokens=6)
            np.testing.assert_array_equal(
                np.asarray(ref)[0, len(prompts[c.rid]):], c.tokens)

    def test_int8_64_token_decode_parity(self, tiny):
        """The acceptance decode: 64 generated tokens through the int8
        cache match the bf16 cache greedy stream — the per-block read
        error (<= absmax/254, pinned at the store level above) stays
        below every greedy decision boundary of this model."""
        cfg, model, params = tiny
        rs = np.random.RandomState(2)
        prompt = rs.randint(0, cfg.vocab_size, 9).astype(np.int32)
        req = lambda: [Request(rid=0, prompt=prompt, max_new_tokens=64)]
        out = {}
        for mode in ("bf16", "int8"):
            eng = _engine(model, params, mode=mode,
                          prefill_buckets=(16,), batch_buckets=(1, 2))
            completed, _ = eng.serve(req())
            out[mode] = completed[0].tokens
            assert len(completed[0].tokens) == 64
        np.testing.assert_array_equal(out["bf16"], out["int8"])

    def test_eos_finishes_early(self, tiny):
        cfg, model, params = tiny
        rs = np.random.RandomState(0)
        prompt = rs.randint(0, cfg.vocab_size, 5).astype(np.int32)
        ref = generate(model, params, jnp.asarray(prompt)[None, :],
                       max_new_tokens=8)
        eos = int(np.asarray(ref)[0, len(prompt) + 2])  # 3rd new token
        eng = _engine(model, params, eos_token_id=eos)
        completed, _ = eng.serve(
            [Request(rid=0, prompt=prompt, max_new_tokens=8)])
        c = completed[0]
        assert c.finish_reason == "eos"
        assert c.tokens[-1] == eos
        assert len(c.tokens) <= 8

    def test_validation(self, tiny):
        cfg, model, params = tiny
        full = GPTModel(cfg)  # decode=False
        with pytest.raises(ValueError, match="decode=True"):
            ServeEngine(full, params, ServeConfig())
        with pytest.raises(ValueError, match="num_slots"):
            _engine(model, params, batch_buckets=(16,), num_slots=4)
        with pytest.raises(ValueError, match="max_position"):
            _engine(model, params, prefill_buckets=(4096,))
        # impossible shapes are admission-control rejections (recorded
        # serve/rejected events), not exceptions — tests/L0/
        # test_serving_robust.py covers the full rejection surface
        eng = _engine(model, params)
        sched = Scheduler(eng)
        assert not sched.submit(Request(
            rid=0, prompt=np.zeros(99, np.int32), max_new_tokens=1))
        assert not sched.submit(Request(
            rid=1, prompt=np.zeros(8, np.int32),
            max_new_tokens=10_000))
        assert [r.reason for r in sched.rejected] == \
            ["prompt_too_long", "budget_too_long"]
        assert not sched.pending


# ---------------------------------------------------------------------------
# scheduler: continuous-batching invariants
# ---------------------------------------------------------------------------

class _CheckedScheduler(Scheduler):
    """Scheduler that asserts the slot-map invariants after every
    step: active and free partition the slot space, no request is in
    flight twice, completions never duplicate."""

    def step(self):
        super().step()
        active = set(self.active)
        free = set(self.free)
        assert not (active & free), "slot both active and free"
        assert active | free <= set(range(self.num_slots))
        assert len(self.free) == len(free), "duplicate free slot"
        rids = [st.req.rid for st in self.active.values()]
        rids += [c.rid for c in self.completed]
        rids += [r.rid for r in self.pending]
        assert len(rids) == len(set(rids)), "request tracked twice"


class TestScheduler:
    def test_randomized_trace_invariants(self, tiny):
        """Admit/evict/slot-reuse under a randomized Poisson trace with
        more requests than slots: every request completes exactly once,
        within its token budget, and slots are recycled."""
        cfg, model, params = tiny
        eng = _engine(model, params)
        trace = synthetic_trace(
            13, seed=7, mean_interarrival=0.7,
            prompt_lens=(3, 5, 9, 14), max_new=(2, 5, 9),
            vocab_size=cfg.vocab_size)
        sched = _CheckedScheduler(eng)
        completed = sched.run(trace)
        assert sorted(c.rid for c in completed) == list(range(13))
        by_rid = {r.rid: r for r in trace}
        for c in completed:
            assert 1 <= len(c.tokens) <= by_rid[c.rid].max_new_tokens
            assert c.ttft_s >= 0.0
        # slot reuse: 13 requests through 4 slots
        assert sorted(sched.free) == list(range(4))
        assert not sched.active and not sched.pending
        stats = sched.stats()
        assert stats["requests_completed"] == 13
        assert stats["tokens_generated"] == sum(
            len(c.tokens) for c in completed)
        assert stats["ttft_p99_ms"] >= stats["ttft_p50_ms"] >= 0.0
        assert stats["tok_latency_p99_ms"] >= \
            stats["tok_latency_p50_ms"] >= 0.0

    def test_trace_determinism(self):
        a = synthetic_trace(6, seed=3)
        b = synthetic_trace(6, seed=3)
        for x, y in zip(a, b):
            assert x.arrival == y.arrival
            assert x.max_new_tokens == y.max_new_tokens
            np.testing.assert_array_equal(x.prompt, y.prompt)
        c = synthetic_trace(6, seed=4)
        assert any(not np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c))

    def test_serve_telemetry(self, tiny, tmp_path):
        """serve/* instruments land: ttft + tok_latency histograms
        (with the new p50/p99 reservoir fields), occupancy gauge,
        request_done + kv_cache events."""
        cfg, model, params = tiny
        with use_registry(MetricsRegistry(jsonl_dir=str(tmp_path))) \
                as reg:
            eng = _engine(model, params)
            eng.serve(synthetic_trace(5, seed=1, prompt_lens=(3, 6),
                                      max_new=(3, 4),
                                      vocab_size=cfg.vocab_size))
            reg.flush()
            snap = reg.snapshot()
        h = snap["histograms"]["serve/ttft"]
        assert h["count"] == 5
        assert h["p99"] >= h["p50"] > 0.0
        assert snap["histograms"]["serve/tok_latency"]["count"] > 0
        assert snap["counters"]["serve/requests_completed"] == 5.0
        assert snap["counters"]["serve/aot_compiles"] > 0
        assert "serve/slot_occupancy" in snap["gauges"]
        assert snap["gauges"]["serve/kv_cache_bytes"] == \
            eng.kv_cache_bytes()
        events = []
        for p in tmp_path.glob("telemetry-rank*.jsonl"):
            events += [json.loads(l) for l in
                       p.read_text().splitlines()]
        serve_ev = [e for e in events if e["kind"] == "serve"]
        assert [e for e in serve_ev if e["name"] == "engine_start"]
        assert len([e for e in serve_ev
                    if e["name"] == "request_done"]) == 5
        census = [e for e in serve_ev if e["name"] == "kv_cache"]
        assert census and census[-1]["slots_total"] == 4

    def test_histogram_percentiles(self):
        reg = MetricsRegistry(enabled=True)
        h = reg.histogram("x")
        for v in range(1, 101):
            h.observe(float(v))
        assert abs(h.percentile(50) - 50.5) < 1e-9
        assert h.percentile(99) > 99.0
        s = h.summary()
        assert s["p50"] == h.percentile(50)


# ---------------------------------------------------------------------------
# 8-device mesh + recompile discipline
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
class TestMeshServing:
    def test_sharded_engine_no_recompiles_across_ladder(self, tiny,
                                                        dp_mesh):
        """The acceptance invariant: an 8-device data-sharded engine
        serves a trace whose occupancy sweeps the bucket ladder
        (staggered arrivals -> 1..8 active) with ZERO XLA compiles
        after startup, and the compile count equals the ladder size."""
        cfg, model, params = tiny
        mesh = dp_mesh(8, axis_name="data")
        watcher = CompileWatcher(enabled=True)
        eng = _engine(model, params, mode="int8", mesh=mesh,
                      watcher=watcher, batch_buckets=(2, 4, 8),
                      prefill_buckets=(8, 16), num_slots=8)
        ladder = 3 * 2 + 3
        assert eng.compile_count == ladder
        trace = synthetic_trace(
            14, seed=5, mean_interarrival=0.6,
            prompt_lens=(3, 6, 10, 14), max_new=(3, 8, 14),
            vocab_size=cfg.vocab_size)
        with assert_no_recompiles(watcher):
            completed, stats = eng.serve(trace)
        assert len(completed) == 14
        assert eng.compile_count == ladder  # flat, by construction
        assert watcher.recompile_count() == 0
        # occupancy genuinely varied (staggered Poisson arrivals over
        # 8 slots): more than one decode bucket was exercised
        assert stats["decode_steps"] > 0
        lens = eng.slot_lengths()
        assert lens.shape == (8,)

    @pytest.mark.slow  # tier-1 budget (round 23): no_recompiles_across_ladder is the stronger gate
    def test_two_traces_same_executables(self, tiny, dp_mesh):
        """Different arrival patterns through one engine: compile
        count identical (trivially — nothing compiled at all)."""
        cfg, model, params = tiny
        mesh = dp_mesh(8, axis_name="data")
        watcher = CompileWatcher(enabled=True)
        eng = _engine(model, params, mesh=mesh, watcher=watcher,
                      batch_buckets=(2, 4, 8),
                      prefill_buckets=(8, 16), num_slots=8)
        count0 = eng.compile_count
        out = {}
        for seed, gap in ((0, 0.25), (1, 1.5)):
            trace = synthetic_trace(
                6, seed=seed, mean_interarrival=gap,
                prompt_lens=(4, 8), max_new=(4, 6),
                vocab_size=cfg.vocab_size)
            with assert_no_recompiles(watcher):
                completed, _ = eng.serve(trace)
            out[seed] = completed
        assert eng.compile_count == count0


# ---------------------------------------------------------------------------
# e2e: the bench contract
# ---------------------------------------------------------------------------

class TestServeBenchE2E:
    # tier-1 budget (ISSUE 12): the in-process two-trace /
    # sharded-ladder e2es above keep the flat-compile invariant in
    # tier-1 — same precedent as the fleet bench e2e
    @pytest.mark.slow
    def test_serve_decode_bench_contract(self, monkeypatch, capsys):
        """bench.py serve_decode on the (up to) 8-device CPU mesh:
        emits tokens/sec, p50/p99 TTFT + per-token latency,
        kv_cache_bytes and compile_count; zero compiles during trace B
        (different arrival pattern, same ladder); int8 bytes cut
        >= 3.5x vs the fp32-equivalent store."""
        monkeypatch.setenv("APEX_TPU_SERVE_SMOKE", "1")
        monkeypatch.syspath_prepend(ROOT)
        import bench

        ret = bench.bench_serve_decode(4, 3)
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["metric"] == "serve_decode_tokens_per_sec_per_chip"
        assert line["value"] > 0
        for key in ("ttft_p50_ms", "ttft_p99_ms", "tok_latency_p50_ms",
                    "tok_latency_p99_ms", "kv_cache_bytes"):
            assert isinstance(line[key], (int, float))
        assert line["compile_count"] == 9  # (2,4,8) x (16,32) + decode
        assert line["recompiles_trace_b"] == 0
        assert ret["kv_cache_reduction_vs_fp32"] >= 3.5
        # the emitted line passes the round-11 schema gate
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import bench_schema_check as bsc

        assert bsc.check_metric_line(line, round_n=11, errors=[]) == []
        errs = bsc.check_metric_line(line, round_n=10, errors=[])
        assert errs  # serve fields are not defined before round 11


class TestSchemaGate:
    def test_serve_fields_round_gating(self):
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import bench_schema_check as bsc

        base = {"metric": "serve_decode_tokens_per_sec_per_chip",
                "value": 1.0, "unit": "tokens/sec", "vs_baseline": 1.0,
                "tflops_per_sec": 0.0, "mfu": 0.0,
                "comm_bytes_per_step": 0,
                "measured_comm_bytes_per_step": None,
                "model_flops_per_step_xla": None,
                "peak_hbm_bytes": None, "hbm_headroom_pct": None,
                "compile_count": 9}
        # round 11 without the serve fields: flagged
        errs = bsc.check_metric_line(dict(base), round_n=11, errors=[])
        assert any("serve_decode line missing" in e for e in errs)
        full = dict(base, ttft_p50_ms=1.0, ttft_p99_ms=2.0,
                    tok_latency_p50_ms=0.5, tok_latency_p99_ms=0.9,
                    kv_cache_bytes=1024)
        assert bsc.check_metric_line(full, round_n=11, errors=[]) == []
        # pre-round-11 records must not carry them
        errs = bsc.check_metric_line(full, round_n=9, errors=[])
        assert any("only defined from round 11" in e for e in errs)
        # non-serve metrics are unaffected at round 11
        other = dict(base, metric="gpt2_345m_tokens_per_sec_per_chip")
        assert bsc.check_metric_line(other, round_n=11, errors=[]) == []


# ---------------------------------------------------------------------------
# canonical KV payloads: checksums, consolidation, the migration wire format
# ---------------------------------------------------------------------------

class TestKVCanonical:
    def _spec(self, mode="int8"):
        parallel_state.destroy_model_parallel()
        cfg = _cfg()
        return KVCacheSpec(GPTModel(cfg, decode=True), 4, mode=mode)

    def test_payload_checksum_chains_and_detects_flip(self):
        from apex_tpu.serving.kv_cache import payload_checksum

        tree = {"a": np.arange(8, dtype=np.float32),
                "b": np.ones((2, 3), np.int8)}
        crc = payload_checksum(tree)
        assert crc == payload_checksum(tree)  # deterministic
        # chaining folds state forward
        assert payload_checksum(tree, crc) != crc
        flipped = jax.tree_util.tree_map(np.copy, tree)
        flipped["b"].reshape(-1).view(np.uint8)[0] ^= 0xFF
        assert payload_checksum(flipped) != crc

    def test_host_zero_row_canonical_scales_groups(self):
        spec = self._spec(mode="bf16")
        r1 = spec.host_zero_row(tp=1)
        r2 = spec.host_zero_row(tp=2)
        l1 = jax.tree_util.tree_flatten_with_path(r1)[0]
        l2 = {_n(p): v for p, v in
              jax.tree_util.tree_flatten_with_path(r2)[0]}
        from apex_tpu.serving.kv_cache import _is_kv, _names
        for path, v in l1:
            w = l2[_names(path)]
            if _is_kv(_names(path)):
                # groups axis (-2) doubles; everything else identical
                assert w.shape == v.shape[:-2] + (2 * v.shape[-2],
                                                  v.shape[-1:][0],)
            else:
                assert w.shape == v.shape

    def test_store_and_row_pspecs_shard_head_axis(self):
        from jax.sharding import PartitionSpec as P
        from apex_tpu.serving.kv_cache import KV_LEAF_PREFIX, _names

        def is_kv_path(path):
            return any(n.startswith(KV_LEAF_PREFIX)
                       for n in _names(path))

        for mode in ("bf16", "int8"):
            spec = self._spec(mode=mode)
            sps = jax.tree_util.tree_flatten_with_path(
                spec.store_pspecs("data", "tp"),
                is_leaf=lambda l: isinstance(l, P))[0]
            for path, p in sps:
                if is_kv_path(path):
                    assert p[-1] == "tp" and all(
                        a is None for a in p[:-1])
                else:
                    assert p == P()
            rps = jax.tree_util.tree_flatten_with_path(
                spec.row_pspecs("tp", lead=1),
                is_leaf=lambda l: isinstance(l, P))[0]
            for path, p in rps:
                if is_kv_path(path):
                    assert p[-1] == "tp"
                else:
                    assert p == P()

    def test_host_global_store_scales_sharded_axis(self):
        spec = self._spec(mode="int8")
        from apex_tpu.serving.kv_cache import _is_kv, _names
        g1 = jax.tree_util.tree_flatten_with_path(
            spec.host_global_store(tp=1),
            is_leaf=lambda l: isinstance(l, dict) and "q" in l)[0]
        g2 = {_names(p): v for p, v in jax.tree_util.tree_flatten_with_path(
            spec.host_global_store(tp=2),
            is_leaf=lambda l: isinstance(l, dict) and "q" in l)[0]}
        for path, v in g1:
            w = g2[_names(path)]
            if isinstance(v, dict):
                assert w["q"].shape[-2] == 2 * v["q"].shape[-2]
                assert w["scale"].shape[-2] == 2 * v["scale"].shape[-2]
            else:
                assert w.shape == v.shape

    def test_int8_requant_idempotent_bit_exact(self):
        """Dequantize -> requantize reproduces the int8 codes exactly:
        the invariant that makes seeding a survivor's store from the
        dequantized migration payload reproduce the donor's store."""
        spec = self._spec(mode="int8")
        rng = np.random.RandomState(3)
        row = jax.tree_util.tree_map(
            lambda sd: jnp.asarray(
                rng.standard_normal(sd.shape).astype(np.float32),
                sd.dtype),
            spec.template)
        q1 = spec.quantize_rows(row)
        deq = spec.materialize_rows(q1)
        q2 = spec.quantize_rows(deq)

        def codes(t):
            return [np.asarray(l["q"]) for l in
                    jax.tree_util.tree_leaves(
                        t, is_leaf=lambda l: isinstance(l, dict)
                        and "q" in l)
                    if isinstance(l, dict)]

        for a, b in zip(codes(q1), codes(q2)):
            np.testing.assert_array_equal(a, b)

    def test_consolidate_roundtrips_global_store_row(self):
        """device-get a global-store slot (tp=2 layout) ->
        consolidate -> canonical rows match the tp-scaled zero
        template exactly (and a filled bf16 row passes through)."""
        spec = self._spec(mode="bf16")
        store = spec.host_global_store(tp=2)
        rows = jax.tree_util.tree_map(lambda l: l[1], store)
        canon = spec.consolidate_host_rows(rows, tp=2)
        tmpl = spec.host_zero_row(tp=2)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b),
            canon, tmpl)

    def test_consolidate_int8_dequantizes_per_rank(self):
        spec = self._spec(mode="int8")
        store = spec.host_global_store(tp=2)
        rows = jax.tree_util.tree_map(
            lambda l: np.copy(l[0]), store)
        # stamp rank-distinct codes into one K leaf and check they land
        # in rank order on the canonical groups axis
        from apex_tpu.serving.kv_cache import _names
        flat = jax.tree_util.tree_flatten_with_path(
            rows, is_leaf=lambda l: isinstance(l, dict) and "q" in l)[0]
        kv = [(p, l) for p, l in flat if isinstance(l, dict)][0][1]
        nb = kv["q"].shape[-2] // 2
        kv["q"][..., :nb, :] = 1          # rank 0 codes
        kv["q"][..., nb:, :] = 2          # rank 1 codes
        kv["scale"][..., :nb, :] = 1.0
        kv["scale"][..., nb:, :] = 0.5
        canon = spec.consolidate_host_rows(rows, tp=2)
        leaf = [l for p, l in jax.tree_util.tree_flatten_with_path(
            canon)[0] if not isinstance(l, dict)]
        got = [np.asarray(l, np.float32) for l in leaf
               if l.ndim >= 3 and l.shape[-2] > 1][0]
        g = got.shape[-2] // 2
        assert np.allclose(got[..., :g, :], 1.0)   # rank 0: 1 * 1.0
        assert np.allclose(got[..., g:, :], 1.0)   # rank 1: 2 * 0.5

    def test_consolidate_rejects_incompatible_layout(self):
        spec = self._spec(mode="bf16")
        rows = spec.host_zero_row(tp=2)
        with pytest.raises(ValueError, match="canonical layout"):
            spec.consolidate_host_rows(rows, tp=4)  # wrong tp scale
        bad = jax.tree_util.tree_map(
            lambda l: l.astype(np.float32), rows)
        with pytest.raises(ValueError):
            spec.consolidate_host_rows(bad, tp=2)   # wrong dtype


def _n(path):
    from apex_tpu.serving.kv_cache import _names
    return _names(path)


# ---------------------------------------------------------------------------
# tensor-parallel serving: big-model engines on a (data, model) slice
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
class TestTPServing:
    def _tp_engine(self):
        """The shared tiny TP=2 engine (same instance the
        serve_decode_tp lint target builds — lru-cached, so tier-1
        pays its ladder once across analysis + serving tests)."""
        from apex_tpu.analysis.targets import serve_decode_tp_step
        serve_decode_tp_step()  # builds engine + rebinds parallel_state
        from apex_tpu.analysis.targets import _tiny_engine_tp
        return _tiny_engine_tp()

    def test_validation_refuses_tp_without_mesh(self, tiny):
        cfg, model, params = tiny
        parallel_state.destroy_model_parallel()
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=2, devices=jax.devices()[:2])
        try:
            with pytest.raises(ValueError, match="mesh"):
                _engine(model, params)
            from jax.sharding import Mesh
            bad = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                       ("data", "tp"))
            with pytest.raises(ValueError, match="data"):
                _engine(model, params, mesh=bad)
            bad_ax = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                          ("data", "model"))
            with pytest.raises(ValueError, match="mesh axis 'tp'"):
                _engine(model, params, mesh=bad_ax)
            ok_mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                           ("data", "tp"))
            with pytest.raises(ValueError, match="hardwired"):
                _engine(model, params, mesh=ok_mesh,
                        model_axis="model")
        finally:
            parallel_state.destroy_model_parallel()

    def test_extract_kv_state_layout_and_crc(self):
        from apex_tpu.serving.engine import kv_payload_crc

        engine = self._tp_engine()
        payloads = engine.extract_kv_state([0, 2])
        assert sorted(payloads) == [0, 2]
        for slot, payload in payloads.items():
            assert payload["slot"] == slot
            assert payload["tp"] == 2
            assert payload["crc"] == kv_payload_crc(payload)
            tmpl = engine.seed_row_template()
            jax.tree_util.tree_map(
                lambda a, b: (np.shape(a) == np.shape(b)) or
                (_ for _ in ()).throw(AssertionError((a.shape, b.shape))),
                payload["rows"], tmpl)
            # corruption breaks the crc
            leaf = jax.tree_util.tree_leaves(payload["rows"])[0]
            leaf.reshape(-1).view(np.uint8)[0] ^= 0xFF
            assert payload["crc"] != kv_payload_crc(payload)

    def test_tp_ladder_static_matches_measured_on_model_axis(self):
        """ISSUE-18 acceptance: the TP decode ladder entry's statically
        priced model-axis wire bytes equal the trace-measured
        ``comm/axis/tp_bytes`` counter exactly."""
        from apex_tpu.analysis import sharding
        from apex_tpu.analysis.targets import TARGETS

        fn, args, _ = TARGETS["serve_decode_tp"]()
        reg = MetricsRegistry(enabled=True)
        with use_registry(reg):
            lowered = fn.lower(*args)
        measured = reg.counter_value("comm/axis/tp_bytes")
        traced = fn.trace(*args)
        static = sharding.static_comm_bytes_by_axis(
            lowered.as_text(), traced.jaxpr)
        assert measured > 0
        assert static.get("tp") == int(round(measured))
        assert "?" not in static

    def test_prefix_scope_accounting_and_adoption(self, tiny):
        from apex_tpu.serving.prefix_cache import PrefixStore

        store = PrefixStore(max_entries=4, min_len=2)
        row = {"k": np.zeros((4,), np.float32)}
        store.insert(np.arange(8), row, scope="engine_a")
        cut, entry = store.lookup(np.arange(8), scope="engine_b")
        assert cut == 7 and entry is not None
        s = store.stats()
        assert s["by_scope"]["engine_a"]["insertions"] == 1
        assert s["by_scope"]["engine_b"]["hits"] == 1
        assert store.scope_stats("engine_b")["hit_tokens"] == 7
        assert store.scope_stats("nobody")["lookups"] == 0

    @pytest.mark.slow
    def test_tp2_engine_token_identical_to_tp1(self, tiny):
        """A GPT served over a (data=1, tp=2) slice decodes greedily
        token-identically to the single-chip engine, with the same
        flat compile accounting."""
        cfg, model, params = tiny
        from jax.sharding import Mesh

        rs = np.random.RandomState(5)
        prompts = [rs.randint(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (3, 7, 5)]

        def run(tp):
            parallel_state.destroy_model_parallel()
            if tp > 1:
                parallel_state.initialize_model_parallel(
                    tensor_model_parallel_size_=tp,
                    devices=jax.devices()[:tp])
            mesh = (Mesh(np.asarray(jax.devices()[:tp]).reshape(1, tp),
                         ("data", "tp")) if tp > 1 else None)
            watcher = CompileWatcher()
            eng = _engine(GPTModel(cfg, decode=True), params,
                          mesh=mesh, watcher=watcher,
                          batch_buckets=(2,), prefill_buckets=(8,),
                          eos_token_id=None, temperature=0.0)
            reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]
            completed, _ = eng.serve(reqs)
            parallel_state.destroy_model_parallel()
            return ({c.rid: list(c.tokens) for c in completed},
                    watcher)

        ref, w1 = run(1)
        got, w2 = run(2)
        assert got == ref
        # identical flat-compile accounting on both engines
        assert w2.compile_count() == w1.compile_count()
        assert w2.recompile_count() == 0
