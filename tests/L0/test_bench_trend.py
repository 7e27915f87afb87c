"""tools/bench_trend — the cross-round regression gate (ROADMAP item
5 trend slice, ISSUE 11 satellite): consecutive BENCH_rNN.json rounds
of the same config are compared, and rate drops / comm-bytes growth
beyond a per-config noise band — or ANY compile-count growth — fail
loudly. bench_error rounds and cross-backend pairs are skipped, never
compared. Also covers the ``telemetry_report --trend`` wiring."""

import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import bench_trend  # noqa: E402
import telemetry_report  # noqa: E402


def _wrap(n, metric="gpt2_345m_tokens_per_sec_per_chip", value=100.0,
          comm=1000, compiles=1, backend="cpu-mesh", **extra):
    parsed = {"metric": metric, "value": value, "unit": "tokens/sec",
              "vs_baseline": 1.0, "tflops_per_sec": 1.0, "mfu": 0.1,
              "comm_bytes_per_step": comm, "compile_count": compiles,
              "backend": backend}
    parsed.update(extra)
    return {"n": n, "cmd": f"python bench.py x  # r{n}", "rc": 0,
            "tail": "", "parsed": parsed}


def _error_wrap(n):
    return {"n": n, "cmd": "python bench.py x", "rc": 2, "tail": "",
            "parsed": {"metric": "bench_error", "value": 0,
                       "unit": "error", "vs_baseline": 0.0,
                       "kind": "no_tpu", "comm_bytes_per_step": None}}


def _write(tmp_path, wrappers):
    for w in wrappers:
        (tmp_path / f"BENCH_r{w['n']:02d}.json").write_text(
            json.dumps(w))
    return str(tmp_path)


def _trend(tmp_path, wrappers, **kw):
    d = _write(tmp_path, wrappers)
    return bench_trend.build_trend(bench_trend.load_rounds([d]), **kw)


class TestTrendGate:
    def test_flat_series_passes(self, tmp_path):
        t = _trend(tmp_path, [_wrap(16, value=100.0),
                              _wrap(17, value=98.0),
                              _wrap(18, value=103.0)])
        assert t["regressions"] == []
        rounds = t["configs"]["gpt2_345m_tokens_per_sec_per_chip"]["rounds"]
        assert [r["n"] for r in rounds] == [16, 17, 18]

    def test_rate_drop_beyond_band_fails_loudly(self, tmp_path):
        t = _trend(tmp_path, [_wrap(16, value=100.0),
                              _wrap(17, value=50.0)])
        (g,) = t["regressions"]
        assert g["field"] == "value"
        assert g["round_a"] == 16 and g["round_b"] == 17
        assert g["delta_pct"] == -50.0
        assert "band" in g["kind"]

    def test_drop_within_band_is_noise(self, tmp_path):
        t = _trend(tmp_path, [_wrap(16, value=100.0),
                              _wrap(17, value=80.0)])  # -20% < 25%
        assert t["regressions"] == []

    def test_comm_bytes_growth_fails(self, tmp_path):
        t = _trend(tmp_path, [_wrap(16, comm=1000),
                              _wrap(17, comm=2000)])
        (g,) = t["regressions"]
        assert g["field"] == "comm_bytes_per_step"
        assert "comm bytes grew" in g["kind"]

    def test_any_compile_count_growth_fails(self, tmp_path):
        """Compile counts are exact — +1 compile is a regression even
        though +1 value would be far inside any band."""
        t = _trend(tmp_path, [_wrap(16, compiles=9),
                              _wrap(17, compiles=10)])
        (g,) = t["regressions"]
        assert g["field"] == "compile_count"
        assert g["old"] == 9 and g["new"] == 10
        # shrinking the ladder is NOT a regression
        t = _trend(tmp_path, [_wrap(16, compiles=9),
                              _wrap(17, compiles=8)])
        assert t["regressions"] == []

    def test_bench_error_rounds_are_skipped_not_compared(self, tmp_path):
        """r17 errored: r16 -> r18 still compares (and catches the
        drop); the error round shows in the counts, not the series."""
        t = _trend(tmp_path, [_wrap(16, value=100.0), _error_wrap(17),
                              _wrap(18, value=40.0)])
        assert t["rounds_seen"] == 3
        assert t["rounds_successful"] == 2
        (g,) = t["regressions"]
        assert (g["round_a"], g["round_b"]) == (16, 18)

    def test_backend_switch_skips_the_pair(self, tmp_path):
        """cpu-mesh and tpu are different perf series: a 10x 'drop'
        crossing the boundary is not a regression; the next same-
        backend pair compares again."""
        t = _trend(tmp_path, [_wrap(16, value=1000.0, backend="tpu"),
                              _wrap(17, value=100.0,
                                    backend="cpu-mesh"),
                              _wrap(18, value=40.0,
                                    backend="cpu-mesh")])
        cfg = t["configs"]["gpt2_345m_tokens_per_sec_per_chip"]
        assert len(cfg["skipped"]) == 1
        assert "backend switch" in cfg["skipped"][0]["reason"]
        (g,) = t["regressions"]
        assert (g["round_a"], g["round_b"]) == (17, 18)

    def test_configs_tracked_independently(self):
        t = bench_trend.build_trend([
            {"file": "x", "n": 16,
             "parsed": _wrap(16, metric="a_steps_per_sec",
                             value=10.0)["parsed"]},
            {"file": "x", "n": 17,
             "parsed": _wrap(17, metric="a_steps_per_sec",
                             value=2.0)["parsed"]},
            {"file": "x", "n": 16,
             "parsed": _wrap(16, metric="serve_fleet_tokens_per_sec",
                             value=100.0)["parsed"]},
            {"file": "x", "n": 17,
             "parsed": _wrap(17, metric="serve_fleet_tokens_per_sec",
                             value=95.0)["parsed"]},
        ])
        assert [g["metric"] for g in t["regressions"]] == \
            ["a_steps_per_sec"]

    def test_per_metric_band_is_config_calibrated(self, tmp_path):
        """The serving configs carry a wider default band (wall-clock
        TTFT swings); a -30% serving drop is noise while the same drop
        on a training config is a regression."""
        t = _trend(tmp_path, [
            _wrap(16, metric="serve_fleet_tokens_per_sec", value=100.0),
            _wrap(17, metric="serve_fleet_tokens_per_sec", value=70.0)])
        assert t["regressions"] == []
        t = _trend(tmp_path, [_wrap(16, value=100.0),
                              _wrap(17, value=70.0)])
        assert [g["field"] for g in t["regressions"]] == ["value"]
        # explicit override wins over the table
        t = _trend(tmp_path, [
            _wrap(16, metric="serve_fleet_tokens_per_sec", value=100.0),
            _wrap(17, metric="serve_fleet_tokens_per_sec", value=70.0)],
            bands={"serve_fleet_tokens_per_sec": 0.1})
        assert [g["field"] for g in t["regressions"]] == ["value"]


class TestTrendCLI:
    def test_cli_exit_codes_and_loud_lines(self, tmp_path, capsys):
        _write(tmp_path, [_wrap(16, value=100.0),
                          _wrap(17, value=10.0)])
        rc = bench_trend.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "TREND REGRESSION" in out
        assert "gpt2_345m_tokens_per_sec_per_chip" in out

    def test_cli_clean_and_json(self, tmp_path, capsys):
        _write(tmp_path, [_wrap(16), _wrap(17)])
        assert bench_trend.main([str(tmp_path)]) == 0
        capsys.readouterr()
        assert bench_trend.main([str(tmp_path), "--json"]) == 0
        report = json.loads(capsys.readouterr().out)
        assert report["regressions"] == []

    def test_cli_band_override(self, tmp_path, capsys):
        _write(tmp_path, [_wrap(16, value=100.0),
                          _wrap(17, value=90.0)])
        assert bench_trend.main([str(tmp_path)]) == 0
        capsys.readouterr()
        assert bench_trend.main([str(tmp_path), "--band", "0.05"]) == 1
        capsys.readouterr()
        assert bench_trend.main(
            [str(tmp_path), "--band-for",
             "gpt2_345m_tokens_per_sec_per_chip=0.05"]) == 1

    def test_render_marks_gaps(self, tmp_path):
        t = _trend(tmp_path, [_wrap(16, value=100.0), _error_wrap(17)])
        buf = io.StringIO()
        bench_trend.render(t, out=buf)
        assert "1/2 round(s)" in buf.getvalue()


class TestTelemetryReportTrendWiring:
    def test_report_trend_flag(self, tmp_path, capsys):
        """telemetry_report --trend DIR appends the cross-round trend
        table (and embeds it under --json)."""
        tel = tmp_path / "tel"
        tel.mkdir()
        (tel / "telemetry-rank0.jsonl").write_text(
            json.dumps({"kind": "summary", "counters": {},
                        "gauges": {}, "histograms": {}}) + "\n")
        bdir = tmp_path / "bench"
        bdir.mkdir()
        _write(bdir, [_wrap(16, value=100.0), _wrap(17, value=10.0)])
        rc = telemetry_report.main([str(tel), "--trend", str(bdir)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "bench trend" in out
        assert "REGRESSION" in out
        rc = telemetry_report.main([str(tel), "--json",
                                    "--trend", str(bdir)])
        report = json.loads(capsys.readouterr().out)
        assert report["trend"]["regressions"]

    def test_report_without_trend_unchanged(self, tmp_path, capsys):
        (tmp_path / "telemetry-rank0.jsonl").write_text(
            json.dumps({"kind": "summary", "counters": {},
                        "gauges": {}, "histograms": {}}) + "\n")
        assert telemetry_report.main([str(tmp_path)]) == 0
        assert "bench trend" not in capsys.readouterr().out


@pytest.mark.parametrize("bad", ["not json", '["list"]', '{"x": 1}'])
def test_unreadable_records_are_skipped(tmp_path, bad):
    (tmp_path / "BENCH_r16.json").write_text(bad)
    records = bench_trend.load_rounds([str(tmp_path)])
    assert records == []
