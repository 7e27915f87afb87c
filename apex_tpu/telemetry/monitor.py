"""Live monitoring control plane: rolling windows, alert rules,
OpenMetrics exposition.

Every other telemetry consumer in this repo is post-hoc — JSONL on
disk, read after the run. The :class:`Monitor` is the live plane: it
consumes the process registry (via the atomic
:meth:`~apex_tpu.telemetry.registry.MetricsRegistry.snapshot` and an
in-process event tap) plus, optionally, *tailed* JSONL from other
ranks/replicas, folds them into rolling time windows — counter deltas
and rates, gauge last values, histogram percentile snapshots — and
evaluates a declarative :class:`AlertRule` table against them. Rule
transitions fire structured ``alert`` events (``state="firing"`` /
``"resolved"`` with window evidence) through the same registry, so the
offline report (``tools/telemetry_report.py``) and the live plane see
one stream; the ``monitor/alerts_firing`` gauge is the one-number
summary.

Exposure is two-channel:

- :func:`render_openmetrics` — OpenMetrics/Prometheus text exposition
  of the current snapshot plus per-rule alert samples, optionally
  behind a stdlib ``http.server`` scrape endpoint
  (:meth:`Monitor.serve`, gated by ``APEX_TPU_MONITOR_PORT``). The
  renderer's output round-trips :func:`parse_openmetrics`, a strict
  conformance parser the tests run.
- ``tools/monitor_dash.py`` — terminal dashboard over a telemetry dir
  (live tail or ``--once``).

The **zero-overhead-off contract** holds end to end: a Monitor built
on a disabled registry installs no tap, starts no thread, opens no
socket, and emits nothing (``enabled`` is False and every method is a
no-op); nothing here ever touches compiled programs, so lowered HLO is
byte-identical with the monitor on or off. The ``monitor_overhead``
bench asserts the disabled leg emits zero monitor/alert events.

Window semantics (docs/observability.md#live-monitoring has the rule
table): each :meth:`Monitor.poll` appends one atomic snapshot to a
bounded history; counter rules measure the delta/rate between the
newest snapshot and the oldest one inside ``window_s``; gauge and
histogram rules read the newest snapshot (the histogram reservoir is
itself a sliding window of the last 4096 observations); sustain is
expressed in polls (``for_polls`` breached evaluations to fire,
``resolve_polls`` clean ones to resolve). The EWMA z-score rule is
event-driven: every matching ``span`` event updates an exponentially
weighted mean/variance and flags samples beyond ``threshold`` standard
deviations (after a warmup count), which the next poll reports.
"""

import collections
import fnmatch
import glob
import http.server
import json
import math
import os
import re
import threading

from apex_tpu.telemetry.attribution import PipelineAttributor
from apex_tpu.telemetry.registry import _process_index, get_registry

ENV_PORT = "APEX_TPU_MONITOR_PORT"

OPENMETRICS_CONTENT_TYPE = (
    "application/openmetrics-text; version=1.0.0; charset=utf-8")

SEVERITIES = ("info", "warn", "page")

RULE_KINDS = (
    "gauge_above",        # newest gauge value > threshold
    "gauge_below",        # newest gauge value < threshold
    "counter_increase",   # counter delta over window_s > threshold
    "counter_rate_above",  # counter delta/dt over window_s > threshold
    "hist_p99_above",     # histogram p99 (reservoir window) > threshold
    "ewma_z",             # |z| of a span duration vs EWMA baseline
    "replica_health",     # any fleet replica quarantined/respawning
    "recovery",           # supervisor failure -> recovery escalation
)

#: replica states the replica_health rule counts as down
BAD_REPLICA_STATES = ("quarantined", "respawning")


class AlertRule:
    """One declarative alert: a named condition over the rolling
    windows, with sustain/resolve hysteresis and a severity.

    ``metric`` is an ``fnmatch`` pattern for the metric-backed kinds
    (so ``fleet/ttft_*`` covers every tier) and a span name for
    ``ewma_z``; the ``replica_health`` / ``recovery`` kinds are
    event-driven and take no metric."""

    __slots__ = ("name", "kind", "metric", "threshold", "window_s",
                 "for_polls", "resolve_polls", "severity", "description")

    def __init__(self, name, kind, *, metric=None, threshold=None,
                 window_s=60.0, for_polls=1, resolve_polls=1,
                 severity="warn", description=""):
        if kind not in RULE_KINDS:
            raise ValueError(f"unknown rule kind {kind!r} "
                             f"(one of {RULE_KINDS})")
        if severity not in SEVERITIES:
            raise ValueError(f"unknown severity {severity!r} "
                             f"(one of {SEVERITIES})")
        if kind not in ("replica_health", "recovery"):
            if metric is None:
                raise ValueError(f"rule {name!r} ({kind}) needs a metric")
            if threshold is None:
                raise ValueError(
                    f"rule {name!r} ({kind}) needs a threshold")
        self.name = name
        self.kind = kind
        self.metric = metric
        self.threshold = threshold
        self.window_s = float(window_s)
        self.for_polls = max(1, int(for_polls))
        self.resolve_polls = max(1, int(resolve_polls))
        self.severity = severity
        self.description = description

    def describe(self):
        return {"name": self.name, "kind": self.kind,
                "metric": self.metric, "threshold": self.threshold,
                "window_s": self.window_s, "for_polls": self.for_polls,
                "resolve_polls": self.resolve_polls,
                "severity": self.severity,
                "description": self.description}


def default_rules(*, ttft_slo_ms=None, pending_depth=64,
                  hbm_headroom_floor=0.05, goodput_floor=0.9,
                  step_time_z=4.0):
    """The stock rule table (docs/observability.md#live-monitoring).
    ``ttft_slo_ms`` maps tier name -> p99 budget in ms (default:
    interactive at 1000 ms); the other knobs parameterize one rule
    each."""
    if ttft_slo_ms is None:
        ttft_slo_ms = {"interactive": 1000.0}
    rules = [
        AlertRule(
            f"ttft_slo_{tier}", "hist_p99_above",
            metric=f"fleet/ttft_{tier}", threshold=float(ms),
            severity="page",
            description=f"{tier} TTFT p99 over its {ms:g} ms SLO")
        for tier, ms in sorted(ttft_slo_ms.items())
    ]
    rules += [
        AlertRule(
            "guard_skips", "gauge_above",
            metric="guard/consecutive_skips", threshold=0.0,
            severity="page",
            description="non-finite step guard is skipping steps"),
        AlertRule(
            "pending_depth", "gauge_above", metric="*/pending_depth",
            threshold=float(pending_depth), for_polls=3,
            description="admission backlog sustained over threshold"),
        AlertRule(
            "recompiles", "counter_increase", metric="compile/count",
            threshold=0.0, window_s=60.0,
            description="steady-state recompilation (shape-unstable "
                        "input leaking into a traced signature)"),
        AlertRule(
            "hbm_headroom", "gauge_below",
            metric="memory/hbm_headroom",
            threshold=float(hbm_headroom_floor), severity="page",
            description="HBM headroom under floor — next allocation "
                        "may RESOURCE_EXHAUSTED"),
        AlertRule(
            "goodput_ratio", "gauge_below",
            metric="recovery/goodput_step_ratio",
            threshold=float(goodput_floor),
            description="committed/dispatched step ratio dropped — "
                        "recovery replays are eating throughput"),
        AlertRule(
            "step_time_anomaly", "ewma_z", metric="train/step",
            threshold=float(step_time_z),
            description="step time beyond z EWMA standard deviations"),
        AlertRule(
            "replica_health", "replica_health", severity="page",
            description="a fleet replica is quarantined or awaiting "
                        "respawn"),
        AlertRule(
            "recovery_escalation", "recovery",
            description="training supervisor is mid-recovery"),
    ]
    return rules


class _RuleState:
    __slots__ = ("firing", "breach_streak", "ok_streak", "since_ts",
                 "fired_count", "value", "evidence")

    def __init__(self):
        self.firing = False
        self.breach_streak = 0
        self.ok_streak = 0
        self.since_ts = None
        self.fired_count = 0
        self.value = None
        self.evidence = None


class JsonlTailer:
    """Incremental reader of ``telemetry-rank*.jsonl`` files: remembers
    a byte offset per file, returns only complete new lines, parsed.
    ``skip_files`` (basenames) excludes e.g. this process's own sink —
    the Monitor already hears itself through the in-process tap."""

    PATTERN = "telemetry-rank*.jsonl"

    def __init__(self, dirpath, *, skip_files=()):
        self.dirpath = dirpath
        self._skip = frozenset(skip_files)
        self._offsets = {}

    def poll(self):
        records = []
        paths = sorted(glob.glob(os.path.join(self.dirpath,
                                              self.PATTERN)))
        for path in paths:
            if os.path.basename(path) in self._skip:
                continue
            try:
                size = os.path.getsize(path)
            except OSError:
                continue
            offset = self._offsets.get(path, 0)
            if size <= offset:
                continue
            try:
                with open(path, "r", errors="replace") as fh:
                    fh.seek(offset)
                    chunk = fh.read()
            except OSError:
                continue
            end = chunk.rfind("\n")
            if end < 0:
                continue  # no complete line yet
            self._offsets[path] = offset + len(
                chunk[:end + 1].encode("utf-8", "replace"))
            for line in chunk[:end].splitlines():
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    records.append(rec)
        return records


class Monitor:
    """The live evaluation loop. See the module docstring for the
    architecture; the short form::

        mon = Monitor(registry, rules=default_rules())
        ...
        mon.poll()            # evaluate once (tests drive this)
        mon.start(interval_s=1.0)   # or: background thread + scrape
        ...
        mon.close()

    Disabled registry => ``enabled`` is False and every method above is
    an inert no-op (no tap, no thread, no socket, no events).
    """

    def __init__(self, registry=None, *, rules=None, tail_dir=None,
                 ewma_alpha=0.25, ewma_warmup=8, history=128):
        self.registry = reg = registry or get_registry()
        self.enabled = bool(reg.enabled)
        self.rules = list(default_rules() if rules is None else rules)
        names = [r.name for r in self.rules]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate rule names in {names}")
        self.attribution = PipelineAttributor()
        self._lock = threading.RLock()
        self._states = {r.name: _RuleState() for r in self.rules}
        self._history = collections.deque(maxlen=max(2, int(history)))
        self._replicas = {}
        self._recovery = {"down": False, "cls": None, "step": None}
        self._ewma_alpha = float(ewma_alpha)
        self._ewma_warmup = int(ewma_warmup)
        self._ewma = {r.name: {"mean": None, "var": 0.0, "n": 0,
                               "anomaly": None}
                      for r in self.rules if r.kind == "ewma_z"}
        self._polls = 0
        self._thread = None
        self._stop = threading.Event()
        self._server = None
        self._server_thread = None
        self._tailer = None
        self._closed = False
        if not self.enabled:
            return
        if tail_dir:
            skip = ()
            if tail_dir == reg.jsonl_dir:
                skip = (f"telemetry-rank{_process_index()}.jsonl",)
            self._tailer = JsonlTailer(tail_dir, skip_files=skip)
        reg.add_event_tap(self._ingest)
        reg.event("monitor", "start", rules=names,
                  tail_dir=tail_dir or None)

    # -- intake -------------------------------------------------------------

    def _ingest(self, rec):
        """Event intake — called synchronously from the registry tap
        and for every tailed cross-rank record. Must stay cheap and
        must never raise into the emitter."""
        kind = rec.get("kind")
        if kind in ("alert", "monitor"):
            return  # our own output; never feed back
        if kind == "span":
            self.attribution.add_span(rec)
            name = rec.get("name")
            dur = rec.get("duration_s")
            if dur is None:
                return
            for rule in self.rules:
                if rule.kind == "ewma_z" and rule.metric == name:
                    self._ewma_update(rule, float(dur), rec)
        elif kind == "fleet" and rec.get("name") == "replica_state":
            with self._lock:
                self._replicas[rec.get("replica")] = rec.get("new")
        elif kind == "recovery":
            name = rec.get("name")
            if name == "failure":
                with self._lock:
                    self._recovery = {"down": True,
                                      "cls": rec.get("cls"),
                                      "step": rec.get("step")}
            elif name in ("recovered", "run_done"):
                with self._lock:
                    self._recovery = dict(self._recovery, down=False)

    def _ewma_update(self, rule, x, rec):
        with self._lock:
            st = self._ewma[rule.name]
            mean, var, n = st["mean"], st["var"], st["n"]
            if mean is not None and n >= self._ewma_warmup:
                std = math.sqrt(var) if var > 0 else 0.0
                if std > 0:
                    z = (x - mean) / std
                    if abs(z) > float(rule.threshold):
                        st["anomaly"] = {
                            "value_s": x, "z": round(z, 3),
                            "ewma_mean_s": mean,
                            "ewma_std_s": std,
                            "step": rec.get("step")}
            if mean is None:
                st["mean"], st["var"] = x, 0.0
            else:
                a = self._ewma_alpha
                d = x - mean
                st["mean"] = mean + a * d
                st["var"] = (1.0 - a) * (var + a * d * d)
            st["n"] = n + 1

    # -- evaluation ---------------------------------------------------------

    def _window_base(self, window_s, now_ts):
        """Oldest snapshot still inside the window (the counter rules'
        rate base); None before the second poll."""
        base = None
        for snap in self._history:
            if now_ts - snap["ts"] <= window_s:
                if base is None or snap["ts"] < base["ts"]:
                    base = snap
        return base

    def _check(self, rule, snap):
        """-> (breached, value, evidence dict)."""
        kind = rule.kind
        if kind in ("gauge_above", "gauge_below"):
            hits = {}
            worst = None
            for name, value in snap["gauges"].items():
                if value is None or not fnmatch.fnmatch(name,
                                                        rule.metric):
                    continue
                breach = (value > rule.threshold
                          if kind == "gauge_above"
                          else value < rule.threshold)
                if breach:
                    hits[name] = value
                    worst = (value if worst is None
                             else (max, min)[kind == "gauge_below"](
                                 worst, value))
            return bool(hits), worst, hits or None
        if kind == "hist_p99_above":
            hits = {}
            worst = None
            for name, summ in snap["histograms"].items():
                if not fnmatch.fnmatch(name, rule.metric):
                    continue
                p99 = summ.get("p99")
                if p99 is not None and p99 > rule.threshold:
                    hits[name] = {"p99": p99, "count": summ["count"]}
                    worst = p99 if worst is None else max(worst, p99)
            return bool(hits), worst, hits or None
        if kind in ("counter_increase", "counter_rate_above"):
            base = self._window_base(rule.window_s, snap["ts"])
            if base is None or base is snap:
                return False, None, None
            hits = {}
            worst = None
            dt = snap["ts"] - base["ts"]
            for name, value in snap["counters"].items():
                if not fnmatch.fnmatch(name, rule.metric):
                    continue
                delta = value - base["counters"].get(name, 0.0)
                measure = (delta if kind == "counter_increase"
                           else (delta / dt if dt > 0 else 0.0))
                if measure > rule.threshold:
                    hits[name] = {"delta": delta,
                                  "window_s": round(dt, 3)}
                    worst = (measure if worst is None
                             else max(worst, measure))
            return bool(hits), worst, hits or None
        if kind == "ewma_z":
            st = self._ewma[rule.name]
            anomaly, st["anomaly"] = st["anomaly"], None
            if anomaly is None:
                return False, None, None
            return True, anomaly["z"], anomaly
        if kind == "replica_health":
            bad = {str(idx): state
                   for idx, state in self._replicas.items()
                   if state in BAD_REPLICA_STATES}
            serving = snap["gauges"].get("fleet/replicas_serving")
            expected = snap["gauges"].get("fleet/replicas_expected")
            short = (serving is not None and expected is not None
                     and serving < expected)
            if not bad and not short:
                return False, None, None
            return True, float(len(bad)), {
                "replicas": bad or None, "serving": serving,
                "expected": expected}
        if kind == "recovery":
            rec = dict(self._recovery)
            gauge = snap["gauges"].get("recovery/in_recovery")
            down = rec.pop("down") or gauge == 1
            if not down:
                return False, None, None
            return True, 1.0, {k: v for k, v in rec.items()
                               if v is not None} or None
        raise AssertionError(f"unreachable rule kind {kind}")

    def poll(self):
        """One evaluation pass: tail cross-rank JSONL, take an atomic
        snapshot, evaluate every rule, emit firing/resolved ``alert``
        events, refresh ``monitor/alerts_firing``. Returns the
        evaluation dict (None when disabled)."""
        if not self.enabled:
            return None
        if self._tailer is not None:
            for rec in self._tailer.poll():
                self._ingest(rec)
        snap = self.registry.snapshot()
        transitions = []
        with self._lock:
            firing = 0
            results = []
            for rule in self.rules:
                breached, value, evidence = self._check(rule, snap)
                st = self._states[rule.name]
                st.value = value
                if breached:
                    st.evidence = evidence
                    st.breach_streak += 1
                    st.ok_streak = 0
                    if (not st.firing
                            and st.breach_streak >= rule.for_polls):
                        st.firing = True
                        st.since_ts = snap["ts"]
                        st.fired_count += 1
                        transitions.append(("firing", rule, st, None))
                else:
                    st.ok_streak += 1
                    st.breach_streak = 0
                    if st.firing and st.ok_streak >= rule.resolve_polls:
                        st.firing = False
                        dur = (snap["ts"] - st.since_ts
                               if st.since_ts is not None else None)
                        transitions.append(("resolved", rule, st, dur))
                if st.firing:
                    firing += 1
                results.append(self._row(rule, st))
            self._history.append(snap)
            self._polls += 1
        reg = self.registry
        for state, rule, st, dur in transitions:
            fields = {"state": state, "severity": rule.severity,
                      "rule_kind": rule.kind, "metric": rule.metric,
                      "threshold": rule.threshold,
                      "window_s": rule.window_s}
            if state == "firing":
                fields.update(value=st.value, evidence=st.evidence)
            else:
                fields.update(duration_s=(round(dur, 6)
                                          if dur is not None else None))
            reg.event("alert", rule.name, **fields)
            if state == "firing":
                reg.counter("monitor/alerts_fired").inc()
        reg.gauge("monitor/alerts_firing").set(float(firing))
        return {"ts": snap["ts"], "firing": firing, "alerts": results}

    @staticmethod
    def _row(rule, st):
        return {"rule": rule.name, "kind": rule.kind,
                "severity": rule.severity, "firing": st.firing,
                "value": st.value, "evidence": st.evidence,
                "since_ts": st.since_ts if st.firing else None,
                "fired_count": st.fired_count}

    def alerts(self):
        """Current per-rule state rows (the dashboard/exposition
        view)."""
        with self._lock:
            return [self._row(rule, self._states[rule.name])
                    for rule in self.rules]

    def alerts_firing(self):
        with self._lock:
            return sum(1 for st in self._states.values() if st.firing)

    def straggler_report(self, **kw):
        """Online pipeline attribution from the spans ingested so far
        (:meth:`PipelineAttributor.report`)."""
        return self.attribution.report(**kw)

    # -- exposition ---------------------------------------------------------

    def render_openmetrics(self):
        """OpenMetrics text of a fresh snapshot + current alerts."""
        if not self.enabled:
            return "# EOF\n"
        return render_openmetrics(self.registry.snapshot(),
                                  alerts=self.alerts())

    def serve(self, port=None):
        """Start the scrape endpoint on 127.0.0.1:``port`` (default:
        ``$APEX_TPU_MONITOR_PORT``; port 0 binds an ephemeral port —
        read it back from ``bound_port``). No-op returning None when
        disabled or no port is configured."""
        if not self.enabled or self._server is not None:
            return self._server
        if port is None:
            raw = os.environ.get(ENV_PORT)
            if not raw:
                return None
            port = int(raw)
        monitor = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 — http.server API
                if self.path.split("?", 1)[0] not in ("/", "/metrics"):
                    self.send_error(404)
                    return
                try:
                    body = monitor.render_openmetrics().encode("utf-8")
                except Exception as exc:  # pragma: no cover
                    self.send_error(500, str(exc)[:100])
                    return
                self.send_response(200)
                self.send_header("Content-Type",
                                 OPENMETRICS_CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, fmt, *args):
                pass  # scrape noise must not hit stderr

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", int(port)),
                                              Handler)
        srv.daemon_threads = True
        self._server = srv
        self._server_thread = threading.Thread(
            target=srv.serve_forever, name="apex-tpu-monitor-scrape",
            daemon=True)
        self._server_thread.start()
        self.registry.event("monitor", "scrape_endpoint",
                            port=self.bound_port)
        return srv

    @property
    def bound_port(self):
        return (self._server.server_address[1]
                if self._server is not None else None)

    # -- lifecycle ----------------------------------------------------------

    def start(self, interval_s=1.0):
        """Background evaluation: a daemon thread polling every
        ``interval_s`` seconds, plus the scrape endpoint when
        ``$APEX_TPU_MONITOR_PORT`` is set. No-op when disabled.
        Returns self."""
        if not self.enabled or self._thread is not None:
            return self
        self.serve()
        self._stop.clear()

        def loop():
            while not self._stop.wait(interval_s):
                try:
                    self.poll()
                except Exception:  # pragma: no cover
                    pass  # a broken rule must never kill the loop

        self._thread = threading.Thread(
            target=loop, name="apex-tpu-monitor", daemon=True)
        self._thread.start()
        return self

    def close(self):
        """Stop the loop and the scrape endpoint, detach from the
        registry, emit the ``monitor``/``stop`` event. Idempotent."""
        if not self.enabled or self._closed:
            return
        self._closed = True
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
            if self._server_thread is not None:
                self._server_thread.join(timeout=5.0)
                self._server_thread = None
        self.registry.remove_event_tap(self._ingest)
        self.registry.event("monitor", "stop", polls=self._polls,
                            alerts_total=sum(
                                st.fired_count
                                for st in self._states.values()))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# -- OpenMetrics exposition -------------------------------------------------

_NAME_OK = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SANITIZE = re.compile(r"[^a-zA-Z0-9_:]")


def _metric_name(name, prefix="apex_tpu_"):
    out = prefix + _SANITIZE.sub("_", str(name))
    if not _NAME_OK.match(out):
        out = prefix + "invalid"
    return out


def _fmt(value):
    value = float(value)
    if math.isnan(value):
        return "NaN"
    if math.isinf(value):
        return "+Inf" if value > 0 else "-Inf"
    return repr(value)


def _label_escape(value):
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def render_openmetrics(snapshot, alerts=(), *, prefix="apex_tpu_"):
    """Render a registry snapshot (plus optional alert rows from
    :meth:`Monitor.alerts`) as OpenMetrics text exposition.

    Naming: ``/``-separated metric names sanitize to ``_`` under the
    ``apex_tpu_`` prefix (``fleet/ttft_interactive`` ->
    ``apex_tpu_fleet_ttft_interactive``). Counters expose a single
    ``_total`` sample; gauges their last value (unset gauges are
    omitted); histograms map to ``summary`` families — ``{quantile=
    "0.5"|"0.99"}`` over the reservoir window plus exact ``_count`` /
    ``_sum``. Firing alerts are ``apex_tpu_monitor_alert{rule=...,
    severity=...} 1`` samples. Output terminates with ``# EOF`` and
    round-trips :func:`parse_openmetrics`."""
    lines = []
    seen = set()

    def family(name, mtype):
        if name in seen:
            return False
        seen.add(name)
        lines.append(f"# TYPE {name} {mtype}")
        return True

    for raw in sorted(snapshot.get("counters", {})):
        name = _metric_name(raw, prefix)
        if family(name, "counter"):
            lines.append(
                f"{name}_total "
                f"{_fmt(snapshot['counters'][raw])}")
    for raw in sorted(snapshot.get("gauges", {})):
        value = snapshot["gauges"][raw]
        if value is None:
            continue
        name = _metric_name(raw, prefix)
        if family(name, "gauge"):
            lines.append(f"{name} {_fmt(value)}")
    for raw in sorted(snapshot.get("histograms", {})):
        summ = snapshot["histograms"][raw]
        name = _metric_name(raw, prefix)
        if not family(name, "summary"):
            continue
        for q, key in (("0.5", "p50"), ("0.99", "p99")):
            if summ.get(key) is not None:
                lines.append(
                    f'{name}{{quantile="{q}"}} {_fmt(summ[key])}')
        lines.append(f"{name}_count {int(summ.get('count') or 0)}")
        lines.append(f"{name}_sum {_fmt(summ.get('total') or 0.0)}")
    firing = [row for row in alerts if row.get("firing")]
    if firing:
        name = prefix + "monitor_alert"
        if family(name, "gauge"):
            for row in firing:
                lines.append(
                    f'{name}{{rule="{_label_escape(row["rule"])}",'
                    f'severity="{_label_escape(row["severity"])}"}} 1')
    lines.append("# EOF")
    return "\n".join(lines) + "\n"


_TYPE_LINE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
    r"(counter|gauge|summary|histogram|info|stateset|unknown)$")
_SAMPLE_LINE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? (\S+)$")
_LABEL_BODY = re.compile(
    r'^([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\["\\n])*)"$')
_VALUE_OK = re.compile(r"^(NaN|[+-]Inf|[+-]?[0-9]*\.?[0-9]+"
                       r"([eE][+-]?[0-9]+)?)$")

_SUFFIXES = ("_total", "_count", "_sum", "_bucket", "_created")


def _family_of(sample_name, declared):
    if sample_name in declared:
        return sample_name, ""
    for suffix in _SUFFIXES:
        if sample_name.endswith(suffix):
            base = sample_name[:-len(suffix)]
            if base in declared:
                return base, suffix
    return None, None


def parse_openmetrics(text):
    """Strict conformance parser for the renderer's output: validates
    metric-name / label / value syntax, TYPE-before-sample ordering,
    one TYPE per family, counter ``_total`` naming, summary suffix
    discipline, and the terminal ``# EOF``. Raises ``ValueError`` with
    the offending line on any violation; returns ``{family: {"type":
    ..., "samples": [(name, labels, value), ...]}}``."""
    if not text.endswith("\n"):
        raise ValueError("exposition must end with a newline")
    lines = text.splitlines()
    if not lines or lines[-1] != "# EOF":
        raise ValueError("exposition must terminate with '# EOF'")
    families = {}
    for i, line in enumerate(lines[:-1], 1):
        if line == "# EOF":
            raise ValueError(f"line {i}: '# EOF' before end of input")
        if line.startswith("#"):
            if line.startswith("# HELP "):
                continue
            m = _TYPE_LINE.match(line)
            if m is None:
                raise ValueError(f"line {i}: malformed comment/TYPE "
                                 f"line: {line!r}")
            name, mtype = m.group(1), m.group(2)
            if name in families:
                raise ValueError(
                    f"line {i}: duplicate TYPE for {name!r}")
            families[name] = {"type": mtype, "samples": []}
            continue
        m = _SAMPLE_LINE.match(line)
        if m is None:
            raise ValueError(f"line {i}: malformed sample: {line!r}")
        sample_name, label_blob, value = m.groups()
        family, suffix = _family_of(sample_name, families)
        if family is None:
            raise ValueError(
                f"line {i}: sample {sample_name!r} has no preceding "
                f"TYPE line")
        mtype = families[family]["type"]
        if mtype == "counter" and suffix not in ("_total", "_created"):
            raise ValueError(
                f"line {i}: counter sample must use the _total "
                f"suffix: {sample_name!r}")
        if mtype == "gauge" and suffix:
            raise ValueError(
                f"line {i}: gauge sample must not carry suffix "
                f"{suffix!r}")
        if mtype == "summary" and suffix not in ("", "_count", "_sum",
                                                 "_created"):
            raise ValueError(
                f"line {i}: invalid summary suffix {suffix!r}")
        labels = {}
        if label_blob:
            body = label_blob[1:-1]
            if body:
                for part in body.split(","):
                    lm = _LABEL_BODY.match(part)
                    if lm is None:
                        raise ValueError(
                            f"line {i}: malformed label {part!r}")
                    labels[lm.group(1)] = lm.group(2)
        if mtype == "summary" and suffix == "" and \
                "quantile" not in labels:
            raise ValueError(
                f"line {i}: bare summary sample needs a quantile "
                f"label: {line!r}")
        if not _VALUE_OK.match(value):
            raise ValueError(f"line {i}: malformed value {value!r}")
        families[family]["samples"].append(
            (sample_name, labels, value))
    return families
