"""Compile watch: make silent recompilation a first-class, observable
event.

The failure mode: XLA recompiles whenever a jitted function sees a new
abstract signature — a shape-unstable input pipeline, a Python scalar
whose type drifts, a sharding that flips between calls — and on TPU a
large-model compile costs minutes. A per-step retrace therefore turns a
"fast" run into one that spends 99% of wall-clock in the compiler while
the step-time telemetry (PR 2) sees only mysteriously slow steps: the
compile itself was invisible. This module is the missing signal:

- the record of compile phases — always on, bounded, in memory: one
  ``jax.monitoring`` listener (jax 0.9: the
  ``/jax/core/compile/{jaxpr_trace,jaxpr_to_mlir_module,backend_compile}_duration``
  time spans, which carry ``fun_name``, and the
  ``/jax/compilation_cache/*`` events) keeps for every trace, lowering
  and backend compile (or persistent-cache load) of the process a
  :class:`PhaseRecord` ``(phase, fun_name, start, end, thread,
  cache_hit)`` on the ``perf_counter`` clock, and the package's own
  import beside them (:func:`record_import`). :func:`phase_records`
  hands them out, :func:`phase_table` folds them into self seconds by
  function and phase, :func:`process_start_perf` puts the process's
  start on the same clock, and :func:`backend_compiles` /
  :func:`cache_totals` (``_compile_cache.cache_stats()``) are views of
  the same record. ``_compile_cache.enable_compile_cache()`` installs
  the listener, so an entry point records from before its first jit.
  No option and no environment variable: nothing fires the listener in
  steady state, because a compiled function called again compiles
  nothing. With the registry enabled each record is also a span
  (``compile/trace``, ``compile/lower``, ``compile/backend``,
  ``import``; attributes ``fun_name``, ``cache_hit``) and bumps
  ``compile/traces`` / ``compile/lowerings`` / ``compile/count`` /
  ``compile/seconds`` and ``compile_cache/{hits,misses,
  retrieval_seconds,saved_seconds}``. A phase of under a millisecond
  inside another of its kind (the jnp helpers inside a step's trace, by
  the thousand) is counted and folded into the one around it; past
  ``MAX_RECORDS`` only the totals grow and ``compile/records_dropped``
  counts (:func:`record_stats`).
- :class:`CompileWatcher` — wrap a jitted callable with
  :meth:`~CompileWatcher.watch`; every call snapshots the pjit cache
  size (``fn._cache_size()``), so a cache-size increase IS a
  trace+compile, attributed to exactly that call. On a *re*compile the
  watcher diffs the new abstract signature (per-argument shapes /
  dtypes / weak-types / named shardings / Python-scalar values) against
  the cached one and emits a ``compile`` JSONL event naming exactly
  which argument changed (path, old -> new). Per-function metrics land
  in the registry as ``compile/count/<name>``.
- :func:`assert_no_recompiles` — the test/CI primitive: a context
  manager that counts backend compiles across the block (the record's
  total) and raises :class:`RecompileError` when any happened, naming
  the changed argument when a watched function saw it. Wrap N
  steady-state steps after warmup and any future per-step retrace fails
  tier-1 loudly.

Everything is host-side: watching never touches the traced program, so
the lowered HLO of a watched step is byte-identical to the unwatched
one (asserted in tests/L0/test_compile_watch.py — the same contract the
numerics layer keeps).

The watcher is opt-in: ``APEX_TPU_COMPILE_WATCH=1`` enables the
process-global one returned by :func:`get_watcher` (``bench.py
ddp_memwatch`` enables it programmatically); a disabled watcher's
``watch`` returns the function unchanged — zero overhead off.
:func:`assert_no_recompiles` works regardless of the opt-in (tests
should not depend on env state).
"""

import collections
import contextlib
import os
import threading
import time

from apex_tpu.telemetry.registry import get_registry
from apex_tpu.telemetry.trace import emit_span

ENV_WATCH = "APEX_TPU_COMPILE_WATCH"
# opt-in for the static HLO lint pass (apex_tpu.analysis,
# docs/analysis.md): an enabled watcher lints every newly compiled
# executable it sees and emits `lint` JSONL events per finding
ENV_LINT = "APEX_TPU_HLO_LINT"


class RecompileError(RuntimeError):
    """Raised by :func:`assert_no_recompiles` when a compile happened
    inside the guarded block."""


# -- the process-wide record of compile phases ------------------------------

# jax.monitoring's names in jax 0.9 (probed in tests). Each of the three
# phases fires once as a duration and once as a time span that carries
# ``fun_name``; the record is fed by the spans. ``backend_compile`` brackets
# ``compile_or_get_cached``, so a load from the persistent cache is inside
# it, and so are the cache's own events below.
_PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_KEY_OF_EVENT = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_seconds",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_seconds",
}
# span name and registry counter of each phase
_SPAN_OF_PHASE = {"import": "import", "trace": "compile/trace",
                  "lower": "compile/lower", "compile": "compile/backend"}
_COUNTER_OF_PHASE = {"trace": "compile/traces", "lower": "compile/lowerings",
                     "compile": "compile/count"}

# A whole train step's trace is thousands of jnp helpers' traces of a few
# microseconds each, inside the step's own (GPT-2 345M: 11,915 traces, of
# which all but some hundreds are such). A phase that ran inside another
# of its own kind and took less than this is counted in the totals and
# folded into the one around it, not kept: the union of the kept records
# is the same, and a function's self seconds then hold its helpers'.
FOLD_BELOW = 1e-3
# the record's bound: past it only the totals grow (and ``dropped``)
MAX_RECORDS = 16384

PhaseRecord = collections.namedtuple(
    "PhaseRecord", "phase fun_name start end thread cache_hit")
PhaseRecord.__doc__ = """One phase of one compile. ``phase`` is ``trace``
(Python to a jaxpr), ``lower`` (jaxpr to MLIR, Mosaic's lowering of the
Pallas kernels inside it), ``compile`` (XLA's compile, or the persistent
cache's load) or ``import`` (:func:`record_import`); ``start`` and ``end``
are raw ``time.perf_counter()`` readings; ``cache_hit`` is ``True`` /
``False`` for a ``compile`` the persistent cache served / had to store,
``None`` where the cache took no part."""


class _PhaseLog:
    """The bounded list of :class:`PhaseRecord` with its running totals:
    one per process, as ``jax.monitoring``'s listeners are."""

    def __init__(self):
        self.lock = threading.Lock()
        self.installed = False
        self.records = []
        self.folded = 0
        self.dropped = 0
        # phase -> [count, seconds]: exact, folded and dropped included
        self.totals = {phase: [0, 0.0] for phase in _SPAN_OF_PHASE}
        self.cache = {"hits": 0, "misses": 0, "retrieval_seconds": 0.0,
                      "saved_seconds": 0.0}
        # per thread: how many phases of each kind are open (``depth``),
        # and what the cache said of the compile that is open (``hit``:
        # its events come before the end of the compile they are inside)
        self.local = threading.local()
        self.wall_offset = 0.0
        self.listener_seconds = 0.0
        self.jax_preloaded = None

    def depth(self):
        try:
            return self.local.depth
        except AttributeError:
            self.local.depth = dict.fromkeys(_SPAN_OF_PHASE, 0)
            return self.local.depth

    def add(self, phase, fun_name, start, end, cache_hit=None, fold=False):
        with self.lock:
            total = self.totals[phase]
            total[0] += 1
            total[1] += end - start
            kept = not fold and len(self.records) < MAX_RECORDS
            if kept:
                self.records.append(PhaseRecord(
                    phase, fun_name, start, end, threading.get_ident(),
                    cache_hit))
            elif fold:
                self.folded += 1
            else:
                self.dropped += 1
        reg = get_registry()
        if reg.enabled:
            if phase in _COUNTER_OF_PHASE:
                reg.counter(_COUNTER_OF_PHASE[phase]).inc()
            if phase == "compile":
                reg.counter("compile/seconds").inc(end - start)
            if kept:
                emit_span(_SPAN_OF_PHASE[phase], start, end, registry=reg,
                          fun_name=fun_name, cache_hit=cache_hit)
            elif not fold:
                reg.counter("compile/records_dropped").inc()


_LOG = _PhaseLog()


def _on_phase_begin(event, value, **meta):
    """jax announces a phase's start as a scalar (its ``time.time()``)
    under the phase's own name: count it open on this thread, so that its
    end knows whether it ran inside another of its kind."""
    phase = _PHASE_OF_EVENT.get(event)
    if phase is not None:
        _LOG.depth()[phase] += 1


def _on_jax_event(event, *values, **meta):
    """The end of a phase and the cache's events, registered on three of
    ``jax.monitoring``'s lists: a time span comes with two values (start
    and end on ``time.time()``), a duration with one, a plain event with
    none."""
    t0 = time.perf_counter()
    if len(values) == 2:
        phase = _PHASE_OF_EVENT.get(event)
        if phase is None:
            return
        depth = _LOG.depth()
        # 0 where the listener was installed inside the phase
        depth[phase] = max(depth[phase] - 1, 0)
        hit = None
        if phase == "compile":
            hit = getattr(_LOG.local, "hit", None)
            _LOG.local.hit = None
        start, end = values
        _LOG.add(phase, str(meta.get("fun_name", "")),
                 start + _LOG.wall_offset, end + _LOG.wall_offset, hit,
                 fold=depth[phase] > 0 and end - start < FOLD_BELOW)
    else:
        key = _CACHE_KEY_OF_EVENT.get(event)
        if key is None:
            return
        amount = float(values[0]) if values else 1
        if not values:
            _LOG.local.hit = key == "hits"
        with _LOG.lock:
            _LOG.cache[key] += amount
        reg = get_registry()
        if reg.enabled:
            reg.counter(f"compile_cache/{key}").inc(amount)
    with _LOG.lock:
        _LOG.listener_seconds += time.perf_counter() - t0


def install_monitoring():
    """Register the (one, idempotent) ``jax.monitoring`` listener that
    feeds the record of compile phases: :func:`phase_records`,
    :func:`phase_table`, :func:`backend_compiles`, :func:`cache_totals`
    and the ``compile/*`` / ``compile_cache/*`` registry counters.
    ``_compile_cache.enable_compile_cache()`` calls it, so every entry
    point records from before its first jit. jax offers no per-listener
    removal, so this registers exactly once per process and the listener
    stays: nothing fires it in steady state, because a compiled function
    called again traces, lowers and compiles nothing."""
    with _LOG.lock:
        if _LOG.installed:
            return
        _LOG.installed = True
        # jax stamps its spans with time.time(); one offset, sampled
        # back-to-back as MetricsRegistry samples its epoch, puts them on
        # perf_counter, the clock emit_span and the registry use
        _LOG.wall_offset = time.perf_counter() - time.time()
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_on_phase_begin)
    jax.monitoring.register_event_time_span_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
    jax.monitoring.register_event_listener(_on_jax_event)


def record_import(name, start, end, jax_preloaded):
    """A package's own import as a record (``phase`` ``import``), from
    the two ``perf_counter`` readings ``apex_tpu/__init__.py`` takes at
    its top and bottom. ``jax_preloaded``: whether ``jax`` was imported
    before it; where not, jax's own import is inside the interval."""
    _LOG.jax_preloaded = bool(jax_preloaded)
    _LOG.add("import", name, start, end)


def phase_records(until=None):
    """The records kept so far, in the order their phases ended (an
    inner jit's before its caller's); with ``until`` (a ``perf_counter``
    reading) those that had ended by then."""
    with _LOG.lock:
        records = list(_LOG.records)
    if until is None:
        return records
    return [r for r in records if r.end <= until]


def record_stats():
    """``{"kept", "folded", "dropped", "listener_seconds"}``: records in
    the list; phases counted in the totals and folded into the one around
    them (``FOLD_BELOW``); phases that ended after the list had reached
    ``MAX_RECORDS``, counted and not kept; and what the listener itself
    has taken so far (self-timed)."""
    with _LOG.lock:
        return {"kept": len(_LOG.records), "folded": _LOG.folded,
                "dropped": _LOG.dropped,
                "listener_seconds": _LOG.listener_seconds}


def jax_preloaded():
    """Whether ``jax`` was imported before ``apex_tpu`` (``None`` before
    the package's import has ended)."""
    return _LOG.jax_preloaded


def backend_compiles():
    """``(count, total_seconds)`` of XLA backend compiles observed since
    :func:`install_monitoring` ran (process-wide, watched or not; a load
    from the persistent cache counts as one)."""
    with _LOG.lock:
        return tuple(_LOG.totals["compile"])


def cache_totals():
    """``{"hits", "misses", "retrieval_seconds", "saved_seconds"}`` of
    the persistent compilation cache since :func:`install_monitoring`
    ran; ``_compile_cache.cache_stats()`` is its first two."""
    with _LOG.lock:
        return dict(_LOG.cache)


def process_start_perf():
    """The ``time.perf_counter()`` value at which this process started,
    so that "seconds since start" and a span share a clock. On Linux from
    ``/proc/self/stat``'s start time (in clock ticks since boot) against
    ``CLOCK_BOOTTIME``; ``None`` elsewhere."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the parenthesised command name; starttime
            # is the 22nd of the line, the 20th after it
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, AttributeError, ValueError, IndexError):
        return None
    return time.perf_counter() - age


def _self_seconds(records):
    """``{record index: seconds}``: a record's duration less what the
    records inside it cover. The parent of a record is the innermost
    record of the same thread that contains it; phases of one thread
    nest or follow one another, so a parent's children do not overlap."""
    own = {}
    by_thread = collections.defaultdict(list)
    for i, r in enumerate(records):
        by_thread[r.thread].append(i)
        own[i] = r.end - r.start
    for indices in by_thread.values():
        indices.sort(key=lambda i: (records[i].start, -records[i].end))
        open_ = []
        for i in indices:
            r = records[i]
            while open_ and records[open_[-1]].end <= r.start:
                open_.pop()
            if open_:
                parent = records[open_[-1]]
                own[open_[-1]] -= min(r.end, parent.end) - r.start
            open_.append(i)
    return own


def phase_table(until=None):
    """Where the compile path's time went, by function and phase: rows
    ``{"fun_name", "phase", "calls", "self_s", "total_s", "cache_hits",
    "cache_misses"}``, largest ``self_s`` first. ``self_s`` leaves out
    what nested records cover (an inner jit's trace inside its caller's,
    a constant's compile inside a trace), so the column adds up to the
    time in which any phase ran on a thread. ``until`` as for
    :func:`phase_records`. Print it after a restart to see what the
    seconds before the first step went into."""
    records = phase_records(until)
    own = _self_seconds(records)
    rows = {}
    for i, r in enumerate(records):
        row = rows.setdefault((r.fun_name, r.phase), {
            "fun_name": r.fun_name, "phase": r.phase, "calls": 0,
            "self_s": 0.0, "total_s": 0.0, "cache_hits": 0,
            "cache_misses": 0})
        row["calls"] += 1
        row["self_s"] += own[i]
        row["total_s"] += r.end - r.start
        row["cache_hits"] += r.cache_hit is True
        row["cache_misses"] += r.cache_hit is False
    return sorted(rows.values(), key=lambda row: -row["self_s"])


# -- abstract signatures ----------------------------------------------------

def _leaf_path_str(path):
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _describe_leaf(x):
    """One stable string per argument leaf — everything that can key a
    retrace: shape/dtype/weak-type for arrays, the named-sharding spec
    when one is attached (a resharded input retraces), and the VALUE of
    Python scalars/strings (value-keyed when the arg is static; for a
    traced weak-typed scalar the extra precision is harmless because
    diffs are only taken on calls that did compile)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            desc = f"{dtype.name if hasattr(dtype, 'name') else dtype}" \
                   f"{list(shape)}"
        except Exception:
            desc = f"{dtype}[?]"
        if getattr(x, "weak_type", False):
            desc += "~"
        sharding = getattr(x, "sharding", None)
        spec = getattr(sharding, "spec", None)
        if spec is not None:
            desc += f"@{spec}"
        return desc
    if isinstance(x, (bool, int, float, complex, str, bytes, type(None))):
        return f"py:{type(x).__name__}={x!r}"
    return f"static:{type(x).__name__}"


def abstract_signature(args, kwargs=None):
    """``{arg_path: descriptor}`` for a call's arguments — the host-side
    mirror of the signature jit keys its cache on. Paths are '/'-joined
    pytree paths under ``args/<i>`` / ``kwargs/<name>``."""
    import jax

    sig = {}
    for root, tree in (("args", tuple(args)),
                       ("kwargs", dict(kwargs or {}))):
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                tree, is_leaf=lambda l: l is None)[0]:
            sig[f"{root}/{_leaf_path_str(path)}"] = _describe_leaf(leaf)
    return sig


def diff_signatures(old, new):
    """Per-argument changes between two :func:`abstract_signature`
    dicts: ``[{"arg", "old", "new"}, ...]`` (``None`` marks an
    added/removed argument), sorted by argument path."""
    changes = []
    for key in sorted(set(old) | set(new)):
        a, b = old.get(key), new.get(key)
        if a != b:
            changes.append({"arg": key, "old": a, "new": b})
    return changes


# -- the watcher ------------------------------------------------------------

class _FnStats:
    __slots__ = ("name", "signature", "compiles", "recompiles",
                 "compile_seconds", "last_change")

    def __init__(self, name):
        self.name = name
        self.signature = None
        self.compiles = 0
        self.recompiles = 0
        self.compile_seconds = 0.0
        self.last_change = None


def _cache_size(fn):
    try:
        return int(fn._cache_size())
    except Exception:
        return None


class _WatchedFunction:
    """Host-side wrapper around one jitted callable. Delegates every
    attribute (``lower``, ``_cache_size``, ...) to the wrapped function,
    so it drops into code that uses the AOT API."""

    def __init__(self, fn, name, watcher):
        self._fn = fn
        self._name = name
        self._watcher = watcher
        self.__wrapped__ = fn

    def __call__(self, *args, **kwargs):
        w = self._watcher
        if not w.enabled:
            return self._fn(*args, **kwargs)
        before = _cache_size(self._fn)
        nb_before = backend_compiles()[0]
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        after = _cache_size(self._fn)
        if after is not None and before is not None:
            compiled = after > before
        else:  # no pjit cache introspection: fall back to process count
            compiled = backend_compiles()[0] > nb_before
        if compiled:
            w._on_compile(self._name, abstract_signature(args, kwargs), dt)
            w._maybe_lint(self._name, self._fn, args, kwargs)
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


class CompileWatcher:
    """Trace/compile accounting for jitted functions (host-side only).

    Usable three ways: as a plain object (``w = CompileWatcher();
    step = w.watch(step)``), as a context manager (the exit emits a
    ``compile`` summary event covering the block), and process-globally
    via :func:`get_watcher` + ``APEX_TPU_COMPILE_WATCH=1``. A disabled
    watcher's ``watch`` returns the function unchanged.
    """

    def __init__(self, *, enabled=None, registry=None, lint=None):
        if enabled is None:
            enabled = os.environ.get(ENV_WATCH, "") not in ("", "0")
        if lint is None:
            lint = os.environ.get(ENV_LINT, "") not in ("", "0")
        self.enabled = bool(enabled)
        self.lint_enabled = bool(lint)
        self._registry = registry
        self.functions = {}
        self.lint_reports = {}
        self._entered_at = None
        if self.enabled:
            install_monitoring()

    # -- enablement ---------------------------------------------------------

    def enable(self):
        self.enabled = True
        install_monitoring()
        return self

    def disable(self):
        self.enabled = False
        return self

    def _reg(self):
        return self._registry or get_registry()

    # -- watching -----------------------------------------------------------

    def watch(self, fn, name=None):
        """Wrap ``fn`` (typically a jitted callable) so every
        trace+compile is counted, timed, and — when it is a recompile —
        signature-diffed. Returns ``fn`` itself when disabled."""
        if not self.enabled:
            return fn
        if name is None:
            name = getattr(fn, "__name__", None) or repr(fn)
        self.functions.setdefault(name, _FnStats(name))
        return _WatchedFunction(fn, name, self)

    def _on_compile(self, name, signature, call_seconds):
        rec = self.functions.setdefault(name, _FnStats(name))
        rec.compiles += 1
        rec.compile_seconds += call_seconds
        changed = None
        if rec.signature is not None:  # a RE-compile: name the culprit
            rec.recompiles += 1
            changed = diff_signatures(rec.signature, signature)
            rec.last_change = changed
        rec.signature = signature
        reg = self._reg()
        if reg.enabled:
            reg.counter(f"compile/count/{name}").inc()
            reg.histogram("compile/call_seconds").observe(call_seconds)
            reg.event("compile", name,
                      compiles=rec.compiles,
                      recompile=rec.recompiles > 0 and changed is not None,
                      call_seconds=round(call_seconds, 6),
                      changed=changed)

    # -- HLO lint (apex_tpu.analysis; APEX_TPU_HLO_LINT=1) ------------------

    def _maybe_lint(self, name, fn, args, kwargs, *, lowered=None):
        """Lint the program that just compiled and emit ``lint`` events.
        Never raises: a lint crash is a telemetry gap, not a training
        failure. Reports accumulate in ``self.lint_reports``."""
        if not (self.enabled and self.lint_enabled):
            return None
        from apex_tpu import analysis

        try:
            if lowered is not None:
                report = analysis.lint_lowered(lowered, name=name)
            else:
                report = analysis.lint_fn(fn, *args, name=name,
                                          **(kwargs or {}))
        except Exception as e:  # noqa: BLE001 — lint must never kill a run
            reg = self._reg()
            if reg.enabled:
                reg.event("lint", name, error=f"{type(e).__name__}: "
                                             f"{str(e)[:200]}")
            return None
        self.lint_reports[name] = report
        analysis.report_to_registry(report, registry=self._registry,
                                    name=name)
        return report

    def lint_violation_count(self):
        """Total findings across every lint this watcher ran."""
        return sum(len(r.findings) for r in self.lint_reports.values())

    def record_aot(self, name, args=(), kwargs=None, *, seconds=0.0,
                   lowered=None):
        """Register an ahead-of-time compile (``jit(...).lower(args)
        .compile()`` — the ServeEngine startup path) under ``name``.

        AOT executables never pass through :meth:`watch`'s cache-size
        probe (calling one cannot compile), so the startup compile is
        recorded explicitly here: it lands in the same per-function
        stats, ``compile`` JSONL events, and signature bookkeeping as a
        watched jit compile — and a second ``record_aot`` under the
        same name with a different signature shows up as a named
        recompile, exactly like a drifting jit signature would.

        ``lowered`` (the pre-compile ``Lowered``) opts the AOT compile
        into the HLO lint pass when ``APEX_TPU_HLO_LINT=1`` — the
        ServeEngine passes each ladder entry's lowering here so the
        serving executables are linted without a second trace."""
        if not self.enabled:
            return
        self._on_compile(name, abstract_signature(args, kwargs), seconds)
        if lowered is not None:
            self._maybe_lint(name, None, (), None, lowered=lowered)

    # -- accounting ---------------------------------------------------------

    def compile_count(self, name=None):
        """Compiles of one watched function (or the sum over all)."""
        if name is not None:
            rec = self.functions.get(name)
            return rec.compiles if rec else 0
        return sum(r.compiles for r in self.functions.values())

    def recompile_count(self):
        return sum(r.recompiles for r in self.functions.values())

    def last_changes(self):
        """``{fn_name: [{"arg", "old", "new"}, ...]}`` for every watched
        function whose latest compile was a signature-diffed recompile."""
        return {n: r.last_change for n, r in self.functions.items()
                if r.last_change}

    # -- context manager ----------------------------------------------------

    def __enter__(self):
        self.enable()
        self._entered_at = backend_compiles()
        return self

    def __exit__(self, *exc):
        count0, secs0 = self._entered_at or (0, 0.0)
        count1, secs1 = backend_compiles()
        reg = self._reg()
        if reg.enabled:
            reg.event("compile", "watch_summary",
                      backend_compiles=count1 - count0,
                      backend_compile_seconds=round(secs1 - secs0, 6),
                      watched={n: {"compiles": r.compiles,
                                   "recompiles": r.recompiles}
                               for n, r in self.functions.items()})
        return False


_GLOBAL = None
_GLOBAL_LOCK = threading.Lock()


def get_watcher():
    """The process-global watcher, created on first use — enabled iff
    ``APEX_TPU_COMPILE_WATCH`` was set at that point (call
    ``get_watcher().enable()`` to opt in programmatically, as
    ``bench.py ddp_memwatch`` does)."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = CompileWatcher()
    return _GLOBAL


@contextlib.contextmanager
def assert_no_recompiles(watcher=None, *, allow=0):
    """Fail loudly if anything compiled inside the block.

    The test/CI primitive for shape stability: warm the step up, then
    run N steady-state steps under this context — any retrace (a Python
    scalar leaking into the traced signature, a drifting input shape, a
    flipped sharding) raises :class:`RecompileError`. Counting is
    process-wide via the ``jax.monitoring`` backend-compile listener,
    so even compiles of helpers you forgot to watch are caught; when a
    watched function saw the recompile, the error names the changed
    argument (path, old -> new). ``allow`` tolerates that many compiles
    (e.g. a known one-off lazy init inside the block)."""
    install_monitoring()
    watcher = watcher or get_watcher()
    before = backend_compiles()[0]
    marks = {n: r.recompiles for n, r in watcher.functions.items()}
    yield watcher
    delta = backend_compiles()[0] - before
    if delta <= allow:
        return
    detail = ""
    for name, rec in watcher.functions.items():
        if rec.recompiles > marks.get(name, 0) and rec.last_change:
            first = rec.last_change[0]
            detail = (f" Watched fn '{name}' recompiled: argument "
                      f"'{first['arg']}' changed "
                      f"{first['old']} -> {first['new']}.")
            break
    raise RecompileError(
        f"{delta} XLA compile(s) happened inside an "
        f"assert_no_recompiles block (allowed {allow}) — something is "
        f"retracing per call; check input shapes/dtypes and Python "
        f"scalars reaching the jitted signature.{detail}")
