"""Set-up by phase, for the six readers under ``setup_s``
(``layer_metrics/setup_{import,trace,lower,compile,unattributed}_s.py``
and ``setup_cache_misses.py``).

The harness times set-up from outside, as one number: ``values["setup_s"]``
seconds from ``run.py``'s first statement to the window's opening. What
the seconds went into is visible only from inside the program, which keeps
a record of every phase of every compile on the ``perf_counter`` clock
(``apex_tpu.telemetry.compile_watch``: ``trace``, Python to a jaxpr;
``lower``, jaxpr to MLIR with Mosaic's lowering of the Pallas kernels
inside it; ``compile``, XLA's compile or the persistent cache's load; and
``import``, the package's own). This module takes the records that **ended
before the window opened** and gives per phase the **union** of their
intervals: an inner jit's trace lies inside its caller's, and a sum would
count it twice. The window opened ``setup_s`` seconds after the run's
clock started: that is the ``_T0`` of the script that runs the cell
(``run.py``, ``tools/block_parts.py``: both hand it to ``harness.Clock``),
read from ``__main__``; where ``__main__`` has none (a test, another
driver) it is ``compile_watch.process_start_perf()``, the process's start
on the same clock, which on the chip's host lies 0.2-0.5 s before ``_T0``
(the interpreter's start and the script's own imports).
``unattributed_s`` is ``setup_s`` less the union of every record: the
import of jax, the TPU client's start, the first executions, the checked
steps and the warm-up, and Python between them. So the union of the named
phases and ``unattributed_s`` add up to the run's ``setup_s``.

The readers run after the window and the reference (``scopes.py`` lowers
the step a second time then); only what ended before the opening counts.
On a program without the record, outside Linux, or where ``values`` has no
``setup_s``, every reader reads nothing.
"""

import sys

from benchmark import xplane

PHASES = ("import", "trace", "lower", "compile")


def _record():
    """``(phase_records, process_start_perf)`` of the program, ``None``
    where it has none (the parent of the PR that brought the record)."""
    try:
        from apex_tpu.telemetry import compile_watch

        return compile_watch.phase_records, compile_watch.process_start_perf
    except (ImportError, AttributeError):
        return None


def split(records, start: float, setup_s: float) -> dict:
    """The readings from ``records`` (each with ``phase``, ``start``,
    ``end``, ``cache_hit``), the clock's start and ``setup_s``, all on
    one clock. Records that ended after ``start + setup_s`` are left out;
    one that began before ``start`` is cut to it."""
    opening = start + setup_s
    before = [r for r in records if r.end <= opening]
    spans = {phase: [(max(r.start, start), r.end) for r in before
                     if r.phase == phase] for phase in PHASES}
    out = {f"{phase}_s": xplane.union_seconds(spans[phase])
           for phase in PHASES}
    out["named_s"] = xplane.union_seconds(
        [iv for phase in PHASES for iv in spans[phase]])
    out["unattributed_s"] = setup_s - out["named_s"]
    out["cache_misses"] = sum(r.phase == "compile" and r.cache_hit is False
                              for r in before)
    out["records"] = len(before)
    out["opening"] = opening
    return out


def clock_start(process_start_perf):
    """The ``perf_counter`` reading ``setup_s`` counts from: the running
    script's ``_T0``, else the process's start (``None`` outside Linux)."""
    t0 = getattr(sys.modules.get("__main__"), "_T0", None)
    return t0 if isinstance(t0, float) else process_start_perf()


def _build(ctx):
    setup_s = (ctx.get("values") or {}).get("setup_s")
    record = _record()
    if setup_s is None or record is None:
        return None
    phase_records, process_start_perf = record
    start = clock_start(process_start_perf)
    if start is None:
        return None
    return split(phase_records(), start, setup_s)


def phases(ctx):
    """``split``'s readings for the run, made on the first call and kept
    in ``ctx``; ``None`` where there is nothing to read."""
    if "setup_phases" not in ctx:
        ctx["setup_phases"] = _build(ctx)
    return ctx["setup_phases"]


def reading(ctx, key):
    got = phases(ctx)
    return None if got is None else got[key]
