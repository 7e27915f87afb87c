"""HBM budget accounting: know the peak before the chip finds out.

The other silent killer next to recompilation: a config that exceeds
HBM dies with a raw ``RESOURCE_EXHAUSTED`` naming no buffer, usually
tens of minutes into a compile — and the ROADMAP's "as fast as the
hardware allows" (raise the batch, drop remat, widen the model) is
exactly the knob-set you cannot touch safely without knowing peak HBM
headroom per step. Memory attribution is also what makes ZeRO-style
sharding decisions tractable (Xu et al., arXiv:2004.13336): the win IS
bytes, so the bytes must be measurable.

Four host-side pieces (nothing here touches the traced program):

- :func:`step_memory` — wraps ``lowered.compile().memory_analysis()``
  into one report dict: argument / output / temp / generated-code
  bytes, the derived ``peak_bytes``, the device's HBM capacity
  (``APEX_TPU_HBM_GB`` override, else the device's own
  ``memory_stats()['bytes_limit']``; a CPU stand-in for tests) and the
  ``headroom_frac`` that lands in the ``memory/hbm_headroom`` gauge.
  Every report is appended to an in-process headroom trend ring — the
  post-mortem's "how did it trend" answer.
- :func:`live_buffer_census` — groups ``jax.live_arrays()`` by
  shape/dtype (plus caller-supplied pytree labels, e.g.
  ``labels={"params": params, "opt": opt_state}`` — live arrays carry
  no named scopes, so attribution comes from matching the caller's own
  trees) into a top-K table by bytes.
- :func:`preflight` — compare estimated peak against capacity *before*
  dispatch: warn, or raise :class:`MemoryBudgetError` with
  ``strict=True``.
- :func:`oom_postmortem` / :func:`oom_guard` — catch
  ``RESOURCE_EXHAUSTED`` from a guarded train step and write an atomic
  ``memory-postmortem-rank<N>.json`` (census + last step_memory report
  + headroom trend), mirroring the numerics post-mortem format, then
  re-raise as :class:`HBMExhaustedError`. ``resilience.guarded_call``
  is the train-loop entry point; ``faults.inject_alloc_failure`` makes
  the path testable on CPU.

Env knobs: ``APEX_TPU_HBM_GB`` (capacity override, in GB),
``APEX_TPU_MEMORY_DIR`` (post-mortem directory; falls back to the
telemetry JSONL dir, then the CWD). See docs/observability.md.
"""

import collections
import contextlib
import json
import os
import time
import warnings

from apex_tpu.telemetry.registry import _process_index, get_registry

ENV_HBM_GB = "APEX_TPU_HBM_GB"
ENV_DIR = "APEX_TPU_MEMORY_DIR"
POSTMORTEM_BASENAME = "memory-postmortem-rank{rank}.json"
TREND_LENGTH = 64

# The CPU "HBM" is host RAM and this figure a stand-in for tests. A TPU
# has no row here on purpose: its capacity is the device's own
# ``memory_stats()['bytes_limit']`` (or $APEX_TPU_HBM_GB), never a
# platform-wide guess — chip generations differ (v4 32G, v5e 16G,
# v5p 95G).
_CPU_CAPACITY_BYTES = int(16e9)


class MemoryBudgetError(RuntimeError):
    """Raised by ``preflight(strict=True)`` when the estimated peak
    exceeds HBM capacity — fail before dispatch, not 20 minutes into
    the compile."""


class HBMExhaustedError(RuntimeError):
    """Raised by :func:`oom_guard` after a RESOURCE_EXHAUSTED killed a
    step and the memory post-mortem landed — the OOM sibling of
    ``resilience.NonFiniteError``."""


def _default_backend():
    import jax

    return jax.default_backend()


def _device_bytes_limit():
    """The accelerator's own reported capacity (TPUs report one via
    ``Device.memory_stats()``; the CPU backend reports no stats)."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    limit = (stats or {}).get("bytes_limit")
    return int(limit) if limit else None


def hbm_capacity_bytes(backend=None):
    """HBM capacity in bytes for ``backend`` (default: the current jax
    default backend). Resolution order: ``$APEX_TPU_HBM_GB`` (decimal
    GB) > the device's own ``bytes_limit`` > the CPU stand-in. An
    accelerator that reports no limit raises: headroom against a
    guessed capacity is not headroom."""
    env = os.environ.get(ENV_HBM_GB)
    if env:
        return int(float(env) * 1e9)
    if backend is None:
        backend = _default_backend()
    if backend == "cpu":
        return _CPU_CAPACITY_BYTES
    measured = _device_bytes_limit()
    if measured is None:
        raise RuntimeError(
            f"the {backend} device reports no memory_stats()"
            f"['bytes_limit']; set ${ENV_HBM_GB} to its HBM capacity")
    return measured


# -- step memory accounting -------------------------------------------------

# last report + bounded headroom trend, fed by report_from_lowered and
# consumed by the OOM post-mortem ("what did headroom look like before
# the step died")
_LAST_REPORT = None
_TREND = collections.deque(maxlen=TREND_LENGTH)


def headroom_trend():
    """The last ``TREND_LENGTH`` step-memory snapshots, oldest first:
    ``[{"t", "peak_bytes", "headroom_frac"}, ...]``."""
    return list(_TREND)


def reset_trend():
    """Drop the trend + last report (test isolation)."""
    global _LAST_REPORT
    _LAST_REPORT = None
    _TREND.clear()


def report_from_lowered(lowered, *, backend=None, registry=None,
                        record=True, name="step"):
    """Memory report for an already-``.lower()``-ed computation.

    Compiles it (``lowered.compile()`` — with the persistent compile
    cache enabled this is a disk hit when the same program was compiled
    before; without it, one extra compile) and reads XLA's own
    ``memory_analysis()``. Returns None when the backend offers no
    analysis. The report lands in the ``memory/hbm_headroom`` /
    ``memory/peak_hbm_bytes`` gauges, a ``memory`` JSONL event, and the
    in-process headroom trend unless ``record=False``."""
    global _LAST_REPORT
    try:
        stats = lowered.compile().memory_analysis()
    except Exception:
        return None
    if stats is None:
        return None
    arg = int(getattr(stats, "argument_size_in_bytes", 0))
    out = int(getattr(stats, "output_size_in_bytes", 0))
    temp = int(getattr(stats, "temp_size_in_bytes", 0))
    code = int(getattr(stats, "generated_code_size_in_bytes", 0))
    alias = int(getattr(stats, "alias_size_in_bytes", 0))
    # the standard XLA accounting: aliased (donated) buffers are counted
    # in both argument and output sizes, so subtract them once
    peak = arg + out + temp + code - alias
    capacity = hbm_capacity_bytes(backend)
    report = {
        "name": name,
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": temp,
        "generated_code_bytes": code,
        "alias_bytes": alias,
        "peak_bytes": peak,
        "capacity_bytes": capacity,
        "headroom_frac": 1.0 - peak / capacity if capacity else None,
        "backend": backend or _default_backend(),
    }
    if record:
        _LAST_REPORT = report
        _TREND.append({"t": round(time.time(), 6), "peak_bytes": peak,
                       "headroom_frac": report["headroom_frac"]})
        reg = registry or get_registry()
        if reg.enabled:
            reg.gauge("memory/peak_hbm_bytes").set(peak)
            if report["headroom_frac"] is not None:
                reg.gauge("memory/hbm_headroom").set(
                    report["headroom_frac"])
            fields = dict(report)
            fields["step"] = fields.pop("name")  # "name" is the event's
            reg.event("memory", "step_memory", **fields)
    return report


def step_memory(fn, *args, backend=None, registry=None, record=True,
                name=None, **kwargs):
    """Memory report for one invocation of ``fn(*args, **kwargs)``
    (``fn`` a jitted callable, or any traceable — it is jitted on the
    fly). Host-side only: lowering reads avals, never runs the step.
    Returns the :func:`report_from_lowered` dict, or None when no
    analysis is available."""
    try:
        lower = getattr(fn, "lower", None)
        if lower is None:
            import jax

            lower = jax.jit(fn).lower
        lowered = lower(*args, **kwargs)
    except Exception:
        return None
    if name is None:
        name = getattr(fn, "__name__", None) or "step"
    return report_from_lowered(lowered, backend=backend,
                               registry=registry, record=record,
                               name=name)


# -- live buffer census -----------------------------------------------------

def live_buffer_census(top_k=10, *, labels=None):
    """Group the process's live device arrays into a top-K table.

    ``jax.live_arrays()`` grouped by (label, shape, dtype), descending
    by total bytes. Arrays carry no named scopes, so ``labels`` maps
    group names to pytrees whose leaves are matched by identity
    (``labels={"params": params, "opt_state": opt_state}``); unmatched
    arrays group under ``"<anon>"``. Returns ``{"total_arrays",
    "total_bytes", "groups": [{"label", "shape", "dtype", "count",
    "bytes"}, ...], "dropped_groups", "dropped_bytes"}``."""
    try:
        import jax

        arrays = jax.live_arrays()
    except Exception:
        arrays = []
    id_to_label = {}
    if labels:
        import jax

        for label, tree in labels.items():
            for leaf in jax.tree_util.tree_leaves(tree):
                id_to_label[id(leaf)] = label
    groups = {}
    total_bytes = 0
    total_arrays = 0
    for x in arrays:
        try:
            if x.is_deleted():
                continue
            nbytes = int(x.nbytes)
            key = (id_to_label.get(id(x), "<anon>"),
                   tuple(x.shape), str(x.dtype))
        except Exception:
            continue
        g = groups.setdefault(key, {"count": 0, "bytes": 0})
        g["count"] += 1
        g["bytes"] += nbytes
        total_bytes += nbytes
        total_arrays += 1
    rows = [{"label": label, "shape": list(shape), "dtype": dtype,
             "count": g["count"], "bytes": g["bytes"]}
            for (label, shape, dtype), g in groups.items()]
    rows.sort(key=lambda r: (-r["bytes"], r["label"], r["dtype"]))
    kept = rows[:top_k] if top_k else rows
    return {
        "total_arrays": total_arrays,
        "total_bytes": total_bytes,
        "groups": kept,
        "dropped_groups": max(0, len(rows) - len(kept)),
        "dropped_bytes": sum(r["bytes"] for r in rows[len(kept):]),
    }


# -- preflight --------------------------------------------------------------

def preflight(fn, *args, strict=False, capacity_bytes=None,
              safety_frac=0.0, backend=None, registry=None, **kwargs):
    """Estimate the step's peak HBM *before* dispatch and complain when
    it exceeds capacity: a warning by default, a
    :class:`MemoryBudgetError` with ``strict=True``. ``safety_frac``
    reserves a fraction of capacity (XLA's analysis is pre-fragmentation
    — real allocators need slack). Returns the step_memory report (None
    when the backend offers no analysis — never a false alarm)."""
    report = step_memory(fn, *args, backend=backend, registry=registry,
                         **kwargs)
    if report is None:
        return None
    capacity = capacity_bytes if capacity_bytes is not None \
        else report["capacity_bytes"]
    budget = int(capacity * (1.0 - safety_frac))
    report = dict(report, budget_bytes=budget,
                  over_budget=report["peak_bytes"] > budget)
    if report["over_budget"]:
        msg = (f"estimated peak HBM {report['peak_bytes'] / 1e9:.2f} GB "
               f"exceeds the {budget / 1e9:.2f} GB budget "
               f"({capacity / 1e9:.2f} GB capacity, "
               f"{safety_frac:.0%} safety margin) — this step will "
               f"RESOURCE_EXHAUSTED at dispatch; shrink the batch, "
               f"re-enable remat, or shard the optimizer state (ZeRO)")
        reg = registry or get_registry()
        if reg.enabled:
            reg.event("memory", "preflight_over_budget",
                      peak_bytes=report["peak_bytes"],
                      budget_bytes=budget, capacity_bytes=capacity)
        if strict:
            raise MemoryBudgetError(msg)
        warnings.warn(msg, stacklevel=2)
    return report


# -- OOM post-mortem --------------------------------------------------------

def is_oom_error(exc):
    """True when ``exc`` is an HBM exhaustion — XLA's
    ``RESOURCE_EXHAUSTED`` runtime error, or the synthetic one
    ``faults.inject_alloc_failure`` raises (same message marker, so the
    post-mortem path is testable on CPU)."""
    text = f"{type(exc).__name__}: {exc}"
    return ("RESOURCE_EXHAUSTED" in text
            or "Out of memory" in text
            or "out of memory" in text)


def resolve_dir(directory=None, registry=None):
    if directory:
        return directory
    env = os.environ.get(ENV_DIR)
    if env:
        return env
    reg = registry or get_registry()
    return reg.jsonl_dir or "."


# the most recent post-mortem record (with "path") — lets callers
# (bench, smoke stages) find what oom_guard dumped on their behalf,
# mirroring FlightRecorder.last_postmortem
_LAST_POSTMORTEM = None


def last_postmortem():
    """The most recent :func:`oom_postmortem` record this process wrote
    (None before the first)."""
    return _LAST_POSTMORTEM


def oom_postmortem(error=None, directory=None, *, registry=None,
                   census=None, labels=None, extra=None):
    """Write ``memory-postmortem-rank<N>.json`` (atomic tmp+rename;
    overwrites — the newest wreckage is the one that matters):
    the live-buffer census at death, the last :func:`step_memory`
    report, and the headroom trend — mirroring the numerics post-mortem
    format. Returns the record dict (with ``"path"``); also lands a
    ``memory`` event in the registry when enabled."""
    rank = _process_index()
    directory = resolve_dir(directory, registry)
    record = {
        "t": round(time.time(), 6),
        "reason": "resource_exhausted",
        "rank": rank,
        "error": None if error is None else
        f"{type(error).__name__}: {str(error)[:2000]}",
        "census": census if census is not None
        else live_buffer_census(labels=labels),
        "last_step_memory": _LAST_REPORT,
        "headroom_trend": headroom_trend(),
        "capacity_bytes": hbm_capacity_bytes(),
    }
    if extra:
        record.update(extra)
    path = os.path.join(directory, POSTMORTEM_BASENAME.format(rank=rank))
    try:
        os.makedirs(directory, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1)
        os.replace(tmp, path)
        record["path"] = path
    except OSError:
        # an unwritable post-mortem dir must never mask the OOM itself
        record["path"] = None
    reg = registry or get_registry()
    if reg.enabled:
        reg.event("memory", "postmortem", path=record["path"],
                  error=record["error"],
                  census_bytes=record["census"]["total_bytes"],
                  trend_points=len(record["headroom_trend"]))
    global _LAST_POSTMORTEM
    _LAST_POSTMORTEM = record
    return record


@contextlib.contextmanager
def oom_guard(directory=None, *, registry=None, labels=None):
    """Run a block (typically one train-step dispatch + its host fetch)
    under the OOM post-mortem handler: a RESOURCE_EXHAUSTED escaping the
    block writes the post-mortem and re-raises as
    :class:`HBMExhaustedError` (with the original as ``__cause__``);
    every other exception passes through untouched."""
    try:
        yield
    except Exception as e:
        if isinstance(e, HBMExhaustedError) or not is_oom_error(e):
            raise
        record = oom_postmortem(e, directory, registry=registry,
                                labels=labels)
        raise HBMExhaustedError(
            f"step dispatch hit RESOURCE_EXHAUSTED — HBM is over "
            f"budget, not transiently busy. Memory post-mortem "
            f"(live-buffer census + headroom trend): "
            f"{record['path'] or '<unwritable dir>'}. Triage: "
            f"docs/resilience.md 'When a step OOMs'.") from e
