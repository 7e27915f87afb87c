"""XLA cost accounting: FLOPs / bytes-accessed for a jitted step, and
achieved MFU / HBM-utilization against a per-device-kind peak table.

``step_cost(jitted, *args)`` extracts XLA's own cost analysis from the
lowered (or compiled) computation — the measured counterpart to the
analytic FLOP formulas in ``bench.py``. By default it stops at
``.lower(...)``: the trace-only HLO cost analysis avoids paying a second
compilation (the jit call's own compile is cached separately, and a
large model can take tens of minutes to compile on this host's 1-core
CPU). Pass ``use_compiled=True`` for post-optimization numbers when a
compile is acceptable (or already cached).

The peak table is keyed by ``device_kind`` — what
``jax.devices()[0].device_kind`` reports — and every row names its
source. A device that is not in the table is an error, never a default:
a utilization against another chip's peak is not a utilization.
"""

# device_kind -> (peak bf16 FLOP/s, peak HBM bytes/s)
_PEAKS_BY_DEVICE_KIND = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s per chip
    "TPU v5 lite": (197e12, 819e9),
    # order-of-magnitude placeholder: the CPU mesh exists for tests,
    # not rooflines
    "cpu": (0.1e12, 0.05e12),
}


def peak_table(device_kind=None):
    """(peak_flops_per_sec, peak_hbm_bytes_per_sec) for ``device_kind``
    (default: the first jax device's own). Raises ``ValueError`` for a
    kind the table does not hold."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return _PEAKS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add a "
            f"row (with its source) to telemetry.xla_cost."
            f"_PEAKS_BY_DEVICE_KIND — known: "
            f"{sorted(_PEAKS_BY_DEVICE_KIND)}") from None


def _normalize(analysis):
    """XLA returns a dict (Lowered) or a list of per-computation dicts
    (Compiled); collapse to {"flops", "bytes_accessed"} floats."""
    if analysis is None:
        return None
    if not isinstance(analysis, dict):
        entries = [a for a in analysis if isinstance(a, dict)]
        if not entries:
            return None
        analysis = entries[0]
    return {
        "flops": float(analysis.get("flops", 0.0)),
        "bytes_accessed": float(analysis.get("bytes accessed", 0.0)),
    }


def cost_from_lowered(lowered, use_compiled=False):
    """Cost analysis of an already-``.lower()``-ed computation (lets a
    caller that also wants ``memory_analysis`` pay for one lowering,
    not two — see ``bench._measure_step_cost``)."""
    if use_compiled:
        try:
            return _normalize(lowered.compile().cost_analysis())
        except Exception:
            pass
    try:
        return _normalize(lowered.cost_analysis())
    except Exception:
        return None


def step_cost(jitted, *args, use_compiled=False, **kwargs):
    """Cost analysis of one invocation of ``jitted(*args, **kwargs)``:
    ``{"flops", "bytes_accessed"}``, or None when the backend offers no
    analysis. Lowering re-traces the function (host-side only — safe on
    donated/deleted example arrays since only avals are read)."""
    try:
        lowered = jitted.lower(*args, **kwargs)
    except Exception:
        return None
    return cost_from_lowered(lowered, use_compiled=use_compiled)


def utilization(flops_per_step, step_seconds, *, bytes_per_step=None,
                device_kind=None):
    """Achieved fractions of peak: ``{"mfu", "hbm_util", ...}``.

    ``mfu`` = model FLOP/s over peak FLOP/s (PaLM convention — pass
    model FLOPs, not hardware FLOPs, if you want the classic MFU);
    ``hbm_util`` = bytes-accessed/s over peak HBM bandwidth (an upper
    bound on demand — XLA's bytes-accessed counts every operand touch,
    not DRAM traffic)."""
    peak_flops, peak_hbm = peak_table(device_kind)
    out = {
        "flops_per_sec": flops_per_step / step_seconds,
        "mfu": flops_per_step / step_seconds / peak_flops,
    }
    if bytes_per_step is not None:
        out["bytes_per_sec"] = bytes_per_step / step_seconds
        out["hbm_util"] = bytes_per_step / step_seconds / peak_hbm
    return out


def record_step_cost(cost, step_seconds, *, registry=None,
                     device_kind=None):
    """Fold a :func:`step_cost` result + measured step time into the
    registry: ``mfu`` / ``hbm_util`` / ``model_flops_per_step_xla``
    gauges. Returns the :func:`utilization` dict (or None)."""
    from apex_tpu.telemetry.registry import get_registry

    if cost is None or not step_seconds:
        return None
    util = utilization(cost["flops"], step_seconds,
                       bytes_per_step=cost.get("bytes_accessed"),
                       device_kind=device_kind)
    reg = registry or get_registry()
    if reg.enabled:
        reg.gauge("model_flops_per_step_xla").set(cost["flops"])
        reg.gauge("mfu").set(util["mfu"])
        if "hbm_util" in util:
            reg.gauge("hbm_util").set(util["hbm_util"])
        reg.event("xla_cost", "step",
                  flops=cost["flops"],
                  bytes_accessed=cost.get("bytes_accessed"),
                  step_seconds=step_seconds,
                  mfu=util["mfu"], hbm_util=util.get("hbm_util"))
    return util
