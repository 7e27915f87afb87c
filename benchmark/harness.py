"""What every kind of cell shares: finding a cell's files by name, the
look for a chip, the compile cache, spans, the traced window, the
per-layer readers, the comparison's print-out and the result line.

A cell is data: ``BENCHMARK.json`` names a configuration and a traffic
mix, and this module finds ``configs/<config>.json``,
``traffic/<traffic>.json``, ``limits/<workload>.json`` and, for each
per-layer metric, ``layer_metrics/<name>.py`` under ``<root>/benchmark``.
``root`` is the checkout; tests pass a directory of their own. Code is
found by name too: the configuration's ``family`` names
``families/<family>.py`` and ``reference/<family>.py``, a train mix's
``optimizer`` names ``optimizers/<name>.py``, and a mix's ``kind`` names
``<kind>_cell.py``.
"""

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = pathlib.Path(__file__).resolve().parent


class BenchmarkError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file as written
    arch: dict            # its sizes, as its family's module names them
    traffic_name: str
    mix: dict             # the traffic file
    limits: dict          # number compared -> limit
    end_to_end: list      # metric entries this cell reports
    per_layer: list
    root: pathlib.Path


def _read_json(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"missing {path}") from None


def _selected(metrics: list, cell: str, reported: set = None) -> list:
    """Entries of ``metrics`` this cell reports: those that list it under
    ``workloads``; one without the key belongs to every cell that reports
    the end-to-end metric it moves."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif reported is None or m.get("moves") in reported:
            out.append(m)
    return out


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    from benchmark import families

    root = pathlib.Path(root)
    bench = _read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise BenchmarkError(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(by_name)}")
    w = by_name[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _read_json(root / entry["file"])
    data = root / "benchmark"
    mix = _read_json(data / "traffic" / f"{w['traffic']}.json")
    limits_path = data / "limits" / f"{workload}.json"
    limits = _read_json(limits_path) if limits_path.exists() else {}
    e2e = _selected(bench["end_to_end"], workload)
    reported = {m["name"] for m in e2e}
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, arch=families.of(config["family"]).arch(config),
        traffic_name=w["traffic"], mix=mix,
        limits={k: v for k, v in limits.items() if k != "readings"},
        end_to_end=e2e,
        per_layer=_selected(bench["per_layer"], workload, reported),
        root=root)


# ------------------------------------------------------------ the device

def peaks(kind: str) -> dict:
    table = _read_json(PACKAGE / "peaks.json")
    if kind not in table or kind == "source":
        raise BenchmarkError(
            f"device kind {kind!r} is not in benchmark/peaks.json; a "
            f"device that is not in the table is an error, not a default")
    return table[kind]


def require_chips(chips: int) -> dict:
    """The devices as JAX reports them; fails without ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise BenchmarkError(
            f"the benchmark needs a TPU, jax found platform {platform!r}")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell needs {chips} chips, jax found {len(devices)}")
    kind = devices[0].device_kind
    peaks(kind)
    return {"platform": platform, "kind": kind, "count": chips}


def enable_cache():
    """JAX's persistent cache at ``<checkout>/.jit_cache`` (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), through the program's one rule."""
    from apex_tpu._compile_cache import enable_compile_cache

    return enable_compile_cache(min_compile_secs=0.0)


def memory_peak_bytes(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def compiles_so_far() -> int:
    from apex_tpu.telemetry import compile_watch

    compile_watch.install_monitoring()
    return compile_watch.backend_compiles()[0]


# ------------------------------------------------------------------ spans

def span(name: str):
    """A host span in the profiler's own trace, around one of the
    benchmark's calls into the program."""
    import jax

    return jax.profiler.TraceAnnotation(name)


SPAN_NAMES = ("step.dispatch", "loss.fetch", "input.next",
              "scheduler.step", "engine.prefill", "engine.decode")


@contextlib.contextmanager
def traced_window(cell: Cell, enabled: bool, out: dict):
    """Profile the block when ``enabled``; afterwards ``out["trace"]``
    holds the reduced trace (``xplane.Trace``). The raw trace lives under
    ``<checkout>/.bench_trace`` only until it has been read."""
    if not enabled:
        yield
        return
    import jax

    from benchmark import xplane

    trace_dir = cell.root / ".bench_trace" / cell.name
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        try:
            out["trace"] = xplane.load_dir(trace_dir, SPAN_NAMES)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)


@contextlib.contextmanager
def measured_window(cell: Cell, trace: bool, out: dict):
    """What holds around every measured window: the collector frozen and
    off, the profiler on for a ``--trace 1`` run, and afterwards
    ``out["compiles"]``, the compilations that fell inside."""
    compiles_before = compiles_so_far()
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        with traced_window(cell, trace, out):
            yield
    finally:
        gc.enable()
        gc.unfreeze()
    out["compiles"] = compiles_so_far() - compiles_before


# ------------------------------------------------------ per-layer readers

def load_reader(name: str, root: pathlib.Path):
    """``layer_metrics/<name>.py``'s ``read`` function, found by name."""
    path = pathlib.Path(root) / "benchmark" / "layer_metrics" / f"{name}.py"
    if not path.exists():
        raise BenchmarkError(f"no reader {path} for per-layer metric "
                             f"{name!r}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_per_layer(cell: Cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something to
    read. A reader returns ``None`` where it does not (no trace, no such
    event); the metric is then left out of the line, never reported 0."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"], cell.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------- the result

def compare(numbers: dict, limits: dict) -> tuple:
    """-> (correct, {name: [number, limit]}). Every limit has to have its
    number and every number has to be finite and within its limit; a
    number with no limit is shown with ``null`` and not held."""
    compared, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        compared[name] = [value, limit]
        if limit is not None and not (value == value and value <= limit):
            ok = False
    for name in limits:
        if name not in numbers:
            compared[name] = [None, limits[name]]
            ok = False
    return ok, compared


def emit(result: dict, compared: dict):
    """The comparison on standard error, then the one result line."""
    sys.stdout.flush()
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["compared"] = compared
    print(json.dumps(line), flush=True)


def metrics_line(cell: Cell, values: dict) -> dict:
    """The cell's end-to-end metrics, by name, with their units."""
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise BenchmarkError(f"cell {cell.name} did not measure "
                                 f"{m['name']}")
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def result_line(cell: Cell, outcome: dict, values: dict, window: dict,
                device: dict, peak: int, traced) -> dict:
    """The result without ``compared``: ``outcome`` (``correct``,
    ``attempted``, ``failed``), then the cell's end-to-end metrics from
    ``values`` or, for a ``--trace 1`` run (``traced`` is what
    ``measured_window`` left, else ``None``), the per-layer metrics whose
    readers find something, the trace's busy time and the breakdown."""
    from benchmark import flops

    device = dict(device, memory_peak_bytes=peak)
    result = dict(outcome)
    if traced is None:
        result["metrics"] = metrics_line(cell, values)
    else:
        tr = traced.get("trace")
        on_chip = device["platform"] == "tpu"
        ctx = {"cell": cell, "arch": cell.arch, "mix": cell.mix,
               "trace": tr, "window": window, "values": values,
               "flops": flops,
               "peaks": peaks(device["kind"]) if on_chip else None}
        result["metrics"] = read_per_layer(cell, ctx)
        if tr is not None and tr.ops:
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
            result["breakdown"] = {"device_ops": tr.top_ops(10),
                                   "idle_gaps": tr.idle_gaps(10)}
    result["device"] = device
    return result


def percentile(values, q: float) -> float:
    """The *q*-th percentile (0-100), linear between order statistics."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class Clock:
    """Process start (or as near as ``start`` was taken), for ``setup_s``."""

    def __init__(self, start: float = None):
        self.start = time.perf_counter() if start is None else start

    def since_start(self) -> float:
        return time.perf_counter() - self.start
