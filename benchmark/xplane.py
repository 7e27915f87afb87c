"""From the profiler's ``.xplane.pb`` to numbers: device busy time, time
by operation, idle gaps by what the host was doing.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU's
plane is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
per executed HLO operation (start and duration in nanoseconds, the
operation's name, and in its stats the HLO category and the long name
with the source scope). Host threads are lines of the plane
``/host:CPU``; the benchmark's own spans (``jax.profiler.TraceAnnotation``)
are events there, on the same clock.

Everything below the loader works on plain tuples, so the tests drive it
with a synthetic trace.
"""

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.\d+|\.remat\d*|\.clone\d*)+$")
_INSTR = re.compile(r"^%([\w\-.]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


def parse_hlo(text: str) -> tuple:
    """An ``XLA Ops`` event is named by its whole HLO instruction,
    ``%name.12 = shape opcode(operands), kind=kLoop, ...``. ->
    (instruction name, opcode, fusion kind or ""). A name that is not an
    instruction comes back as it is, with no opcode."""
    m = _INSTR.match(text)
    if not m:
        return text, "", ""
    rest = text[m.end() - 1:]
    op = _OPCODE.search(rest)
    kind = _KIND.search(rest)
    return m.group(1), op.group(1) if op else "", \
        kind.group(1) if kind else ""


@dataclasses.dataclass
class Op:
    device: int
    name: str        # the HLO instruction's name, e.g. ``fusion.123``
    start: float     # seconds
    end: float
    opcode: str = ""   # ``fusion``, ``custom-call``, ``copy``, ...
    kind: str = ""     # a fusion's kind: ``kLoop``, ``kOutput``, ...


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, start: float, end: float):
    """The idle ``(start, end)`` gaps of ``[start, end]`` left by the
    union of ``intervals``."""
    out, cursor = [], start
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, end)))
        cursor = max(cursor, e)
        if cursor >= end:
            break
    if cursor < end:
        out.append((cursor, end))
    return [(s, e) for s, e in out if e > s]


def group_name(op: Op) -> str:
    """A stable name for an operation: opcode (with a fusion's kind) and
    the instruction's name without its number, as in
    ``custom-call:self_attention`` or ``fusion.kOutput:convert_reduce_fusion``."""
    base = _SUFFIX.sub("", op.name) or op.name
    head = f"{op.opcode}.{op.kind}" if op.kind else op.opcode
    if not head or head == base:
        return base
    return f"{head}:{base}"


class Trace:
    """A reduced trace: device operations and the benchmark's host spans.

    The window is the span from the first device operation's start to the
    last one's end, unless ``window`` is given."""

    def __init__(self, ops, spans, window=None):
        self.ops = list(ops)
        self.spans = sorted(spans, key=lambda s: s.start)
        self.devices = sorted({op.device for op in self.ops})
        if window is None and self.ops:
            window = (min(op.start for op in self.ops),
                      max(op.end for op in self.ops))
        self.window = window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0] if self.window else 0.0

    def _clipped(self, device, keep=None):
        lo, hi = self.window
        for op in self.ops:
            if op.device != device or (keep and not keep(op)):
                continue
            s, e = max(op.start, lo), min(op.end, hi)
            if e > s:
                yield s, e

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(union_seconds(self._clipped(d))
                   for d in self.devices) / len(self.devices)

    def seconds_in(self, keep) -> float:
        """Device time of the operations ``keep`` selects, averaged over
        devices (durations summed: operations of one stream do not
        overlap)."""
        if not self.devices:
            return 0.0
        return sum(e - s for d in self.devices
                   for s, e in self._clipped(d, keep)) / len(self.devices)

    def count(self, keep) -> int:
        return sum(1 for op in self.ops if keep(op))

    def top_ops(self, n=10):
        totals = {}
        for d in self.devices:
            for op in self.ops:
                if op.device == d:
                    key = group_name(op)
                    totals[key] = totals.get(key, 0.0) + (op.end - op.start)
        k = max(len(self.devices), 1)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
        return [[name, seconds / k] for name, seconds in ranked]

    def idle_gaps(self, n=10):
        """Idle time of the first device, summed by the benchmark span
        the host was in when each gap began."""
        if not self.devices:
            return []
        device = self.devices[0]
        by_span, inside, nxt = {}, [], 0
        for s, e in gaps(self._clipped(device), *self.window):
            # gaps and spans both come in time order: keep the spans that
            # hold ``s``; the last one started is the innermost
            while nxt < len(self.spans) and self.spans[nxt].start <= s:
                inside.append(self.spans[nxt])
                nxt += 1
            inside = [sp for sp in inside if sp.end >= s]
            name = inside[-1].name if inside \
                else "outside the benchmark's spans"
            total, count, longest = by_span.get(name, (0.0, 0, 0.0))
            by_span[name] = (total + (e - s), count + 1,
                             max(longest, e - s))
        ranked = sorted(by_span.items(), key=lambda kv: -kv[1][0])[:n]
        return [[f"{name} ({count} gaps, longest {longest * 1e3:.3f} ms)",
                 total] for name, (total, count, longest) in ranked]


def find_xplane(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_dir(trace_dir, span_names=()) -> Trace:
    """Read the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    wanted = set(span_names)
    ops, spans = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            device = int(m.group(1))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, opcode, kind = parse_hlo(ev.name)
                    start = ev.start_ns * 1e-9
                    ops.append(Op(device, name, start,
                                  start + ev.duration_ns * 1e-9,
                                  opcode, kind))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted or ev.name.split("#")[0] in wanted:
                        start = ev.start_ns * 1e-9
                        spans.append(Span(ev.name.split("#")[0], start,
                                          start + ev.duration_ns * 1e-9))
    return Trace(ops, spans)
