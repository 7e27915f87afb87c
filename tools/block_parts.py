#!/usr/bin/env python
"""Device milliseconds a step by part and phase, from one traced run of a
benchmark cell: the table PERF.md section 5 is written from.

    chiprun -- python3 tools/block_parts.py --workload nemotron3_nano_30b_a3b_train_8k --seed 7

Run by hand, on the chip; no cell runs it. It makes the benchmark's own
``--trace 1`` run of the cell in this process (``benchmark/train_cell.py``
``run``, so its result line is printed too, last), keeps the reduced
trace the harness read and the scope table the run's own readers lowered
the step for (``benchmark/scopes.py`` ``compiled_step``,
``apex_tpu.telemetry.scopes.scope_table``; lowered here where no reader
asked) and joins the two on the instruction name.

A *part* is finer than ``telemetry.scopes.classify``'s block: the block,
then the component of the scope that follows the block's own
(``moe/dispatch``, ``moe/combine``, ``moe/experts``, ``moe/router``,
``moe/shared``, ``ssm/scan``, ``mla/rope``, ...), where there is one. A
Pallas kernel is listed under its part by its own name as well.

Only *leaf* operations are summed: a ``while`` (or any operation inside
which another one starts) spans the operations of its body in the trace,
and a sum that took both would count a loop's work twice. A leaf of a
loop's body whose own scope names no block (XLA keeps ``while/body/gather``
of some and drops the program's scopes) is the loop's. Beside the sum
of a block's leaves stands the union of all its operations' intervals,
which is what ``benchmark/block_time.py`` reads (``moe_ms_per_step``).

Under the device table stands where the run's set-up went, from the
program's record of compile phases (``apex_tpu.telemetry.compile_watch``):
the six ``setup_*`` readings of the result line and the twenty rows of
``phase_table`` with the most self seconds before the window opened, by
function and phase, with the record's size and what its listener took.

Writes ``chiprun_out/block_parts_<workload>.json`` beside the printed
tables.
"""

import argparse
import collections
import functools
import json
import os
import pathlib
import sys
import time
from unittest import mock

_T0 = time.perf_counter()
_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))


@functools.lru_cache(maxsize=None)
def part_of(scope: str) -> tuple:
    """``(part, phase)`` of a scope string: ``classify``'s block and
    phase, the block followed by the next component of the scope where
    the block is one that sits inside another's module (``moe``,
    ``indexer``) and the scope names a part under it."""
    from apex_tpu.telemetry import scopes

    block, phase = scopes.classify(scope)
    if block not in scopes._INNER:
        return block, phase
    parts = list(scopes._components(scope))
    # the last component that opens the block: the indexer's flax module
    # and its scopes share the name (``indexer/indexer/scores``)
    for part, sub in reversed(list(zip(parts, parts[1:]))):
        if scopes._BLOCK_OF.get(part) == block:
            return (f"{block}/{sub}" if sub.isidentifier() else block), phase
    return block, phase


def leaves(ops, scope_of):
    """``(op, part, phase)`` of the operations of one device inside which
    no other starts, in time order. XLA names some operations of a
    loop's body by their place in the loop alone (``while/body/gather``,
    the program's scopes lost), so a leaf whose own scope names no block
    answers to the innermost operation around it whose scope does."""
    ops = sorted(ops, key=lambda op: (op.start, -op.end))
    around = []     # (end, part, phase) of the operations still open
    for op, nxt in zip(ops, ops[1:] + [None]):
        while around and around[-1][0] <= op.start:
            around.pop()
        part, phase = part_of(scope_of.get(op.name, ""))
        if part is None and around:
            _, part, phase = around[-1]
        if nxt is not None and nxt.start < op.end:
            if part is not None:
                around.append((op.end, part, phase))
        else:
            yield op, part, phase


def table(trace, scope_of, steps):
    """``{"parts": {part: {phase: ms a step}}, "kernels": {part: {kernel
    name: ms a step}}, "blocks": {block: {"leaves": ms, "union": ms}}}``
    from a reduced trace (``benchmark.xplane.Trace``), ``{instruction
    name: scope}`` and the number of steps in the traced window; device
    time averaged over the trace's devices. ``union`` is over an
    operation's own scope, as ``benchmark/block_time.py`` reads it."""
    from benchmark import scopes, xplane

    parts = collections.defaultdict(lambda: collections.defaultdict(float))
    kernels = collections.defaultdict(lambda: collections.defaultdict(float))
    spans = collections.defaultdict(list)
    per_ms = 1e3 / (steps * max(len(trace.devices), 1))
    lo, hi = trace.window
    for device in trace.devices:
        ops = [op for op in trace.ops if op.device == device
               and min(op.end, hi) > max(op.start, lo)]
        for op in ops:
            block = part_of(scope_of.get(op.name, ""))[0]
            spans[(device, (block or "none").split("/")[0])].append(
                (max(op.start, lo), min(op.end, hi)))
        for op, part, phase in leaves(ops, scope_of):
            ms = (min(op.end, hi) - max(op.start, lo)) * per_ms
            parts[part or "none"][phase] += ms
            if op.opcode == "custom-call":
                kernels[part or "none"][scopes.kernel_name(op)] += ms
    blocks = collections.defaultdict(lambda: {"leaves": 0.0, "union": 0.0})
    for part, phases in parts.items():
        blocks[part.split("/")[0]]["leaves"] += sum(phases.values())
    for (_, block), intervals in spans.items():
        blocks[block]["union"] += xplane.union_seconds(intervals) * per_ms
    return {"parts": {p: dict(v) for p, v in parts.items()},
            "kernels": {p: dict(v) for p, v in kernels.items()},
            "blocks": dict(blocks)}


def render(result) -> str:
    lines = []
    phases = ("forward", "recompute", "backward", "update")
    lines.append(f"{'part':<24}{'ms/step':>9}" + "".join(
        f"{p:>11}" for p in phases))
    rows = sorted(result["parts"].items(),
                  key=lambda kv: -sum(kv[1].values()))
    for part, by_phase in rows:
        lines.append(f"{part:<24}{sum(by_phase.values()):>9.2f}" + "".join(
            f"{by_phase.get(p, 0.0):>11.2f}" for p in phases))
        for name, ms in sorted(result["kernels"].get(part, {}).items(),
                               key=lambda kv: -kv[1]):
            lines.append(f"    {name:<36}{ms:>9.2f}")
    lines.append("")
    lines.append(f"{'block':<24}{'leaves':>9}{'union':>9}")
    for block, v in sorted(result["blocks"].items(),
                           key=lambda kv: -kv[1]["leaves"]):
        lines.append(f"{block:<24}{v['leaves']:>9.2f}{v['union']:>9.2f}")
    total = sum(v["leaves"] for v in result["blocks"].values())
    lines.append(f"{'all leaves':<24}{total:>9.2f}")
    return "\n".join(lines)


def setup_table(result, setup_s) -> dict:
    """Where set-up went: the result line's ``setup_*`` readings and the
    compile path's phases before the window opened, largest first."""
    from apex_tpu.telemetry import compile_watch

    start = compile_watch.process_start_perf()
    opening = _T0 + setup_s       # main's Clock counts from _T0
    longest = sorted(compile_watch.phase_records(opening),
                     key=lambda r: r.start - r.end)[:16]
    return {
        "setup_s": setup_s,
        # the sixteen longest records in time order, seconds after the
        # process's start: the gaps between them are what no record names
        "timeline": [[r.start - (start or 0.0), r.end - (start or 0.0),
                      r.phase, r.fun_name]
                     for r in sorted(longest, key=lambda r: r.start)],
        "readings": {name: m["value"]
                     for name, m in result["metrics"].items()
                     if name.startswith("setup_")},
        "rows": compile_watch.phase_table(until=opening)[:20],
        "records_before_window": len(compile_watch.phase_records(opening)),
        "record_stats": compile_watch.record_stats(),
        "jax_preloaded": compile_watch.jax_preloaded(),
        # the readers count from this script's _T0, as its Clock does
        "clock_start_after_process_s": None if start is None
        else _T0 - start}


def render_setup(setup) -> str:
    lines = [f"set-up {setup['setup_s']:.3f} s: " + ", ".join(
        f"{name} {value:.3f}"
        for name, value in setup["readings"].items())]
    lines.append(f"{'function':<44}{'phase':<9}{'calls':>7}{'self s':>9}"
                 f"{'total s':>9}{'hit/miss':>10}")
    for row in setup["rows"]:
        lines.append(
            f"{row['fun_name'][:43]:<44}{row['phase']:<9}{row['calls']:>7}"
            f"{row['self_s']:>9.3f}{row['total_s']:>9.3f}"
            f"{row['cache_hits']:>6}/{row['cache_misses']}")
    lines.append("the longest records, seconds after the process's start:")
    for begin, end, phase, fun_name in setup["timeline"]:
        lines.append(f"{begin:>10.3f} -{end:>9.3f}  {phase:<9}{fun_name}")
    lines.append(
        f"{setup['records_before_window']} records before the window, "
        f"{setup['record_stats']['kept']} in all, "
        f"{setup['record_stats']['folded']} folded, "
        f"{setup['record_stats']['dropped']} dropped; the listener "
        f"took {setup['record_stats']['listener_seconds']:.4f} s; jax "
        f"imported "
        f"before apex_tpu: {setup['jax_preloaded']}; the run's clock "
        f"started {setup['clock_start_after_process_s']} s after the "
        f"process")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    args = parser.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from apex_tpu.telemetry import scopes as program_scopes
    from benchmark import harness, scopes, train_cell

    cell = harness.load_cell(args.workload, _ROOT)
    kept = {}
    line = harness.result_line

    def keep(cell, outcome, values, window, device, peak, traced):
        kept.update(window=window, trace=(traced or {}).get("trace"),
                    setup_s=values["setup_s"])
        return line(cell, outcome, values, window, device, peak, traced)

    scope_table = program_scopes.scope_table

    def keep_table(compiled):   # what the run's own readers lower for
        kept["scope_of"] = scope_table(compiled)
        return kept["scope_of"]

    with mock.patch.object(harness, "result_line", keep), \
            mock.patch.object(program_scopes, "scope_table", keep_table):
        result, compared = train_cell.run(cell, args.seed, args.seconds,
                                          True, harness.Clock(_T0))
    trace, window = kept["trace"], kept["window"]
    if trace is None or not trace.ops:
        sys.exit("block_parts: the run left no device trace")
    scope_of = kept.get("scope_of") or scope_table(
        scopes.compiled_step(cell))
    steps = scopes.steps_traced({"window": window, "trace": trace})
    out = table(trace, scope_of, steps)
    out.update(workload=args.workload, seed=args.seed, steps=steps,
               window_s=trace.window_s, busy_s=trace.busy_s())
    out["setup"] = setup_table(result, kept["setup_s"])
    print(f"{args.workload} seed {args.seed}: {steps:.2f} steps in a "
          f"{trace.window_s:.3f} s window, busy {trace.busy_s():.3f} s")
    print(render(out))
    print()
    print(render_setup(out["setup"]), flush=True)
    out_dir = _ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"block_parts_{args.workload}.json").write_text(
        json.dumps(out, indent=1, sort_keys=True))
    harness.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
