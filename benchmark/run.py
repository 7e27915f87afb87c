#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object (see
README.md). Exits non-zero, with no result, without the chips the cell
asks for. One process: nothing here starts another that touches JAX.
"""

import argparse
import importlib
import os
import pathlib
import sys
import time

_T0 = time.perf_counter()
_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))


def main(argv=None, *, root=_ROOT) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark import harness

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    clock = harness.Clock(_T0)
    try:
        cell = harness.load_cell(args.workload, root)
        kind = importlib.import_module(f"benchmark.{cell.mix['kind']}_cell")
        result, compared = kind.run(cell, args.seed, args.seconds,
                                    bool(args.trace), clock)
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    harness.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
