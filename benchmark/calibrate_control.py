#!/usr/bin/env python3
"""Hand-run on the chip, beside ``calibrate.py``: a training cell's upper
readings alone (the half-batch fault and the fp8 control on ``--seeds``
seeds), with one ``Reference`` on the device at a time.

``calibrate.py`` keeps the program's executables, the reference and the
control in one process; where the control's gradient block needs most of
what five float32 copies of the parameters leave, it does not load beside
them. Same functions, same records, the same file format. Where it does
not load even alone (the ``nemotron_h`` family at 2 x 8192: it has to
reserve 2.94 GB where 2.83 are free), ``--control-rows`` takes the
control's reading on the first rows of each batch alone, on both of its
sides (the reference in float32 and in fp8 over the same rows): the
widths and the length are the cell's, the record says how many rows.

    python3 benchmark/calibrate_control.py --workload nemotron3_nano_30b_a3b_train_8k --seeds 3000,10919 --out chiprun_out/cal_control.jsonl
"""

import argparse
import gc
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-rows", type=int, default=None)
    parser.add_argument("--out", default="")
    args = parser.parse_args()

    import jax

    from benchmark import calibrate, compare, harness, train_cell
    from benchmark.reference import lowp
    from benchmark.reference import train as ref_train

    cell = harness.load_cell(args.workload)
    say = calibrate.Record(cell, harness.require_chips(cell.chips), args.out)
    harness.enable_cache()
    mix = cell.mix
    seeds = [int(s) for s in args.seeds.split(",")]

    def record(what, got, want, seed, **kw):
        numbers, notes = compare.train_numbers(got, want)
        say(what, seed=seed, numbers=numbers, notes=notes,
            vectors=calibrate._vectors(got, want), **kw)

    rows = args.control_rows
    ref = ref_train.Reference(cell.arch, mix["optimizer"], mix["hp"])
    wants = {}
    for seed in seeds:
        want = train_cell.reference_readings(cell, seed, ref)
        half = train_cell.reference_readings(cell, seed, ref,
                                             keep_rows=mix["batch"] // 2)
        record("fault_half_batch", half, want, seed)
        # what the control is held against: the same rows in float32
        wants[seed] = want if rows is None else (
            half if rows == mix["batch"] // 2
            else train_cell.reference_readings(cell, seed, ref,
                                               keep_rows=rows))
    ref = None
    jax.clear_caches()
    gc.collect()
    control = ref_train.Reference(cell.arch, mix["optimizer"], mix["hp"],
                                  quant=lowp.fp8)
    for seed in seeds:
        t0 = time.perf_counter()
        got = train_cell.reference_readings(cell, seed, control,
                                            keep_rows=rows)
        record("control_fp8", got, wants[seed], seed, rows=rows,
               control_s=time.perf_counter() - t0)


if __name__ == "__main__":
    main()
