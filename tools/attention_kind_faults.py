#!/usr/bin/env python3
"""Hand-run on the chip, beside ``benchmark/calibrate.py``: the readings of
faults planted in a cell whose layers mix attention kinds
(``TransformerConfig.attention_kinds``), read against its committed
limits. ``calibrate.py`` gives the program's readings, its fp8 control
and its half-batch fault; this gives the faults of the mechanism's own, in
one process, on ``--seeds``:

- ``fault_window_causal``: the program as the cell runs it with every
  kind's window taken away, so that the window layers attend over all
  their causal keys;
- ``fault_yarn_off``: the program with every kind's rope scaling taken
  away (the full layers' half-rotary without YaRN's frequencies and
  attention factor).

Each is the cell's ``TrainProgram`` with its configuration changed before
the step is first traced; the reference is the cell's own. Same records
and file format as ``calibrate.py``.

    python3 tools/attention_kind_faults.py --workload laguna_xs2_train_8k --seeds 3000,10919 --out chiprun_out/faults.jsonl
"""

import argparse
import dataclasses
import gc
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

FAULTS = {
    "fault_window_causal": lambda kind: dataclasses.replace(kind,
                                                            window=None),
    "fault_yarn_off": lambda kind: dataclasses.replace(kind,
                                                       rope_scaling=None),
}


def planted_program(cell, fault):
    """The cell's ``TrainProgram`` with ``fault`` applied to each of its
    attention kinds (the configuration is frozen: the fault goes around
    it)."""
    from benchmark import program

    prog = program.TrainProgram(cell.arch, cell.mix, cell.mix.get("mesh"))
    cfg = prog.model.config
    object.__setattr__(cfg, "attention_kinds",
                       tuple(map(fault, cfg.attention_kinds)))
    return prog


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--out", default="")
    args = parser.parse_args()

    import jax

    from benchmark import calibrate, compare, harness, train_cell
    from benchmark.reference import train as ref_train

    cell = harness.load_cell(args.workload)
    say = calibrate.Record(cell, harness.require_chips(cell.chips), args.out)
    harness.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]

    planted = {}
    for what, fault in FAULTS.items():
        prog = planted_program(cell, fault)
        for seed in seeds:
            stepper = train_cell.Stepper(cell, seed, prog)
            planted[what, seed] = train_cell.checked_steps(stepper, seed)
            stepper.free(keep_program=True)
        prog = None
        jax.clear_caches()
        gc.collect()

    ref = ref_train.Reference(cell.arch, cell.mix["optimizer"],
                              cell.mix["hp"])
    for seed in seeds:
        want = train_cell.reference_readings(cell, seed, ref)
        for what in FAULTS:
            got = planted[what, seed]
            numbers, notes = compare.train_numbers(got, want)
            say(what, seed=seed, numbers=numbers, notes=notes,
                vectors=calibrate._vectors(got, want))


if __name__ == "__main__":
    main()
