#!/usr/bin/env python3
"""Hand-run on the chip, beside ``benchmark/calibrate.py``: the upper
readings of a block-diffusion training cell, read against its committed
limits. ``calibrate.py --control-seeds 0`` gives the program's readings;
its fp8 control and half-batch fault cut a batch by rows, and this cell's
batch is one row, so they are taken here, with the planted fault of the
mechanism's own. In one process, on ``--seeds``:

- ``fault_causal``: the program as the cell runs it (same rows, positions,
  head, loss and optimizer) with attention under ``causal`` over the
  ``2L`` rows in the block-diffusion rule's place;
- ``fault_half_batch``: the reference over the first half of each
  sequence's data tokens alone (half the batch's tokens left out, the mean
  taken over those), in the reference's place;
- ``control_fp8``: the reference in fp8 in the program's place, one
  ``Reference`` on the device at a time as ``calibrate_control.py`` has it.

Same records and file format as ``calibrate.py``; each holds ``correct``
as ``harness.compare`` decides it from the committed limits: false for all
three.

    python3 tools/blockdiff_faults.py --workload sdar_30b_a3b_blockdiff_train_8k --seeds 3000,10919 --out chiprun_out/faults.jsonl
"""

import argparse
import gc
import itertools
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))


def causal_program(cell):
    """The cell's ``TrainProgram`` with ``causal`` planted in the rule's
    place (the configuration is frozen: the fault goes around it)."""
    from apex_tpu.transformer.enums import AttnMaskType
    from benchmark import program

    prog = program.TrainProgram(cell.arch, cell.mix, cell.mix.get("mesh"))
    object.__setattr__(prog.model.config, "attn_mask_type",
                       AttnMaskType.causal)
    return prog


def half_the_tokens(batch):
    return {k: v[:, :v.shape[1] // 2] for k, v in batch.items()}


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--out", default="")
    args = parser.parse_args()

    import jax

    from benchmark import (calibrate, compare, families, harness, train_cell,
                           weights)
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

    prog = causal_program(cell)
    planted = {}
    for seed in seeds:
        stepper = train_cell.Stepper(cell, seed, prog)
        planted[seed] = train_cell.checked_steps(stepper, seed)
        stepper.free(keep_program=True)
    prog = None
    jax.clear_caches()
    gc.collect()

    ref = ref_train.Reference(cell.arch, mix["optimizer"], mix["hp"])
    wants = {}
    for seed in seeds:
        wants[seed] = train_cell.reference_readings(cell, seed, ref)
        record("fault_causal", planted[seed], wants[seed], seed)
        feed = families.batches(cell.arch, train_cell.global_mix(mix), seed)
        halved = [half_the_tokens(b) for b in itertools.islice(
            feed, train_cell.CHECKED_STEPS)]
        half = ref.run(weights.make_on_device(cell.arch, seed), halved,
                       block_rows=mix.get("reference_block_rows", 2))
        record("fault_half_batch", half, wants[seed], seed)
    ref = None
    jax.clear_caches()
    gc.collect()

    control = ref_train.Reference(cell.arch, mix["optimizer"], mix["hp"],
                                  quant=lowp.fp8)
    for seed in seeds:
        t0 = time.perf_counter()
        got = train_cell.reference_readings(cell, seed, control)
        record("control_fp8", got, wants[seed], seed,
               control_s=time.perf_counter() - t0)


if __name__ == "__main__":
    main()
