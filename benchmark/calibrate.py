#!/usr/bin/env python3
"""Hand-run on the chip: the readings that a cell's limits are set from.

Not part of a benchmark run, and it runs nowhere but on the chip. In one
process, at the cell's own size:

- the program's numbers against the reference on ``--seeds`` seeds (the
  lower readings);
- the control's (the reference in fp8 put in the program's place) and,
  for a training cell, the half-batch fault's, on ``--control-seeds``
  seeds (the upper readings);
- for a serving cell, optionally first a sweep over ``--sweep`` rates, to
  find the knee.

Every record names the device it was read on, and holds ``correct`` as
``harness.compare`` decides it from the cell's committed limits: true for
the program, false for the control and the fault.

    python3 benchmark/calibrate.py --workload gpt2_345m_train --seeds 12 --out chiprun_out/cal_gpt2.json
"""

import argparse
import json
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))


class Record:
    """Prints each record and appends it to ``out``; stamps the device,
    and holds a record's numbers to the cell's committed limits."""

    def __init__(self, cell, device, out):
        self.cell, self.device, self.out = cell, device, out

    def __call__(self, what, **record):
        from benchmark import harness

        record = dict(what=what, device=self.device, **record)
        if "numbers" in record:
            limits = self.cell.limits
            held = {k: v for k, v in record["numbers"].items()
                    if k in limits}
            record["correct"], record["compared"] = harness.compare(
                held, limits)
        print(json.dumps({k: v for k, v in record.items()
                          if k != "vectors"}), flush=True)
        if self.out:
            with open(self.out, "a") as f:
                f.write(json.dumps(record) + "\n")


def _vectors(got, want):
    """The per-tensor norms behind the numbers, so that a number can be
    worked out again from a calibration's record without the chip."""
    from benchmark.reference.train import flatten_norms

    names, g_ref = flatten_norms(want["grad_norms"])
    return {"names": names,
            "grad_ref": [float("%.6g" % x) for x in g_ref],
            "grad_got": [float("%.6g" % x) for x in
                         flatten_norms(got["grad_norms"])[1]],
            "change_ref": [float("%.6g" % x) for x in
                           flatten_norms(want["change_norms"])[1]],
            "change_got": [float("%.6g" % x) for x in
                           flatten_norms(got["change_norms"])[1]]}


def calibrate_train(cell, seeds, control_seeds, say):
    from benchmark import compare, program, train_cell
    from benchmark.reference import lowp
    from benchmark.reference import train as ref_train

    mix = cell.mix
    prog = program.TrainProgram(cell.arch, mix, mix.get("mesh"))
    ref = ref_train.Reference(cell.arch, mix["optimizer"], mix["hp"])
    for seed in seeds:
        t0 = time.perf_counter()
        stepper = train_cell.Stepper(cell, seed, prog)
        got = train_cell.checked_steps(stepper, seed)
        stepper.free(keep_program=True)
        t1 = time.perf_counter()
        want = train_cell.reference_readings(cell, seed, ref)
        numbers, notes = compare.train_numbers(got, want)
        say("program", seed=seed, numbers=numbers, notes=notes,
            program_s=t1 - t0, reference_s=time.perf_counter() - t1,
            vectors=_vectors(got, want))
    prog = None
    control = ref_train.Reference(cell.arch, mix["optimizer"], mix["hp"],
                                  quant=lowp.fp8)
    for seed in control_seeds:
        want = train_cell.reference_readings(cell, seed, ref)
        t0 = time.perf_counter()
        got = train_cell.reference_readings(cell, seed, control)
        numbers, notes = compare.train_numbers(got, want)
        say("control_fp8", seed=seed, numbers=numbers, notes=notes,
            control_s=time.perf_counter() - t0, vectors=_vectors(got, want))
        got = train_cell.reference_readings(cell, seed, ref,
                                            keep_rows=mix["batch"] // 2)
        numbers, notes = compare.train_numbers(got, want)
        say("fault_half_batch", seed=seed, numbers=numbers, notes=notes,
            vectors=_vectors(got, want))


def _serve_phase(driver, cell, mix, seed, ramp, seconds):
    """One ramp + window at ``mix``'s rate on a fresh scheduler."""
    from apex_tpu.serving import robust
    from benchmark import loadgen, serve_cell

    driver.reset()
    arrivals = loadgen.schedule(mix, cell.arch["vocab_real"], seed,
                                ramp + seconds + 1.0, lead_s=ramp,
                                cycle_s=seconds)
    gen = serve_cell.LoadGenerator(arrivals, time.perf_counter())
    gen.start()
    try:
        serve_cell.drive(driver, gen, gen.t0 + ramp)
        t_open = time.perf_counter()
        t_close = serve_cell.drive(driver, gen, t_open + seconds)
    finally:
        gen.stop.set()
        gen.join(timeout=10.0)
    w = serve_cell.window_stats(driver, t_open, t_close, robust.OK_STATUSES)
    pending = sum(1 for r in driver.records.values()
                  if not r.token_times and not r.rejected)
    # finish what is in flight, so that the next phase starts empty
    t_drain = time.perf_counter()
    while driver.busy() and time.perf_counter() - t_drain < 90:
        driver.step()
    return w, pending


def calibrate_serve(cell, seeds, control_seeds, sweep, say, seconds):
    from benchmark import harness, serve_cell
    from benchmark.reference import lowp

    mix = dict(cell.mix)
    ramp = float(mix["ramp_s"])
    t0 = time.perf_counter()
    driver = serve_cell.Driver(cell, seeds[0])
    say("engine_start", seconds=time.perf_counter() - t0)
    sustained = []
    for rate in sweep:
        trial = dict(mix, rate_rps=rate)
        w, pending = _serve_phase(driver, cell, trial, 7, ramp, seconds)
        late = sorted(w["ttft_s"][len(w["ttft_s"]) * 2 // 3:])
        rec = {"rate_rps": rate,
               "tokens_per_s": w["tokens"] / w["elapsed_s"],
               "attempted": w["attempted"], "pending_at_close": pending,
               "ttft_p50_ms": harness.percentile(w["ttft_s"], 50) * 1e3,
               "ttft_p95_ms": harness.percentile(w["ttft_s"], 95) * 1e3,
               "ttft_p50_last_third_ms": late[len(late) // 2] * 1e3
               if late else None,
               "itl_p50_ms": harness.percentile(w["gaps_s"], 50) * 1e3,
               "itl_p95_ms": harness.percentile(w["gaps_s"], 95) * 1e3,
               "occupancy": sum(w["occupancy"]) / len(w["occupancy"]),
               "step_ms_p50": harness.percentile(w["step_s"], 50) * 1e3}
        say("sweep", **rec)
        if pending <= 3 and rec["ttft_p50_last_third_ms"] < 2000:
            sustained.append(rate)
    if sweep:
        knee = max(sustained) if sustained else min(sweep)
        mix["rate_rps"] = round(0.8 * knee, 2)
        say("knee", knee_rps=knee, cell_rate_rps=mix["rate_rps"])

    for seed in seeds:
        # same shapes, new values: the compiled ladder takes the weights
        # as an argument (calibration only; a run builds its own engine)
        driver.engine._params = serve_cell.Driver.served_params(cell.arch,
                                                                 seed)
        w, pending = _serve_phase(driver, cell, mix, seed, ramp, seconds)
        picked = serve_cell.sample(w["finished"], seed)
        gap, n = serve_cell.served_gap(cell, seed, picked)
        rec = {"seed": seed, "numbers": {"served_logit_gap": gap},
               "tokens_compared": n,
               "finished": len(w["finished"]), "failed": w["failed"],
               "tokens_per_s": w["tokens"] / w["elapsed_s"],
               "itl_p95_ms": harness.percentile(w["gaps_s"], 95) * 1e3,
               "ttft_p95_ms": harness.percentile(w["ttft_s"], 95) * 1e3,
               "pending_at_close": pending}
        if seed in control_seeds:
            cgap, _ = serve_cell.served_gap(cell, seed, picked, lowp.fp8)
            say("control_fp8", seed=seed,
                numbers={"served_logit_gap": cgap})
        say("program", **rec)


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=3000)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--sweep", default="")
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--out", default="")
    args = parser.parse_args()

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    say = Record(cell, harness.require_chips(cell.chips), args.out)
    harness.enable_cache()
    # large seeds among them: the driver's are
    seeds = [args.first_seed + 7919 * i + (2 ** 31 if i % 4 == 3 else 0)
             for i in range(args.seeds)]
    control = seeds[:args.control_seeds]
    if cell.mix["kind"] == "train":
        calibrate_train(cell, seeds, control, say)
    else:
        sweep = [float(x) for x in args.sweep.split(",") if x]
        calibrate_serve(cell, seeds, control, sweep, say, args.seconds)


if __name__ == "__main__":
    main()
