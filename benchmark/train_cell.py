"""A training cell: the program's compiled step, driven from the seed.

Set-up builds one object, the jitted step with its state, drives it
through its first ``CHECKED_STEPS`` steps (whose readings the reference
is held against once the window has closed) and through the warm-up, and
hands that same object to the window. The window runs whole steps only:
from the first dispatch after a ``block_until_ready`` to the host fetch
of the last step's loss, one step kept in flight so that the device never
waits for the host.
"""

import gc
import statistics
import time

from benchmark import compare, families, harness, optimizers, weights

CHECKED_STEPS = 3
WARMUP_STEPS, WARMUP_SECONDS = 5, 3.0
TRACE_SECONDS = 10.0


def global_mix(mix: dict) -> dict:
    """``batch`` is per chip; under a mesh the feeder makes the global
    batch, which the step splits over the data axis."""
    if not mix.get("mesh"):
        return mix
    (_, n), = mix["mesh"].items()
    return dict(mix, batch=mix["batch"] * n)


class Stepper:
    """The step with its state: the one object that set-up checks and the
    window times."""

    def __init__(self, cell, seed, prog=None):
        import jax

        from benchmark import program

        self.jax = jax
        self.cell = cell
        self.prog = prog or program.TrainProgram(cell.arch, cell.mix,
                                                 cell.mix.get("mesh"))
        self.step = self.prog.step
        self.batch_sharding = self.prog.batch_sharding
        self.params, self.opt_state = self.prog.init_state(seed)
        self.feed = families.batches(cell.arch, global_mix(cell.mix), seed)
        self.input_wait_s = 0.0
        self.steps = 0

    def next_batch(self):
        t0 = time.perf_counter()
        with harness.span("input.next"):
            batch = next(self.feed)
            batch = self.jax.device_put(batch, self.batch_sharding)
        self.input_wait_s += time.perf_counter() - t0
        return batch

    def dispatch(self):
        """Feed and dispatch one step; returns its (not yet fetched) loss."""
        batch = self.next_batch()
        with harness.span("step.dispatch"):
            self.params, self.opt_state, loss = self.step(
                self.params, self.opt_state, batch)
        self.steps += 1
        return loss

    @staticmethod
    def fetch(loss) -> float:
        with harness.span("loss.fetch"):
            return float(loss)

    def free(self, keep_program=False):
        self.params = self.opt_state = None
        if not keep_program:
            self.step = self.prog = None
            self.jax.clear_caches()
        gc.collect()


def checked_steps(stepper: Stepper, seed: int) -> dict:
    """The program's readings over its first steps, through the window's
    own call and feed."""
    import jax

    from benchmark import program
    from benchmark.reference.train import tensor_norms

    arch, mix = stepper.cell.arch, stepper.cell.mix
    family, opt = families.of(arch), optimizers.of(mix["optimizer"])
    first_gradient_norms = jax.jit(lambda opt_state: tensor_norms(
        family.from_program(
            opt.program_first_gradient(opt_state, mix["hp"]), arch),
        arch["heads"]))
    losses, grad_norms = [], None
    for i in range(CHECKED_STEPS):
        losses.append(stepper.fetch(stepper.dispatch()))
        if i == 0:
            grad_norms = jax.device_get(
                first_gradient_norms(stepper.opt_state))

    def change(masters, start):
        now = family.from_program(masters, arch)
        return tensor_norms({k: now[k] - start[k] for k in start},
                            arch["heads"])

    change_norms = jax.device_get(jax.jit(change)(
        program.masters(stepper.opt_state),
        weights.make_on_device(arch, seed)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}


def reference_readings(cell, seed: int, ref=None, **kw) -> dict:
    """The plain reference over the same first steps (run once the
    program's state is freed). ``ref`` reuses a built ``Reference``."""
    import itertools

    from benchmark.reference import train as ref_train

    ref = ref or ref_train.Reference(cell.arch, cell.mix["optimizer"],
                                     cell.mix["hp"])
    params = weights.make_on_device(cell.arch, seed)
    feed = families.batches(cell.arch, global_mix(cell.mix), seed)
    batches = list(itertools.islice(feed, CHECKED_STEPS))
    kw.setdefault("block_rows", cell.mix.get("reference_block_rows", 2))
    return ref.run(params, batches, **kw)


def window(stepper: Stepper, seconds: float) -> dict:
    """Whole steps for ``seconds``; -> steps, elapsed, per-step seconds."""
    jax = stepper.jax
    jax.block_until_ready((stepper.params, stepper.opt_state))
    stepper.input_wait_s, steps0 = 0.0, stepper.steps
    done_at = []
    t0 = time.perf_counter()
    in_flight = stepper.dispatch()
    while time.perf_counter() - t0 < seconds:
        nxt = stepper.dispatch()
        last = stepper.fetch(in_flight)
        done_at.append(time.perf_counter())
        in_flight = nxt
    last = stepper.fetch(in_flight)
    t1 = time.perf_counter()
    done_at.append(t1)
    steps = stepper.steps - steps0
    per_step = [b - a for a, b in zip([t0] + done_at[:-1], done_at)]
    return {"steps": steps, "elapsed_s": t1 - t0, "step_s": per_step,
            "input_wait_s": stepper.input_wait_s, "last_loss": last}


def run(cell, seed, seconds, trace, clock):
    """-> (result line without ``compared``, compared)."""
    device = harness.require_chips(cell.chips)
    harness.enable_cache()
    mix = cell.mix
    stepper = Stepper(cell, seed)
    got = checked_steps(stepper, seed)
    t_warm = time.perf_counter()
    loss = None
    for _ in range(WARMUP_STEPS):
        loss = stepper.dispatch()
    while time.perf_counter() - t_warm < WARMUP_SECONDS:
        loss = stepper.dispatch()
    stepper.fetch(loss)

    traced = {}
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    with harness.measured_window(cell, trace, traced):
        setup_s = clock.since_start()
        w = window(stepper, seconds)
    peak = harness.memory_peak_bytes(cell.chips)
    stepper.free()

    t_ref = time.perf_counter()
    want = reference_readings(cell, seed)
    limits = dict(cell.limits, compiles_in_window=0.0)
    numbers, notes = compare.train_numbers(got, want, limits)
    notes["reference_s"] = time.perf_counter() - t_ref
    numbers["compiles_in_window"] = float(traced["compiles"])
    correct, compared = harness.compare(numbers, limits)
    finite = w["last_loss"] == w["last_loss"]

    tokens_per_step = global_mix(mix)["batch"] * mix["seq"]
    rate = tokens_per_step * w["steps"] / w["elapsed_s"] / cell.chips
    values = {"train_tokens_per_s_per_chip": rate, "setup_s": setup_s}
    result = harness.result_line(
        cell, {"correct": bool(correct and finite), "attempted": w["steps"],
               "failed": 0 if finite else w["steps"]},
        values, w, device, peak, traced if trace else None)
    slowest = sorted(enumerate(w["step_s"]), key=lambda kv: -kv[1])[:3]
    result["notes"] = dict(notes, steps=w["steps"],
                           elapsed_s=w["elapsed_s"],
                           step_ms_p50=statistics.median(w["step_s"]) * 1e3,
                           slowest_steps_ms={i: t * 1e3 for i, t in slowest})
    return result, compared
