"""A serving cell: ``ServeEngine`` under ``Scheduler``, driven open loop.

The load generator runs on a thread of its own, off JAX: it sleeps until
each request is due and hands it over with the time it did so. The main
thread submits what has been handed over and calls ``Scheduler.step`` in
a loop; after each step it reads which tokens the step produced, and
stamps them with the step's end, which is when a caller can first read
them. Traffic runs for ``ramp_s`` (the longest request's lifetime) before
the window opens, so that the slots are in steady state; the ramp is part
of set-up.

Once the window has closed: the peak is read, the engine is freed, and
the plain reference is run over a sample of the finished requests, the
longest among them, each prompt with its served tokens; the number
compared is the widest gap by which a served token's logit lies below the
reference's best at its position (greedy tokens).
"""

import functools
import gc
import queue
import statistics
import threading
import time

import numpy as np

from benchmark import compare, families, harness, loadgen, weights

SAMPLE_REQUESTS = 16
REFERENCE_PAD = 128       # reference sequence lengths round up to this
TRACE_SECONDS = 12.0
IDLE_SLEEP_S = 0.001


class LoadGenerator(threading.Thread):
    """Hands each arrival over when it is due, on the wall clock."""

    def __init__(self, arrivals, t0):
        super().__init__(name="loadgen", daemon=True)
        self.arrivals, self.t0 = arrivals, t0
        self.out = queue.SimpleQueue()
        self.stop = threading.Event()

    def run(self):
        for a in self.arrivals:
            wait = self.t0 + a.due_s - time.perf_counter()
            if wait > 0 and self.stop.wait(wait):
                return
            if self.stop.is_set():
                return
            self.out.put((a, time.perf_counter()))


class Record:
    """What the benchmark saw of one request."""

    __slots__ = ("arrival", "due", "sent", "admitted", "token_times",
                 "tokens", "finish_reason", "rejected")

    def __init__(self, arrival, due, sent):
        self.arrival, self.due, self.sent = arrival, due, sent
        self.admitted = None
        self.token_times = []
        self.tokens = None
        self.finish_reason = None
        self.rejected = False


class Driver:
    """The engine, its scheduler, and the loop that drives them."""

    def __init__(self, cell, seed):
        from apex_tpu.serving import ServeConfig, ServeEngine
        from apex_tpu.serving.scheduler import Request

        self.Request = Request
        arch, mix = cell.arch, cell.mix
        eng = mix["engine"]
        model = families.of(arch).build_model(arch, mix, decode=True)
        # weights from the seed in one jitted call, then laid out and
        # cast to the type they are served in in a second
        params = self.served_params(arch, seed)
        self.engine = ServeEngine(model, params, ServeConfig(
            batch_buckets=tuple(eng["batch_buckets"]),
            prefill_buckets=tuple(eng["prefill_buckets"]),
            num_slots=eng["num_slots"], cache_mode=eng["cache_mode"],
            temperature=0.0))
        self._span_calls()
        self.num_slots = eng["num_slots"]
        self.reset()

    def reset(self):
        """A fresh scheduler over the same engine, and nothing seen."""
        from apex_tpu.serving.scheduler import Scheduler

        self.scheduler = Scheduler(self.engine)
        self.records = {}
        self.steps = []       # (start, end, active, decoded, prefilled)
        self._seen = {}
        self._done = 0
        self.call_s = {"engine.prefill": [], "engine.decode": []}

    @staticmethod
    def served_params(arch, seed):
        import jax
        import jax.numpy as jnp

        from apex_tpu import amp

        return jax.jit(lambda canon: amp.frontend.cast_model(
            families.of(arch).to_program(canon, arch), jnp.bfloat16,
            keep_batchnorm_fp32=True))(weights.make_on_device(arch, seed))

    def _span_calls(self):
        """Spans around the engine's two entry points, from outside."""
        for name in ("prefill", "decode"):
            inner = getattr(self.engine, name)
            span = f"engine.{name}"

            def call(*a, _inner=inner, _span=span, **kw):
                t0 = time.perf_counter()
                with harness.span(_span):
                    out = _inner(*a, **kw)
                self.call_s[_span].append(time.perf_counter() - t0)
                return out

            setattr(self.engine, name, call)

    def submit(self, arrival, due, sent):
        rec = Record(arrival, due, sent)
        self.records[arrival.rid] = rec
        ok = self.scheduler.submit(self.Request(
            rid=arrival.rid, prompt=arrival.prompt,
            max_new_tokens=arrival.max_new_tokens))
        rec.rejected = not ok

    def step(self):
        sched = self.scheduler
        decoded0, prefilled0 = sched.decode_steps, sched.prefill_calls
        t0 = time.perf_counter()
        with harness.span("scheduler.step"):
            sched.step()
        t1 = time.perf_counter()
        for st in sched.active.values():
            self._note(st.req.rid, len(st.tokens), t0, t1)
        for done in sched.completed[self._done:]:
            rec = self.records[done.rid]
            self._note(done.rid, len(done.tokens), t0, t1)
            rec.tokens = np.asarray(done.tokens, np.int64)
            rec.finish_reason = done.finish_reason
        self._done = len(sched.completed)
        self.steps.append((t0, t1, len(sched.active),
                           sched.decode_steps - decoded0,
                           sched.prefill_calls - prefilled0))
        return t1

    def _note(self, rid, n_tokens, step_start, step_end):
        rec = self.records[rid]
        seen = self._seen.get(rid, 0)
        if n_tokens > seen:
            if seen == 0:
                rec.admitted = step_start
            rec.token_times.extend([step_end] * (n_tokens - seen))
            self._seen[rid] = n_tokens

    def busy(self) -> bool:
        return bool(self.scheduler.active or self.scheduler.pending)

    def free(self):
        import jax

        self.engine = self.scheduler = None
        jax.clear_caches()
        gc.collect()


def drive(driver, gen, until):
    """Submit and step until the wall clock passes ``until``."""
    now = time.perf_counter()
    while now < until:
        while True:
            try:
                arrival, sent = gen.out.get_nowait()
            except queue.Empty:
                break
            driver.submit(arrival, gen.t0 + arrival.due_s, sent)
        if driver.busy():
            now = driver.step()
        else:
            time.sleep(IDLE_SLEEP_S)
            now = time.perf_counter()
    return now


def window_stats(driver, t_open, t_close, ok_statuses):
    """Everything the metrics read, from the records and the steps."""
    recs = list(driver.records.values())
    due_in = [r for r in recs if t_open <= r.due < t_close]
    tokens_in = sum(1 for r in recs for t in r.token_times
                    if t_open < t <= t_close)
    gaps = [b - a for r in recs
            for a, b in zip(r.token_times, r.token_times[1:])
            if t_open < b <= t_close]
    ttft = [(r.token_times[0] if r.token_times
             and r.token_times[0] <= t_close else t_close) - r.due
            for r in due_in]
    failed = sum(1 for r in due_in if r.rejected or (
        r.finish_reason is not None and r.finish_reason not in ok_statuses))
    steps = [s for s in driver.steps if t_open < s[1] <= t_close]
    contexts = []          # positions each processed token attended over
    for r in recs:
        plen = len(r.arrival.prompt)
        if r.token_times and t_open < r.token_times[0] <= t_close:
            contexts.extend(range(1, plen + 1))
        contexts.extend(plen + j for j, t in enumerate(r.token_times)
                        if j > 0 and t_open < t <= t_close)
    return {
        "elapsed_s": t_close - t_open, "attempted": len(due_in),
        "failed": failed, "tokens": tokens_in, "gaps_s": gaps,
        "ttft_s": ttft,
        "lateness_s": [r.sent - r.due for r in due_in],
        "queue_wait_s": [r.admitted - r.due for r in due_in
                         if r.admitted is not None],
        "occupancy": [s[2] / driver.num_slots for s in steps],
        "decode_step_s": [s[1] - s[0] for s in steps
                          if s[3] > 0 and s[4] == 0],
        "step_s": [s[1] - s[0] for s in steps],
        "contexts": contexts,
        "finished": [r for r in recs if r.tokens is not None
                     and r.finish_reason in ok_statuses
                     and t_open < r.token_times[-1] <= t_close],
    }


def sample(finished, seed, n=SAMPLE_REQUESTS):
    """A seeded sample of finished requests with the longest in it."""
    if not finished:
        return []
    finished = sorted(finished, key=lambda r: r.arrival.rid)
    longest = max(finished, key=lambda r: len(r.arrival.prompt)
                  + len(r.tokens))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4])
    picks = rng.permutation(len(finished))[:n]
    out = [finished[i] for i in picks]
    if longest not in out:
        out[-1] = longest
    return out


def reference_logits(arch, params, fam, rec, quant=None):
    """The reference's logits at the positions that produced ``rec``'s
    served tokens: one full forward over prompt + served tokens."""
    import jax
    import jax.numpy as jnp

    plen, n = len(rec.arrival.prompt), len(rec.tokens)
    seq = np.concatenate([rec.arrival.prompt, rec.tokens[:-1]])
    padded = -(-len(seq) // REFERENCE_PAD) * REFERENCE_PAD
    ids = np.zeros((1, padded), np.int32)
    ids[0, :len(seq)] = seq
    fn = _logits_fn(fam.__name__.rsplit(".", 1)[-1],
                    tuple(sorted(arch.items())), quant)
    logits = fn(params, jnp.asarray(ids))
    return np.asarray(jax.device_get(logits[0, plen - 1:plen - 1 + n]))


@functools.lru_cache(maxsize=None)
def _logits_fn(family_name, arch_items, quant):
    """One jitted forward per family, architecture and arithmetic."""
    import jax

    from benchmark.reference import family as load_family

    fam, arch = load_family(family_name), dict(arch_items)
    kw = {} if quant is None else {"quant": quant}
    return jax.jit(lambda p, ids: fam.logits(p, arch, ids, **kw))


def served_gap(cell, seed, picked, quant=None):
    """-> (widest gap, tokens compared). With ``quant`` the control's: at
    the same positions, the gap of the token the lower precision puts
    first."""
    from benchmark.reference import family as load_family

    fam = load_family(cell.arch["family"])
    params = weights.make_on_device(cell.arch, seed)
    worst, count = 0.0, 0
    for rec in picked:
        ref = reference_logits(cell.arch, params, fam, rec)
        if quant is None:
            tokens = rec.tokens
        else:
            tokens = reference_logits(cell.arch, params, fam, rec,
                                      quant).argmax(axis=-1)
        worst = max(worst, compare.serve_gap(ref, np.asarray(tokens)))
        count += len(tokens)
    return worst, count


def run(cell, seed, seconds, trace, clock):
    from apex_tpu.serving import robust

    device = harness.require_chips(cell.chips)
    harness.enable_cache()
    mix, arch = cell.mix, cell.arch
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    ramp = float(mix["ramp_s"])
    arrivals = loadgen.schedule(mix, arch["vocab_real"], seed,
                                ramp + seconds + 1.0, lead_s=ramp,
                                cycle_s=seconds)
    driver = Driver(cell, seed)

    gen = LoadGenerator(arrivals, time.perf_counter())
    gen.start()
    traced = {}
    try:
        drive(driver, gen, gen.t0 + ramp)
        with harness.measured_window(cell, trace, traced):
            setup_s = clock.since_start()
            t_open = time.perf_counter()
            t_close = drive(driver, gen, t_open + seconds)
    finally:
        gen.stop.set()
        gen.join(timeout=10.0)
    peak = harness.memory_peak_bytes(cell.chips)
    w = window_stats(driver, t_open, t_close, robust.OK_STATUSES)
    w["call_s"] = driver.call_s
    picked = sample(w["finished"], seed)
    driver.free()

    numbers = {"compiles_in_window": float(traced["compiles"]),
               "failed_requests": float(w["failed"])}
    t_ref = time.perf_counter()
    if picked:
        gap, compared_tokens = served_gap(cell, seed, picked)
        numbers["served_logit_gap"] = gap
    else:
        compared_tokens = 0
    limits = dict(cell.limits)
    limits.update(compiles_in_window=0.0, failed_requests=0.0)
    correct, compared = harness.compare(numbers, limits)

    values = {"setup_s": setup_s,
              "serve_tokens_per_s": w["tokens"] / w["elapsed_s"]}
    if w["gaps_s"]:
        values["itl_p95_ms"] = harness.percentile(w["gaps_s"], 95) * 1e3
    if w["ttft_s"]:
        values["ttft_p95_ms"] = harness.percentile(w["ttft_s"], 95) * 1e3
    result = harness.result_line(
        cell, {"correct": bool(correct), "attempted": w["attempted"],
               "failed": w["failed"]},
        values, w, device, peak, traced if trace else None)
    result["notes"] = {
        "window_s": w["elapsed_s"], "tokens": w["tokens"],
        "finished": len(w["finished"]), "sampled": len(picked),
        "tokens_compared": compared_tokens,
        "reference_s": time.perf_counter() - t_ref,
        "steps": len(w["step_s"]),
        "step_ms_p50": statistics.median(w["step_s"]) * 1e3
        if w["step_s"] else None,
        "occupancy_mean": statistics.fmean(w["occupancy"])
        if w["occupancy"] else None,
        "pending_at_close": sum(1 for r in driver.records.values()
                                if not r.token_times and not r.rejected),
        "ttft_p50_ms": harness.percentile(w["ttft_s"], 50) * 1e3
        if w["ttft_s"] else None,
    }
    return result, compared
