"""Engine: median host-clock time of ``ServeEngine.prefill`` calls (a
span the benchmark puts around the entry point from outside)."""

import statistics


def read(ctx):
    xs = ctx["window"]["call_s"]["engine.prefill"]
    return statistics.median(xs) * 1e3 if xs else None
