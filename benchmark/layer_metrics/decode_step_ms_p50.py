"""Engine (``serving/engine.py``): median host-clock time of the
``Scheduler.step`` calls in the window that decoded and admitted nothing
(a step that also prefills is longer by the prefill)."""

import statistics


def read(ctx):
    xs = ctx["window"]["decode_step_s"]
    return statistics.median(xs) * 1e3 if xs else None
