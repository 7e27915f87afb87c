"""Scheduler: mean over the window's steps of active slots over
``num_slots``, read after each step. Program counter
(``len(Scheduler.active)``)."""

import statistics


def read(ctx):
    xs = ctx["window"]["occupancy"]
    return 100.0 * statistics.fmean(xs) if xs else None
