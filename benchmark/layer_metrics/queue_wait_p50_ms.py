"""Scheduler (``serving/scheduler.py``): median, over the requests due in
the window that got a slot, of due -> start of the ``Scheduler.step``
that admitted them. Host clock."""

import statistics


def read(ctx):
    xs = ctx["window"]["queue_wait_s"]
    return statistics.median(xs) * 1e3 if xs else None
