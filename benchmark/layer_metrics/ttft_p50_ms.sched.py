"""Scheduler: median time to first token over the requests due in the
window, a steadier statistic beside the end-to-end 95th percentile."""

import statistics


def read(ctx):
    xs = ctx["window"]["ttft_s"]
    return statistics.median(xs) * 1e3 if xs else None
