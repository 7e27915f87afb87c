"""Train step (``amp`` + ``optimizers.Fused*`` + the jitted step): median
time between the completions of consecutive steps in the window. Host
clock; a steadier statistic beside the end-to-end rate, which is taken
over all steps and all time."""

import statistics


def read(ctx):
    return statistics.median(ctx["window"]["step_s"]) * 1e3
