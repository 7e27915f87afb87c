"""Compile path: seconds of set-up in which Python was being traced to a
jaxpr, every jitted function of set-up (the step, the weights' and the
optimizer's jits, the helpers of ``checked_steps``). The union of the
program's ``trace`` records before the window's opening, on the host's
clock (``benchmark/setup_phases.py``): an inner jit's trace lies inside
its caller's and counts once."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.reading(ctx, "trace_s")
