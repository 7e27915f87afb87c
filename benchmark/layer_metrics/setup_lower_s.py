"""Compile path: seconds of set-up in which a jaxpr was being lowered to
an MLIR module, Mosaic's lowering of each Pallas kernel inside it. The
union of the program's ``lower`` records before the window's opening, on
the host's clock (``benchmark/setup_phases.py``)."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.reading(ctx, "lower_s")
