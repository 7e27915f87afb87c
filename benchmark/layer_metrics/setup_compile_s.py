"""Compile path: seconds of set-up inside jax's ``backend_compile``: XLA's
compile where the persistent cache misses, the cache's read and the
executable's load where it hits. The union of the program's ``compile``
records before the window's opening, on the host's clock
(``benchmark/setup_phases.py``)."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.reading(ctx, "compile_s")
