"""Set-up: ``setup_s`` less the union of the program's ``import``,
``trace``, ``lower`` and ``compile`` records: what no record names. The
import of jax, the TPU client's start, the jits' first executions, the
checked steps and the warm-up, and Python between them. With the named
phases' union it adds up to the run's ``setup_s``
(``benchmark/setup_phases.py``)."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.reading(ctx, "unattributed_s")
