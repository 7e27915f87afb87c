"""Package import: seconds of set-up in the program's own import
(``import apex_tpu``, every subpackage it imports eagerly; jax's import is
inside it only where ``apex_tpu`` imported jax first, which the benchmark
does not: ``harness.require_chips`` has by then). The ``import`` record
the package takes at the top and bottom of ``apex_tpu/__init__.py``, on
the host's clock (``benchmark/setup_phases.py``)."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.reading(ctx, "import_s")
