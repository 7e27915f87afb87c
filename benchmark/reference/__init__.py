"""Plain references: float32 ``jax.numpy`` at ``highest`` matmul precision,
no kernels, no cache, no batching tricks. They import nothing of the
program and take nothing it has made; weights come from
``benchmark/weights.py`` and the batches from ``benchmark/loadgen.py``.
One module per model family, found by the ``family`` of a configuration;
the optimizers' published updates are in ``benchmark/optimizers/``.
"""

import importlib


def family(name: str):
    """The reference module of one model family (``gpt2``, ``bert``)."""
    return importlib.import_module(f"benchmark.reference.{name}")
