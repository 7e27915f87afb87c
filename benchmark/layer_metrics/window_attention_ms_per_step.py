"""The model (``models/transformer_lm.py`` ``ParallelAttention``): device
milliseconds a step in the operations traced under the scope
``attention/window``, which a layer whose attention runs under a sliding
window opens around all of it, from the QKV projection through the
kernels and the per-head output gate to the output projection (block
``attention/window`` and its parts in the program's table, inside block
``attention``), forward, recomputed and backward. The union of their
intervals (``benchmark/scope_union.py``), from the device trace joined to
the compiled step's scopes (``benchmark/scopes.py``); reads nothing on a
program whose scope table has no such block."""

from benchmark import scope_union


def read(ctx):
    return scope_union.ms_per_step(
        ctx, lambda block, phase: (block or "").startswith(
            "attention/window"))
