"""Optimizers (``optimizers/fused_*.py``): device milliseconds a step in
the operations traced under ``AmpOptimizer.step``'s scope ``optimizer``,
which holds the optimizer's own (``optimizer/fused_adam/...``,
``optimizer/fused_lamb/...``): the update with its skip-on-overflow
select, and LAMB's per-tensor norms; with it what XLA fuses into the
update's pass (amp's unscale and cast of the masters, where it does).
Device trace joined to the compiled step's scopes
(``benchmark/scopes.py``)."""

from benchmark import scopes


def read(ctx):
    return scopes.block_ms_per_step(
        ctx, lambda block, phase: block == "optimizer")
