"""amp (``amp/amp_optimizer.py``): device milliseconds a step in the
operations traced under ``AmpOptimizer.step``'s scopes ``amp/unscale``
(``multi_tensor_scale`` and the overflow check), ``amp/master_to_model``
(the cast of the float32 masters to the model's bf16) and
``amp/scaler_update``. A fusion counts for the scope most of its
instructions were traced under, so where XLA fuses the unscale and the
cast into the optimizer's update that pass is the optimizer's, and this
reads what amp costs beside it. Device trace joined to the compiled
step's scopes (``benchmark/scopes.py``)."""

from benchmark import scopes


def read(ctx):
    return scopes.block_ms_per_step(
        ctx, lambda block, phase: block == "amp")
