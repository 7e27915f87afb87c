"""Model (``models/transformer_lm.py``, ``activation_checkpointing``):
share of the traced window the device spends in operations traced under
``jax.checkpoint``'s ``rematted_computation``: every layer's forward run
a second time in the backward pass. Reads nothing where the cell does not
recompute. Device trace joined to the compiled step's scopes
(``benchmark/scopes.py``)."""

from benchmark import scopes


def read(ctx):
    if scopes.table(ctx) is None:
        return None
    tr = ctx["trace"]
    seconds = tr.seconds_in(
        lambda op: scopes.block_of(ctx, op)[1] == "recompute")
    return 100.0 * seconds / tr.window_s if seconds > 0 else None
