"""Normalization (``normalization/`` under the models' modules
``input_layernorm``, ``post_attention_layernorm``, ``final_layernorm``):
device milliseconds a step in the operations traced under them, forward,
backward and recomputed. Where XLA fuses a LayerNorm into the matmul it
feeds or follows, that fusion's time is the matmul's and is not counted
here: this is what LayerNorm costs as operations of its own.
Device trace joined to the compiled step's scopes
(``benchmark/scopes.py``)."""

from benchmark import scopes


def read(ctx):
    return scopes.block_ms_per_step(
        ctx, lambda block, phase: block == "layernorm")
