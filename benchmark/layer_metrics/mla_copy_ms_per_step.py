"""Kernels: device milliseconds a step in ``copy`` operations traced
under the latent attention's scopes ``mla/*``: the layout changes XLA
puts between the projections, the rotary turn and the latent attention's
kernels (``[s, b, ...]`` to the kernels' ``[b, s, ...]`` and the
interleaved rotary pairs' relayouts, forward, recomputed and backward).
``attention_copy_ms_per_step``'s sibling for block ``mla``, which that
reader does not see. Device trace joined to the compiled step's scopes
(``benchmark/scopes.py``); reads nothing on a program whose scope table
has no such block."""

from benchmark import scopes


def read(ctx):
    if scopes.table(ctx) is None:
        return None

    def is_mla_copy(op):
        block = scopes.block_of(ctx, op)[0]
        return (op.opcode == "copy" and block is not None
                and block.split("/")[0] == "mla")

    return scopes.ms_per_step(ctx, is_mla_copy)
