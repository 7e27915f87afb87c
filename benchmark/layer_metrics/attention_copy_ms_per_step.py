"""Kernels: device milliseconds a step in ``copy`` operations traced
under ``self_attention``: the layout changes XLA puts around the
attention kernels (``[s, b, n, d]`` to the kernels' ``[b * n, s, d]`` and
back, forward, backward and recomputed). Device trace joined to the
compiled step's scopes (``benchmark/scopes.py``)."""

from benchmark import scopes


def read(ctx):
    if scopes.table(ctx) is None:
        return None

    def is_attention_copy(op):
        block = scopes.block_of(ctx, op)[0]
        return (op.opcode == "copy" and block is not None
                and block.split("/")[0] == "attention")

    return scopes.ms_per_step(ctx, is_attention_copy)
