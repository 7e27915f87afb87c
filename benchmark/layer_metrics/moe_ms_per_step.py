"""Expert layer (``transformer/moe/layer.py`` ``SwitchMLP``): device
milliseconds a step in the operations traced under its scopes
``moe/router``, ``moe/dispatch``, ``moe/experts`` and ``moe/combine``, and
in XLA's own ``ragged-dot`` kernels (which keep no scope; the program's
table gives them to this block): the router over all the published
experts, the gather of the rows that fell on held experts, the grouped
matmuls and the weighted scatter-add back, forward, recomputed and
backward. The union of their intervals (``benchmark/block_time.py``: the
block's loops show as ``while`` operations that span their bodies), from
the device trace joined to the compiled step's scopes
(``benchmark/scopes.py``); reads nothing on a program whose scope table
has no such block."""

from benchmark import block_time


def read(ctx):
    return block_time.union_ms_per_step(ctx, "moe")
