"""Mamba-2 mixers (``transformer/ssm.py`` ``Mamba2Mixer``): device
milliseconds a step in the operations traced under its scopes
``ssm/in_proj``, ``ssm/conv``, ``ssm/scan``, ``ssm/gate_norm`` and
``ssm/out_proj`` (block ``ssm`` and its parts in the program's table),
forward, recomputed and backward. The union of their intervals
(``benchmark/scope_union.py``: the carry between chunks is a loop, which
shows as a ``while`` operation that spans its body), from the device
trace joined to the compiled step's scopes (``benchmark/scopes.py``);
reads nothing on a program whose scope table has no such block."""

from benchmark import scope_union


def read(ctx):
    return scope_union.ms_per_step(
        ctx, lambda block, phase: (block or "").split("/")[0] == "ssm")
