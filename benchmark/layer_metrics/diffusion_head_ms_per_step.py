"""The model (``models/gpt.py`` under ``diffusion_block_length``): device
milliseconds a step in the operations traced under block
``diffusion_head`` of the program's table, scopes
``diffusion/select_noisy`` (the slice of rows ``L..2L-1``),
``diffusion/head`` (the head on them) and ``diffusion/loss`` (the weighted
cross-entropy), forward and backward: what a block-diffusion model runs on
the noisy half alone. The union of their intervals
(``benchmark/scope_union.py``), from the device trace joined to the
compiled step's scopes (``benchmark/scopes.py``); reads nothing on a
program whose scope table has no such block."""

from benchmark import scope_union


def read(ctx):
    return scope_union.ms_per_step(
        ctx, lambda block, phase: block == "diffusion_head")
