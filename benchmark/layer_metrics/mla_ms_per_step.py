"""Latent attention (``models/transformer_lm.py`` ``ParallelAttention``'s
latent path): device milliseconds a step in the operations traced under
its scopes ``mla/q_proj``, ``mla/kv_down``, ``mla/kv_up``, ``mla/rope``,
``mla/kernel`` and ``mla/out_proj`` (block ``mla`` and its parts in the
program's table), forward, recomputed and backward. The union of their
intervals (``benchmark/scope_union.py``), from the device trace joined to
the compiled step's scopes (``benchmark/scopes.py``); reads nothing on a
program whose scope table has no such block."""

from benchmark import scope_union


def read(ctx):
    return scope_union.ms_per_step(
        ctx, lambda block, phase: (block or "").split("/")[0] == "mla")
