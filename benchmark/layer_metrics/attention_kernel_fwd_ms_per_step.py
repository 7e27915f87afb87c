"""Kernels: device milliseconds a step in attention's forward Pallas
kernels, by the names the program gives them: flash attention's
``self_attention_flash_fwd`` (``contrib/fmha.py``; with recomputation on
it runs twice a layer, and both are counted) and the fused softmax's
``softmax_fwd`` (``kernels/softmax.py``). Device trace."""

from benchmark import scopes


def is_forward_kernel(op):
    name = scopes.kernel_name(op)
    return name.endswith("_flash_fwd") or name == "softmax_fwd"


def read(ctx):
    return scopes.ms_per_step(ctx, is_forward_kernel)
