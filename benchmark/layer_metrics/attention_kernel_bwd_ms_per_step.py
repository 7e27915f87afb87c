"""Kernels: device milliseconds a step in attention's backward Pallas
kernels, by the names the program gives them: flash attention's
``self_attention_flash_dq`` and ``self_attention_flash_dkv``
(``contrib/fmha.py``) and the fused softmax's ``softmax_bwd``
(``kernels/softmax.py``). Device trace."""

from benchmark import scopes


def is_backward_kernel(op):
    name = scopes.kernel_name(op)
    return (name.endswith("_flash_dq") or name.endswith("_flash_dkv")
            or name == "softmax_bwd")


def read(ctx):
    return scopes.ms_per_step(ctx, is_backward_kernel)
