"""Kernels (``contrib/fmha.py``, the flash kernels under the
block-diffusion rule, the Pallas calls named ``blockdiff_attention_*``):
their share of their roofline. The time the attention needs at the least,
over the device time of those kernels' events (forward, the forward under
recomputation where it runs, dq and dkv: the recomputed pass is in the
time and not in the need). The need is the longer of two bounds, both from
shapes alone and independent of how the kernels are designed
(``benchmark/families/sdar_moe.py``): the FLOPs of the pairs the rule lets
a query see (``L^2 + L * bl`` a head and row of ``2L``), forward and
backward, at the chip's peak, and the bytes of q, k, v, the context, the
log-sum-exp and their gradients once a pass at its memory bandwidth. At
8192 data tokens the FLOP bound is the longer by far; the tiles that run
hold 89% visible pairs and the kept forward is three quarters of the
kernels' passes, so about 67% is the share's ceiling. Reads nothing
where no such kernel ran or the family has no such count."""

from benchmark import families, scopes


def is_blockdiff_kernel(op):
    return scopes.kernel_name(op).startswith("blockdiff_attention_")


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None or not tr.ops:
        return None
    family = families.of(ctx["arch"])
    if not hasattr(family, "blockdiff_attention_train_flops_per_step"):
        return None
    seconds = tr.seconds_in(is_blockdiff_kernel)
    if seconds <= 0:
        return None
    mix = ctx["mix"]
    args = (ctx["arch"], mix["batch"], mix["seq"])
    need_s = max(
        family.blockdiff_attention_train_flops_per_step(*args)
        / peaks["flops_per_s"],
        family.blockdiff_attention_train_bytes_per_step(*args)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * need_s * scopes.steps_traced(ctx) / seconds
