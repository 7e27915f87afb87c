"""Kernels (``contrib/fmha.py``, the latent attention's flash kernels,
the Pallas calls named ``mla_attention_*``): their share of their
roofline. The time the attention needs at the least, over the device time
of those kernels' events (forward, the forward under recomputation where
it runs, dq and dkv: the recomputed pass is in the time and not in the
need). The need is the longer of two bounds, both from shapes alone and
independent of how the kernels are designed
(``benchmark/families/deepseek_v3.py``): the causal pairs' FLOPs, forward
and backward, at the chip's peak, and the bytes of q, both keys, v, the
context, the log-sum-exp and their gradients once a pass at its memory
bandwidth. At 8192 positions the FLOP bound is the longer by far. Reads
nothing where no such kernel ran or the family has no such count."""

from benchmark import families, scopes


def is_mla_kernel(op):
    return scopes.kernel_name(op).startswith("mla_attention_")


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None or not tr.ops:
        return None
    family = families.of(ctx["arch"])
    if not hasattr(family, "mla_attention_train_flops_per_step"):
        return None
    seconds = tr.seconds_in(is_mla_kernel)
    if seconds <= 0:
        return None
    mix = ctx["mix"]
    args = (ctx["arch"], mix["batch"], mix["seq"])
    need_s = max(
        family.mla_attention_train_flops_per_step(*args)
        / peaks["flops_per_s"],
        family.mla_attention_train_bytes_per_step(*args)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * need_s * scopes.steps_traced(ctx) / seconds
