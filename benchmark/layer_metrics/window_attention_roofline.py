"""Kernels (``contrib/fmha.py``, the flash kernels under a sliding window,
the Pallas calls named ``window_attention_*``): their share of their
roofline. The time the band's attention needs at the least, over the
device time of those kernels' events (forward, the forward under
recomputation where it runs, dq and dkv: the recomputed pass is in the
time and not in the need). The need is the longer of two bounds, both
from shapes alone and independent of how the kernels are designed
(``benchmark/families/laguna.py``): the FLOPs of the pairs the band lets a
query see (``w (w + 1) / 2 + (s - w) w`` a head and row), forward and
backward, at the chip's peak, and the bytes of q, k and v at their K/V
heads, the context, the log-sum-exp and their gradients once a pass at its
memory bandwidth. At 8192 positions, a window of 512 and tiles of 512 the
tiles that run hold half visible pairs (31 a head, the diagonal's and the
one below it), and dq and dkv form the scores again (nine products a
tile where the need counts six), so about a third is the share's ceiling.
Reads nothing where no such kernel ran or the family has no such
count."""

from benchmark import families, scopes


def is_window_kernel(op):
    return scopes.kernel_name(op).startswith("window_attention_")


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None or not tr.ops:
        return None
    family = families.of(ctx["arch"])
    if not hasattr(family, "window_attention_train_flops_per_step"):
        return None
    seconds = tr.seconds_in(is_window_kernel)
    if seconds <= 0:
        return None
    mix = ctx["mix"]
    args = (ctx["arch"], mix["batch"], mix["seq"])
    need_s = max(
        family.window_attention_train_flops_per_step(*args)
        / peaks["flops_per_s"],
        family.window_attention_train_bytes_per_step(*args)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * need_s * scopes.steps_traced(ctx) / seconds
