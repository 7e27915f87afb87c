"""Kernels (the selective scan of ``transformer/ssm.py``, today XLA's own
operations under scope ``ssm/scan`` and no Pallas kernel): the scan's
share of its roofline. The time the recurrence needs at the least, over
the device time of the operations of block ``ssm/scan`` (the union of
their intervals; the recomputed pass is in the time and not in the need).
The need is the longer of two bounds, both from shapes alone and
independent of how the scan is implemented
(``benchmark/families/nemotron_h.py``): the FLOPs of the recurrence
itself, forward and backward, at the chip's peak, and the bytes a scan
has to move (x, B, C, dt read and y written once a pass, their gradients
once) at its memory bandwidth. At 128 states a channel the byte bound is
the longer on a v5e (about twice the FLOP bound). A chunked algorithm's
extra products, its decay matrices and every intermediate it writes to
memory are not needed work, so they read low here. Reads nothing where
no such operation ran or the family has no such count."""

from benchmark import families, scope_union, scopes


def read(ctx):
    peaks = ctx["peaks"]
    if peaks is None:
        return None
    family = families.of(ctx["arch"])
    if not hasattr(family, "ssm_scan_train_flops_per_step"):
        return None
    seconds = scope_union.seconds(
        ctx, lambda block, phase: block == "ssm/scan")
    if not seconds:
        return None
    mix = ctx["mix"]
    args = (ctx["arch"], mix["batch"], mix["seq"])
    need_s = max(
        family.ssm_scan_train_flops_per_step(*args) / peaks["flops_per_s"],
        family.ssm_scan_train_bytes_per_step(*args)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * need_s * scopes.steps_traced(ctx) / seconds
