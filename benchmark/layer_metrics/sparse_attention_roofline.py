"""Kernels (``contrib/fmha.py`` with a selection operand): the sparse
attention kernels' share of their roofline. The time the kernels need at
the least, over the device time of the ``custom-call`` events named
``sparse_attention_*`` (forward, dq, dkv and the head-summed
probabilities; runs under recomputation included in the time, not in the
need). The need is the longer of two bounds, both from shapes
(``benchmark/families/keye_vl2.py``): the FLOPs over the selected (query,
key) pairs alone, forward + backward, at the chip's peak, and the bytes
the kernels have to move (q, k, v, out and their gradients, the int8
selection once a kernel, the float32 probabilities once) at its memory
bandwidth. At head size 128 and 2048 selected keys a query the FLOP bound
is the longer by far (about 20 times the byte bound on a v5e), so it is
the one that holds. A masked kernel that also computes the unselected
pairs of a tile reads low here: that work is not needed. Reads nothing
where no such event ran or the family has no such count."""

from benchmark import families, scopes


def is_sparse_kernel(op):
    return scopes.kernel_name(op).startswith("sparse_attention_")


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None or not tr.ops:
        return None
    family = families.of(ctx["arch"])
    if not hasattr(family, "sparse_attention_train_flops_per_step"):
        return None
    seconds = tr.seconds_in(is_sparse_kernel)
    if seconds <= 0:
        return None
    mix = ctx["mix"]
    steps = scopes.steps_traced(ctx)
    args = (ctx["arch"], mix["batch"], mix["seq"])
    need_s = max(
        family.sparse_attention_train_flops_per_step(*args)
        / peaks["flops_per_s"],
        family.sparse_attention_train_bytes_per_step(*args)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * need_s * steps / seconds
