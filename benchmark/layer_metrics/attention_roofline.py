"""Kernels (``contrib/fmha.py``): the flash attention kernels' share of
their roofline. Attention's FLOPs per step from shapes (forward +
backward, causal half, no recomputation; ``benchmark/flops.py``) over the
device time of the ``custom-call`` events named ``self_attention`` (the
Pallas calls under ``ParallelAttention``'s scope), over the chip's peak. At head size 64 and sequence 1024 the kernel
is compute-bound (its bytes, q k v o and their gradients once each, take
under a tenth of the time its FLOPs take at peak), so the FLOP bound is
the one that holds. Reads nothing where no such event ran (BERT runs
attention as unnamed XLA fusions today)."""


def is_attention_kernel(op):
    return op.opcode == "custom-call" and "self_attention" in op.name


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None or not tr.ops:
        return None
    seconds = tr.seconds_in(is_attention_kernel)
    if seconds <= 0:
        return None
    mix = ctx["mix"]
    steps_traced = ctx["window"]["steps"] * tr.window_s \
        / ctx["window"]["elapsed_s"]
    needed = ctx["flops"].attention_train_flops_per_step(
        ctx["arch"], mix["batch"], mix["seq"], causal=True) * steps_traced
    return 100.0 * needed / peaks["flops_per_s"] / seconds
