"""Model (``models/transformer_lm.py`` over ``transformer/``): share of
the traced window the device spends in matrix-product operations. On the
TPU XLA turns a dot into a convolution and fuses what follows into its
output, so these are the fusions of kind ``kOutput``, the fusions named
after a convolution, and bare ``convolution`` / ``dot`` instructions.
Device trace."""


def is_matmul(op):
    return (op.kind == "kOutput" or op.opcode in ("convolution", "dot")
            or "convolution" in op.name)


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.ops:
        return None
    seconds = tr.seconds_in(is_matmul)
    return 100.0 * seconds / tr.window_s if seconds > 0 else None
