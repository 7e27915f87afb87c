"""Engine, whole step: FLOPs the tokens processed in the window need (2 x
matrix parameters per prompt or output token, plus attention over each
token's context; ``benchmark/flops.py``) over the window over the chip's
published peak."""


def read(ctx):
    w, peaks = ctx["window"], ctx["peaks"]
    if peaks is None or not w["contexts"]:
        return None
    need = ctx["flops"].serve_flops(ctx["arch"], w["contexts"])
    return 100.0 * need / w["elapsed_s"] / peaks["flops_per_s"]
