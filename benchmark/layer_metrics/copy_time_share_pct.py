"""Engine's slotted KV cache (``serving/kv_cache.py``): share of the
traced window the device spends in ``copy`` operations (the gather and
scatter of whole cache rows around each step). Device trace."""


def is_copy(op):
    return op.name.startswith("copy")


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.ops:
        return None
    seconds = tr.seconds_in(is_copy)
    return 100.0 * seconds / tr.window_s if seconds > 0 else None
