"""Device: share of the device's busy time in operations that classify
to no block of the program: those XLA gives no scope (asynchronous
``copy-start`` / ``copy-done`` / ``slice-start`` / ``slice-done`` between
memory spaces, ``ConcatBitcast`` custom calls, some ``copy``s) and those
traced outside every module and named scope. What the tracing still
cannot name. Device trace joined to the compiled step's scopes
(``benchmark/scopes.py``)."""

from benchmark import scopes


def read(ctx):
    if scopes.table(ctx) is None:
        return None
    tr = ctx["trace"]
    busy = tr.busy_s()
    if busy <= 0:
        return None
    seconds = tr.seconds_in(
        lambda op: scopes.block_of(ctx, op)[0] is None)
    return 100.0 * seconds / busy if seconds > 0 else None
