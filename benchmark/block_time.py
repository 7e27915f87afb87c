"""Device time of one of the program's blocks where the block holds
loops. A ``while`` operation spans the operations of its body in the
trace, so ``Trace.seconds_in`` (a sum of durations) counts a loop's work
twice; the union of the block's intervals counts it once. For the readers
of blocks that loop (``layer_metrics/{indexer,moe}_ms_per_step.py``)."""

from benchmark import scopes, xplane


def union_ms_per_step(ctx, block):
    """Milliseconds a step in which an operation of ``block`` ran;
    ``None`` where there is no trace, no scope table or no such
    operation."""
    if scopes.table(ctx) is None:
        return None
    tr = ctx["trace"]
    seconds = sum(xplane.union_seconds(tr._clipped(
        d, lambda op: scopes.block_of(ctx, op)[0] == block))
        for d in tr.devices) / len(tr.devices)
    return 1e3 * seconds / scopes.steps_traced(ctx) if seconds > 0 else None
