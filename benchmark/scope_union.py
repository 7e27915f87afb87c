"""``block_time.union_ms_per_step`` for a set of blocks: device
milliseconds a step in which an operation ran whose ``(block, phase)``
a predicate accepts, loops counted once. For the readers of a block that
the program's table splits into parts (``ssm/in_proj`` ... ``ssm/scan``:
``layer_metrics/{ssm_ms_per_step,ssm_scan_roofline}.py``)."""

from benchmark import scopes, xplane


def seconds(ctx, wanted):
    """Seconds of the traced window in which an operation ran that
    ``wanted(block, phase)`` accepts, averaged over devices; ``None``
    where there is no trace or no scope table, as on a program that has
    none."""
    if scopes.table(ctx) is None:
        return None
    tr = ctx["trace"]
    return sum(xplane.union_seconds(tr._clipped(
        d, lambda op: wanted(*scopes.block_of(ctx, op))))
        for d in tr.devices) / len(tr.devices)


def ms_per_step(ctx, wanted):
    s = seconds(ctx, wanted)
    return 1e3 * s / scopes.steps_traced(ctx) if s else None
