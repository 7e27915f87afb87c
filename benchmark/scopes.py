"""Which block of the program each device operation of a traced window
belongs to, for the per-layer readers that read device time by block
(``layer_metrics/{optimizer,amp,layernorm}_ms_per_step.py`` and the others
that import this module).

``xplane.Op`` keeps an operation's instruction name and nothing else of
the event, and the harness does not keep the step's executable. So the
cell's step is built again as ``program.py`` builds it and lowered for
abstract state; compiling it returns, from the persistent cache, the
executable the window ran (a miss compiles it again, to the same
instruction names: they do not depend on metadata), and
``apex_tpu.telemetry.scopes`` reads from its text the scope of every
instruction and folds it into (block, phase).
That is Python tracing and lowering of the whole step a second time, so
it happens once a run, only when a reader asks, and a reader asks only
once it holds a device trace: after the window and the reference, outside
everything that is timed. What it took goes to standard error.

A program from before ``apex_tpu/telemetry/scopes.py`` has no such table:
``table`` is then ``None`` and every reader over it reads nothing.
"""

import sys
import time

from benchmark import xplane


def kernel_name(op) -> str:
    """A ``custom-call``'s name without XLA's numbering (as
    ``xplane.group_name`` strips it): the ``name=`` its ``pl.pallas_call``
    was given (``self_attention_flash_dq``)."""
    return xplane._SUFFIX.sub("", op.name) if op.opcode == "custom-call" \
        else ""


def _abstract(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def compiled_step(cell):
    """The cell's train step, compiled for abstract state and batch."""
    import jax

    from benchmark import families, program, train_cell

    # jax caches the traces of jitted helpers (``jnp.where``) with the
    # source location of their first use, which by now is the reference's:
    # start as the run did, or the step's cache key is another
    jax.clear_caches()
    mesh = cell.mix.get("mesh")
    prog = program.TrainProgram(cell.arch, cell.mix, mesh)
    state = jax.eval_shape(lambda: prog.init_state(0))
    batch = next(families.batches(cell.arch,
                                  train_cell.global_mix(cell.mix), 0))
    params, opt_state = _abstract(state, prog._state_sharding)
    return prog.step.lower(params, opt_state,
                           _abstract(batch, prog.batch_sharding)).compile()


def _build(cell):
    try:
        from apex_tpu.telemetry import scopes
    except ImportError:
        return None
    if cell.mix["kind"] != "train":
        return None
    from apex_tpu._compile_cache import cache_stats

    t0, before = time.perf_counter(), cache_stats()
    scope_of = scopes.scope_table(compiled_step(cell))
    after = cache_stats()
    print(f"scope table: {len(scope_of)} instructions in "
          f"{time.perf_counter() - t0:.1f} s (second lowering of the step; "
          f"cache hits {after['hits'] - before['hits']}, misses "
          f"{after['misses'] - before['misses']})", file=sys.stderr)
    return {name: scopes.classify(scope) for name, scope in scope_of.items()}


def table(ctx):
    """``{instruction name: (block, phase)}`` of the cell's step, built on
    the first call of a run and kept in ``ctx``; ``None`` where there is
    no device trace to join it to, or the program has no scope table."""
    tr = ctx["trace"]
    if tr is None or not tr.ops:
        return None
    if "scope_blocks" not in ctx:
        ctx["scope_blocks"] = _build(ctx["cell"])
    return ctx["scope_blocks"]


def block_of(ctx, op) -> tuple:
    """``(block, phase)`` of a traced operation, ``(None, None)`` for one
    the table does not hold. Call only where ``table(ctx)`` is a table."""
    return ctx["scope_blocks"].get(op.name, (None, None))


def steps_traced(ctx) -> float:
    """Steps in the traced window, as ``attention_roofline.py`` counts
    them."""
    w = ctx["window"]
    return w["steps"] * ctx["trace"].window_s / w["elapsed_s"]


def ms_per_step(ctx, keep):
    """Device milliseconds a step in the operations ``keep(op)`` selects;
    ``None`` where there is no trace or no such operation ran."""
    tr = ctx["trace"]
    if tr is None or not tr.ops:
        return None
    seconds = tr.seconds_in(keep)
    return 1e3 * seconds / steps_traced(ctx) if seconds > 0 else None


def block_ms_per_step(ctx, wanted):
    """The same for the operations whose ``(block, phase)`` the predicate
    ``wanted`` accepts; ``None`` too where there is no table."""
    if table(ctx) is None:
        return None
    return ms_per_step(ctx, lambda op: wanted(*block_of(ctx, op)))
