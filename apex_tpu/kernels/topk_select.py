"""The sparse-attention indexer's top-k selection with a block of query
rows held in VMEM for the whole bisection.

The oracle (``models.transformer_lm.topk_selection``'s jnp body) finds
each query's threshold, its ``topk``-th largest causal index score, by a
32-step bisection over the float32's bits, and every step reads the whole
``[s, s]`` score matrix of a sequence from HBM again: 33 reads a
selection. This kernel reads a block of ``ROWS`` query rows once, turns
it into order-preserving integer keys in a VMEM scratch, runs the same 32
compare-and-count passes on the resident keys, and writes the int8
selection: one read and one write of HBM, and **the oracle's result bit
for bit** (same threshold, ties with the threshold all kept, rows shorter
than ``topk`` fully causal; nothing is approximated and no pass dropped).

Inside a block it leaves out what cannot matter: a block whose last row
is below ``topk`` writes the causal mask without bisecting, and a pass
counts only the 128-lane column tiles up to the block's causal limit.

Keys: the oracle orders float32 as uint32 (``~bits`` for negatives,
``bits | 0x80000000`` otherwise, 0 for "not causal"). The vector units
compare signed, so the scratch holds that key with its top bit flipped
(``bits ^ ((bits >> 31) & 0x7fffffff)``, ``INT32_MIN`` for "not causal")
and each pass flips the candidate's top bit the same way: the comparison
is the oracle's. The scratch is ``[s / 128, ROWS, 128]``, a column tile
an entry, so that the passes index it by a leading dimension.

Gate: ``topk_select``; in a trace the kernel is ``indexer_topk_select``.
"""

import functools
import math

import jax
import jax.numpy as jnp

from apex_tpu.kernels.registry import kernel_gate

GATE = kernel_gate("topk_select")

LANES = 128
ROWS = 128            # query rows a grid cell holds (int8 tiles take 32)
GROUP = 4             # column tiles a step of a counting loop takes
_VMEM_BUDGET = 96 * 1024 * 1024
_INT32_MIN = -2 ** 31


def _vmem_bytes(s: int) -> int:
    # scores in and selection out, double-buffered, and the key scratch
    return ROWS * s * (2 * 4 + 2 * 1 + 4)


def fits(shape) -> bool:
    """Can the kernel take ``[b, s, s]`` scores? A predicate on the shape
    alone: whole row blocks and lane tiles, the row block within VMEM."""
    return (len(shape) == 3 and shape[1] == shape[2]
            and shape[1] % ROWS == 0 and shape[1] % LANES == 0
            and _vmem_bytes(shape[1]) <= _VMEM_BUDGET)


def _kernel(scores_ref, out_ref, keys_ref, *, topk, tiles, group):
    from jax.experimental import pallas as pl

    r0 = pl.program_id(1) * ROWS
    row = r0 + jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1)
    # groups of column tiles that hold a causal key of some row of the block
    live = jnp.minimum((r0 + ROWS + group * LANES - 1) // (group * LANES),
                       tiles // group)

    def columns(j):
        return pl.ds(pl.multiple_of(j * LANES, LANES), LANES)

    def causal(j):
        return j * LANES + lane <= row

    def each_tile(first, last, body):
        """``body(j)`` for the column tiles of groups ``first .. last``."""
        def one_group(g, carry):
            for u in range(group):
                body(g * group + u)
            return carry

        jax.lax.fori_loop(first, last, one_group, 0)

    def nothing(j):
        out_ref[0, :, columns(j)] = jnp.zeros((ROWS, LANES), jnp.int8)

    each_tile(live, tiles // group, nothing)

    @pl.when(r0 + ROWS <= topk)
    def _():        # every row keeps all its causal keys
        def write(j):
            out_ref[0, :, columns(j)] = causal(j).astype(jnp.int8)

        each_tile(0, live, write)

    @pl.when(r0 + ROWS > topk)
    def _():
        def to_keys(j):
            bits = jax.lax.bitcast_convert_type(
                scores_ref[0, :, columns(j)], jnp.int32)
            key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
            keys_ref[j] = jnp.where(causal(j), key, jnp.int32(_INT32_MIN))

        each_tile(0, live, to_keys)
        want = jnp.minimum(row[:, :1] + 1, topk)

        def one_pass(i, found):
            cand = found | (jnp.int32(1) << (31 - i))
            signed = cand ^ jnp.int32(_INT32_MIN)

            def count(g, acc):
                for u in range(group):
                    acc = acc + (keys_ref[g * group + u]
                                 >= signed).astype(jnp.int32)
                return acc

            acc = jax.lax.fori_loop(
                0, live, count, jnp.zeros((ROWS, LANES), jnp.int32))
            total = jnp.sum(acc, axis=-1, keepdims=True)
            return jnp.where(total >= want, cand, found)

        found = jax.lax.fori_loop(0, 32, one_pass,
                                  jnp.zeros((ROWS, 1), jnp.int32))
        threshold = found ^ jnp.int32(_INT32_MIN)

        def write(j):
            out_ref[0, :, columns(j)] = (
                (keys_ref[j] >= threshold) & causal(j)).astype(jnp.int8)

        each_tile(0, live, write)


# Under ``jax.jit``: a model's layers share one trace and one lowering of
# the kernel (as contrib/fmha.py's batch-major kernels do).
@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select(scores, *, topk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = scores.shape
    tiles = s // LANES
    spec = pl.BlockSpec((1, ROWS, s), lambda r, i: (r, i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, topk=topk, tiles=tiles,
                          group=math.gcd(GROUP, tiles)),
        grid=(b, s // ROWS),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.int8),
        scratch_shapes=[pltpu.VMEM((tiles, ROWS, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem_bytes(s) + 16 * 1024 * 1024),
        interpret=interpret,
        name="indexer_topk_select",
    )(scores)


def topk_select(scores, topk: int):
    """``[b, s, s]`` float32 scores -> the int8 selection of
    ``topk_selection``, by the kernel (the caller has asked the gate)."""
    return _select(scores, topk=int(topk), interpret=GATE.interpret)
