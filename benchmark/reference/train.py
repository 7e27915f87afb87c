"""The reference's first training steps, in blocks of rows so that a
full-width batch fits: gradients of each block's part of the loss are
added up, then one optimizer step is taken on the sum.

``readings`` are what the comparison that decides ``correct`` holds
against the program's: each step's loss, the norm per tensor of the first
gradient as the optimizer gets it (read back from its state after step
one, by the same formula on both sides: ``benchmark/optimizers/``), and
the norm per tensor of the parameters' change over the steps.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import optimizers
from benchmark.reference import family as load_family
from benchmark.reference import transformer as T


def tensor_norms(tree, heads: int) -> dict:
    """name -> L2 norm per tensor (a vector ``[L]`` for stacked leaves).
    The fused QKV matrix and bias are three tensors each (their layout is
    ``[..., heads, (q|k|v), d]``): the key's bias has no gradient under
    softmax, and fused with the others it would hide in their norm."""
    out = {}
    for k, x in tree.items():
        x = x.astype(jnp.float32)
        if k in ("layers.qkv_w", "layers.qkv_b"):
            x = x.reshape(x.shape[:-1] + (heads, 3, -1))
            axes = tuple(a for a in range(1, x.ndim) if a != x.ndim - 2)
            parts = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))   # [L, 3]
            for i, part in enumerate("qkv"):
                out[f"{k}.{part}"] = parts[:, i]
            continue
        axes = tuple(range(1, x.ndim)) if k.startswith("layers.") else None
        out[k] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


def flatten_norms(norms: dict) -> tuple:
    """-> (names, float64 vector), one entry per tensor, sorted by name."""
    names, values = [], []
    for k in sorted(norms):
        v = np.atleast_1d(np.asarray(norms[k], np.float64))
        for i, x in enumerate(v):
            names.append(f"{k}[{i}]" if k.startswith("layers.") else k)
            values.append(float(x))
    return names, np.asarray(values)


def row_blocks(batch: dict, rows: int):
    n = next(iter(batch.values())).shape[0]
    for lo in range(0, n, rows):
        yield {k: v[lo:lo + rows] for k, v in batch.items()}


class Reference:
    """The reference's jitted pieces for one architecture, optimizer and
    arithmetic, built once and driven from any number of seeds."""

    def __init__(self, arch, optimizer, hp, quant=T.identity):
        fam = load_family(arch["family"])
        self.fam, self.hp = fam, hp
        self.opt = optimizers.of(optimizer)

        @jax.jit
        def part(params, block, totals):
            return jax.value_and_grad(
                lambda p: fam.loss_part(p, arch, block, totals, quant))(
                    params)

        self.part = part
        self.add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        self.update = jax.jit(
            functools.partial(self.opt.step, hp=hp),
            donate_argnums=(0, 2))
        heads = arch["heads"]
        self.norms = jax.jit(lambda tree: tensor_norms(tree, heads))
        self.change = jax.jit(lambda a, b: tensor_norms(
            {k: a[k] - b[k] for k in a}, heads))

    def run(self, params, batches, *, block_rows=2, keep_rows=None):
        """Drive ``len(batches)`` steps from ``params``; return the
        readings. ``keep_rows`` plants the half-batch fault in the
        reference's place: only the first ``keep_rows`` rows of each batch
        are used, and the mean is taken over those."""
        start = params
        params = jax.tree_util.tree_map(jnp.copy, params)
        state = self.opt.init(params)
        losses, grad_norms = [], None
        for batch in batches:
            if keep_rows is not None:
                batch = {k: v[:keep_rows] for k, v in batch.items()}
            totals = self.fam.totals(batch)
            loss, grads = 0.0, None
            for block in row_blocks(batch, block_rows):
                block = {k: jnp.asarray(v) for k, v in block.items()}
                part_loss, part_grads = self.part(params, block, totals)
                loss = loss + float(part_loss)
                grads = part_grads if grads is None \
                    else self.add(grads, part_grads)
            params, state = self.update(params, grads, state)
            losses.append(loss)
            if grad_norms is None:
                grad_norms = jax.device_get(self.norms(
                    self.opt.first_gradient(state, self.hp)))
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": jax.device_get(self.change(params, start))}


def run(arch, params, batches, optimizer, hp, *, quant=T.identity,
        block_rows=2, keep_rows=None):
    return Reference(arch, optimizer, hp, quant).run(
        params, batches, block_rows=block_rows, keep_rows=keep_rows)
