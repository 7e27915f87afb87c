"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights; the program and the plain reference are
both handed the same ones. The layout here is the benchmark's own
("canonical"): per-layer tensors stacked on a leading ``[L]`` axis, named
after what they are. The family's module (``benchmark/families/``) gives
the shapes and maps the layout onto the program's parameter tree; the
reference reads it as it is.

Init follows the published recipes: normal(0, 0.02) matrices and
embeddings, zero biases, LayerNorm gain 1 and bias 0. Matrices and
embeddings are rounded to bf16-representable values, so that the bf16
model weights the configuration states and the float32 masters start out
equal, in the program and in the reference alike.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark import families

STD = 0.02


def seed_key(seed: int):
    """A PRNG key from any whole number (``--seed`` may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def shapes(arch: dict) -> dict:
    """Canonical name -> shape, as the architecture's family has it."""
    return families.of(arch).shapes(arch)


def round_to_bf16(x):
    """``x`` rounded to values bfloat16 holds, still float32.
    ``lax.reduce_precision`` and not ``astype`` there and back: the TPU
    compiler drops such a pair of converts as excess precision (found in
    PR 24: the masters, made from the program's bf16 weights, sat a
    rounding error away from the start their change was measured from)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _kind(name: str) -> str:
    leaf = name.rsplit(".", 1)[-1]
    if leaf.endswith("_g"):
        return "gain"
    if leaf.endswith("_b"):
        return "bias"
    return "matrix"


def make(key, arch: dict) -> dict:
    """The canonical float32 tree for ``arch`` from ``key`` (traceable)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(arch).items())):
        kind = _kind(name)
        if kind == "gain":
            out[name] = jnp.ones(shape, jnp.float32)
        elif kind == "bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            w = STD * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            out[name] = round_to_bf16(w)
    return out


@functools.lru_cache(maxsize=None)
def _maker(arch_items: tuple):
    arch = dict(arch_items)
    return jax.jit(lambda key: make(key, arch))


def make_on_device(arch: dict, seed: int) -> dict:
    """The canonical tree for ``seed`` through ONE compiled program per
    architecture. Everything that needs the seed's weights (the program's
    state, the start that its change is measured from, the reference) calls
    this and nothing else: the same executable gives the same bits, where
    ``make`` traced into two different programs may round a few of them to
    neighbouring bf16 values on the chip (found in PR 24: the parameters'
    change, measured from a start made inside another program, read 2.6
    times the reference's)."""
    return _maker(tuple(sorted(arch.items())))(seed_key(seed))


def n_params(arch: dict) -> int:
    total = 0
    for shape in shapes(arch).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total
