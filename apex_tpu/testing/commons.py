"""Testing harness helpers.

Parity: reference apex/transformer/testing/commons.py (296 LoC — model
providers, initialize_distributed, set_random_seed) and
distributed_test_base.py (spawned multi-process test bases). On TPU the
multi-process harness becomes SPMD ``shard_map`` over a virtual device
mesh; this module centralizes the wrapper used across the test suite.
"""

import functools

import jax
import numpy as np


def shard_map(fn=None, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with vma (replication) checking disabled.

    The apex_tpu collective region ops are custom-vjp pairs whose
    replication typing JAX's static vma checker cannot always infer
    (e.g. psum-in-backward of an identity forward); runtime semantics are
    still exactly SPMD. Usable as a decorator or a function.
    """
    def wrap(f):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    if fn is None:
        return wrap
    return wrap(fn)


def tp_shard_map(mesh, in_specs, out_specs):
    return functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs)


def set_random_seed(seed: int):
    """Reference commons.py set_random_seed: seed all RNG streams."""
    np.random.seed(seed)
    from apex_tpu.transformer.tensor_parallel.random import (
        model_parallel_xla_manual_seed,
    )

    model_parallel_xla_manual_seed(seed)
    return jax.random.PRNGKey(seed)
