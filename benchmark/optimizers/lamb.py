"""LAMB (You et al. 2020) as NVIDIA's recipe runs it: gradients divided
by ``max(global_norm / max_grad_norm, 1)``, Adam direction with bias
correction plus decoupled weight decay, the step scaled per tensor by
``|w| / |update|``. The program's: ``apex_tpu.optimizers.FusedLAMB``."""

import jax.numpy as jnp

from benchmark.optimizers import (adam_moments, first_gradient_from_moment,
                                  fused_first_gradient, init_moments)

init = init_moments
first_gradient = first_gradient_from_moment
program_first_gradient = fused_first_gradient


def _per_tensor_norm(name, x):
    """L2 norm per tensor: per layer for stacked leaves, broadcastable."""
    if name.startswith("layers."):
        axes = tuple(range(1, x.ndim))
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True))
    return jnp.sqrt(jnp.sum(jnp.square(x)))


def step(params, grads, state, hp):
    t = state["step"] + 1
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    clip = jnp.maximum(gnorm / hp["max_grad_norm"], 1.0)
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        update, new_m[k], new_v[k] = adam_moments(
            grads[k] / clip, state["m"][k], state["v"][k], t, hp)
        update = update + hp["weight_decay"] * p
        w_norm = _per_tensor_norm(k, p)
        u_norm = _per_tensor_norm(k, update)
        trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        new_p[k] = p - hp["lr"] * trust * update
    return new_p, {"step": t, "m": new_m, "v": new_v}


def program(hp):
    from apex_tpu.optimizers import FusedLAMB

    return FusedLAMB(lr=hp["lr"], betas=tuple(hp["betas"]), eps=hp["eps"],
                     weight_decay=hp["weight_decay"],
                     max_grad_norm=hp["max_grad_norm"])
