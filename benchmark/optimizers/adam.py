"""Adam (Kingma & Ba 2015) with bias correction; ``weight_decay``
decoupled (AdamW). The program's: ``apex_tpu.optimizers.FusedAdam``."""

from benchmark.optimizers import (adam_moments, first_gradient_from_moment,
                                  fused_first_gradient, init_moments)

init = init_moments
first_gradient = first_gradient_from_moment
program_first_gradient = fused_first_gradient


def step(params, grads, state, hp):
    t = state["step"] + 1
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        update, new_m[k], new_v[k] = adam_moments(
            grads[k], state["m"][k], state["v"][k], t, hp)
        if hp["weight_decay"]:
            update = update + hp["weight_decay"] * p
        new_p[k] = p - hp["lr"] * update
    return new_p, {"step": t, "m": new_m, "v": new_v}


def program(hp):
    from apex_tpu.optimizers import FusedAdam

    return FusedAdam(lr=hp["lr"], betas=tuple(hp["betas"]), eps=hp["eps"],
                     weight_decay=hp["weight_decay"])
