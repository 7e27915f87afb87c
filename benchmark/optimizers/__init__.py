"""One module per optimizer, found by the ``optimizer`` a train mix
names: ``benchmark/optimizers/<name>.py``. Each supplies the published
update in plain float32 on the canonical flat dict, for the reference
(``init(params)``, ``step(params, grads, state, hp)``,
``first_gradient(state, hp)``: the first gradient as the optimizer got
it, from the state after step one) and, towards the program,
``program(hp)``, its optimizer for the same hyper-parameters (it alone
imports ``apex_tpu``, inside the function), and
``program_first_gradient(opt_state, hp)``, the same reading from the
program's state. A "tensor" is one layer's slice of a stacked leaf.
"""

import importlib

import jax.numpy as jnp


def of(name: str):
    return importlib.import_module(f"benchmark.optimizers.{name}")


def adam_moments(g, m, v, t, hp):
    """-> (bias-corrected Adam direction, new m, new v)."""
    b1, b2 = hp["betas"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    return ((m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hp["eps"]),
            m, v)


def init_moments(params):
    return {"step": 0,
            "m": {k: jnp.zeros_like(v) for k, v in params.items()},
            "v": {k: jnp.zeros_like(v) for k, v in params.items()}}


def first_gradient_from_moment(state, hp):
    """``exp_avg / (1 - beta1)`` after step one: the same formula reads
    the program's state and the reference's."""
    return {k: m / (1 - hp["betas"][0]) for k, m in state["m"].items()}


def fused_first_gradient(opt_state, hp):
    """The same from the state of the program's ``Fused*`` optimizers
    under ``amp`` (a tree shaped like the parameters)."""
    import jax

    return jax.tree_util.tree_map(lambda m: m / (1 - hp["betas"][0]),
                                  opt_state["inner"]["exp_avg"])
