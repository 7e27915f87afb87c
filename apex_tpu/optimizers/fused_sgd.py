"""FusedSGD — SGD with momentum in one fused step.

Parity: reference apex/optimizers/fused_sgd.py:6-227 (momentum, dampening,
nesterov, weight_decay, wd_after_momentum, materialize_master_grads). The
reference unscales fp16 grads *inside* the step when driven by amp
(fused_sgd.py:148-209); here that is the ``scale`` argument.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.ops import multi_tensor_sgd
from apex_tpu.optimizers._base import (
    FusedOptimizerBase,
    resolve_found_inf,
    zeros_like_tree,
)


class FusedSGD(FusedOptimizerBase):
    def __init__(self, lr=None, momentum=0.0, dampening=0.0, weight_decay=0.0,
                 nesterov=False, wd_after_momentum=False,
                 materialize_master_grads=True, set_grad_none=False):
        if lr is None:
            raise ValueError("FusedSGD requires a learning rate")
        if nesterov and (momentum <= 0 or dampening != 0):
            raise ValueError("Nesterov momentum requires a momentum and zero dampening")
        self.lr = lr
        self.momentum = momentum
        self.dampening = dampening
        self.weight_decay = weight_decay
        self.nesterov = nesterov
        self.wd_after_momentum = wd_after_momentum

    def init(self, params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "momentum_buffer": zeros_like_tree(params),
        }

    @jax.named_scope("fused_sgd")
    def step(self, grads, state, params, *, lr: Optional[float] = None,
             found_inf=None, scale: float = 1.0):
        lr = self.lr if lr is None else lr
        noop = resolve_found_inf(found_inf)
        step = state["step"] + jnp.where(noop > 0, 0, 1).astype(jnp.int32)
        first_run = state["step"] == 0
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = treedef.flatten_up_to(state["momentum_buffer"])
        new_p, new_m, _ = multi_tensor_applier(
            multi_tensor_sgd, noop, [g_leaves, p_leaves, m_leaves],
            self.weight_decay, self.momentum, self.dampening, lr,
            self.nesterov, first_run, self.wd_after_momentum, 1.0 / scale)
        return (
            jax.tree_util.tree_unflatten(treedef, new_p),
            {"step": step,
             "momentum_buffer": jax.tree_util.tree_unflatten(treedef, new_m)},
        )
