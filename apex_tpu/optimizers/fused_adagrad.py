"""FusedAdagrad. Parity: reference apex/optimizers/fused_adagrad.py:5-121
(``adagrad_w_mode`` decoupled weight decay)."""

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.ops import multi_tensor_adagrad
from apex_tpu.optimizers._base import (
    FusedOptimizerBase,
    resolve_found_inf,
    zeros_like_tree,
)


class FusedAdagrad(FusedOptimizerBase):
    def __init__(self, lr=1e-2, eps=1e-10, weight_decay=0.0,
                 set_grad_none=True, adagrad_w_mode=False):
        self.lr = lr
        self.eps = eps
        self.weight_decay = weight_decay
        self.adagrad_w_mode = adagrad_w_mode

    def init(self, params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "sum": zeros_like_tree(params),
        }

    @jax.named_scope("fused_adagrad")
    def step(self, grads, state, params, *, lr: Optional[float] = None,
             found_inf=None, scale: float = 1.0):
        lr = self.lr if lr is None else lr
        noop = resolve_found_inf(found_inf)
        step = state["step"] + jnp.where(noop > 0, 0, 1).astype(jnp.int32)
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        g_leaves = [g.astype(jnp.float32) / scale for g in g_leaves]
        p_leaves = treedef.flatten_up_to(params)
        h_leaves = treedef.flatten_up_to(state["sum"])
        mode = 1 if self.adagrad_w_mode else 0
        new_p, new_h, _ = multi_tensor_applier(
            multi_tensor_adagrad, noop, [g_leaves, p_leaves, h_leaves],
            lr, self.eps, mode, self.weight_decay)
        return (
            jax.tree_util.tree_unflatten(treedef, new_p),
            {"step": step, "sum": jax.tree_util.tree_unflatten(treedef, new_h)},
        )
