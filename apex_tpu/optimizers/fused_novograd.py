"""FusedNovoGrad — NovoGrad with layer-wise second moments.

Parity: reference apex/optimizers/fused_novograd.py:4-214 (``reg_inside_moment``,
``grad_averaging``, ``norm_type``, ``init_zero``).
"""

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.ops import multi_tensor_novograd
from apex_tpu.optimizers._base import (
    FusedOptimizerBase,
    resolve_found_inf,
    zeros_like_tree,
)


class FusedNovoGrad(FusedOptimizerBase):
    def __init__(self, lr=1e-3, bias_correction=True, betas=(0.9, 0.999),
                 eps=1e-8, weight_decay=0.0, amsgrad=False,
                 reg_inside_moment=False, grad_averaging=True, norm_type=2,
                 init_zero=False, set_grad_none=True):
        if amsgrad:
            raise RuntimeError("FusedNovoGrad does not support the AMSGrad variant.")
        if norm_type not in (2, float("inf")):
            raise RuntimeError("FusedNovoGrad only supports l2/inf norm")
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.weight_decay = weight_decay
        # moment_mode 0: wd inside the moment accumulation; 1: decoupled
        # (reference fused_novograd.py maps reg_inside_moment -> moment_mode).
        self.moment_mode = 0 if reg_inside_moment else 1
        self.grad_averaging = grad_averaging
        self.norm_type = norm_type
        self.init_zero = init_zero

    def init(self, params):
        n = len(jax.tree_util.tree_leaves(params))
        return {
            "step": jnp.zeros((), jnp.int32),
            "exp_avg": zeros_like_tree(params),
            "exp_avg_sq": jnp.zeros((n,), jnp.float32),
        }

    @jax.named_scope("fused_novograd")
    def step(self, grads, state, params, *, lr: Optional[float] = None,
             found_inf=None, scale: float = 1.0):
        lr = self.lr if lr is None else lr
        noop = resolve_found_inf(found_inf)
        step = state["step"] + jnp.where(noop > 0, 0, 1).astype(jnp.int32)
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        g_leaves = [g.astype(jnp.float32) / scale for g in g_leaves]
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = treedef.flatten_up_to(state["exp_avg"])
        norm_code = 2 if self.norm_type == 2 else 0
        new_p, new_m, new_v, _ = multi_tensor_applier(
            multi_tensor_novograd, noop,
            [g_leaves, p_leaves, m_leaves, state["exp_avg_sq"]],
            lr, self.betas[0], self.betas[1], self.eps, step,
            self.bias_correction, self.weight_decay, self.grad_averaging,
            self.moment_mode, norm_code, self.init_zero)
        return (
            jax.tree_util.tree_unflatten(treedef, new_p),
            {"step": step,
             "exp_avg": jax.tree_util.tree_unflatten(treedef, new_m),
             "exp_avg_sq": new_v},
        )
