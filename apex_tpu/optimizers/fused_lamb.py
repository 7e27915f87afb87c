"""FusedLAMB — layer-wise adaptive moments with trust ratio.

Parity: reference apex/optimizers/fused_lamb.py:4-215: global grad norm via
two ``multi_tensor_l2norm`` calls (124-133), then one fused lamb update with
per-layer trust ratios and global grad clipping (183-199).
"""

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.ops import multi_tensor_l2norm, multi_tensor_lamb
from apex_tpu.optimizers._base import (
    FusedOptimizerBase,
    resolve_found_inf,
    zeros_like_tree,
)


class FusedLAMB(FusedOptimizerBase):
    def __init__(self, lr=1e-3, bias_correction=True, betas=(0.9, 0.999),
                 eps=1e-6, weight_decay=0.01, amsgrad=False,
                 adam_w_mode=True, grad_averaging=True, set_grad_none=True,
                 max_grad_norm=1.0, use_nvlamb=False):
        if amsgrad:
            raise RuntimeError("FusedLAMB does not support the AMSGrad variant.")
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.grad_averaging = grad_averaging
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb

    def init(self, params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "exp_avg": zeros_like_tree(params),
            "exp_avg_sq": zeros_like_tree(params),
        }

    @jax.named_scope("fused_lamb")
    def step(self, grads, state, params, *, lr: Optional[float] = None,
             found_inf=None, scale: float = 1.0):
        lr = self.lr if lr is None else lr
        noop = resolve_found_inf(found_inf)
        step = state["step"] + jnp.where(noop > 0, 0, 1).astype(jnp.int32)
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        g_leaves = [g.astype(jnp.float32) / scale for g in g_leaves]
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = treedef.flatten_up_to(state["exp_avg"])
        v_leaves = treedef.flatten_up_to(state["exp_avg_sq"])
        # Global grad norm (reference fused_lamb.py:124-133 computes one norm
        # per dtype bucket then combines; with fp32 grads one call suffices).
        gnorm, _ = multi_tensor_applier(multi_tensor_l2norm, noop, [g_leaves])
        mode = 1 if self.adam_w_mode else 0
        new_p, new_m, new_v, _ = multi_tensor_applier(
            multi_tensor_lamb, noop, [g_leaves, p_leaves, m_leaves, v_leaves],
            lr, self.betas[0], self.betas[1], self.eps, step,
            self.bias_correction, self.weight_decay, self.grad_averaging,
            mode, gnorm, self.max_grad_norm, self.use_nvlamb)
        return (
            jax.tree_util.tree_unflatten(treedef, new_p),
            {"step": step,
             "exp_avg": jax.tree_util.tree_unflatten(treedef, new_m),
             "exp_avg_sq": jax.tree_util.tree_unflatten(treedef, new_v)},
        )
