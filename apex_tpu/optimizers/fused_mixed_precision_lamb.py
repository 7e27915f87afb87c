"""FusedMixedPrecisionLamb — LAMB with fp32 master state for low-precision
params and grad-scaler integration.

Parity: reference apex/optimizers/fused_mixed_precision_lamb.py:8-256
(``multi_tensor_lamb_mp`` with found_inf/inv_scale tensors, fp32 master
copies of bf16/fp16 params, step advanced only on clean iterations).
"""

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.ops import multi_tensor_l2norm_scale, multi_tensor_lamb_mp
from apex_tpu.optimizers._base import (
    FusedOptimizerBase,
    master_copy_tree,
    resolve_found_inf,
    zeros_like_tree,
)


class FusedMixedPrecisionLamb(FusedOptimizerBase):
    def __init__(self, lr=1e-3, step=0, bias_correction=True,
                 betas=(0.9, 0.999), eps=1e-6, weight_decay=0.01,
                 amsgrad=False, adam_w_mode=True, grad_averaging=True,
                 set_grad_none=True, max_grad_norm=1.0, use_nvlamb=False,
                 reduced_precision_dtype=None):
        if amsgrad:
            raise RuntimeError("FusedMixedPrecisionLamb does not support AMSGrad.")
        self.lr = lr
        self.initial_step = step
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
            "step": jnp.asarray(self.initial_step, jnp.int32),
            "exp_avg": zeros_like_tree(params),
            "exp_avg_sq": zeros_like_tree(params),
            "master": master_copy_tree(params),
        }

    @jax.named_scope("fused_mixed_precision_lamb")
    def step(self, grads, state, params, *, lr: Optional[float] = None,
             found_inf=None, scale: float = 1.0):
        lr = self.lr if lr is None else lr
        noop = resolve_found_inf(found_inf)
        step = state["step"] + jnp.where(noop > 0, 0, 1).astype(jnp.int32)
        inv_scale = 1.0 / scale
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = treedef.flatten_up_to(state["exp_avg"])
        v_leaves = treedef.flatten_up_to(state["exp_avg_sq"])
        mw_leaves = treedef.flatten_up_to(state["master"])
        gnorm, _ = multi_tensor_applier(
            multi_tensor_l2norm_scale, noop, [g_leaves], inv_scale)
        mode = 1 if self.adam_w_mode else 0
        new_p, new_m, new_v, new_mw, _ = multi_tensor_applier(
            multi_tensor_lamb_mp, noop,
            [g_leaves, p_leaves, m_leaves, v_leaves, mw_leaves],
            lr, self.betas[0], self.betas[1], self.eps, step,
            self.bias_correction, self.weight_decay, self.grad_averaging,
            mode, gnorm, self.max_grad_norm, self.use_nvlamb, noop, inv_scale)
        return (
            jax.tree_util.tree_unflatten(treedef, new_p),
            {"step": step,
             "exp_avg": jax.tree_util.tree_unflatten(treedef, new_m),
             "exp_avg_sq": jax.tree_util.tree_unflatten(treedef, new_v),
             "master": jax.tree_util.tree_unflatten(treedef, new_mw)},
        )
