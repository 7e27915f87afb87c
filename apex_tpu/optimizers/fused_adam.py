"""FusedAdam — Adam/AdamW over the whole parameter pytree in one fused step.

Parity: reference apex/optimizers/fused_adam.py:4-271 (``adam_w_mode``,
``bias_correction``, ``capturable`` semantics, ``master_weights``). On TPU
the step is always jit-compiled, so the ``capturable`` distinction
disappears: learning rate and step count live on-device and overflow skip is
branch-free.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.ops import multi_tensor_adam, multi_tensor_adam_capturable_master
from apex_tpu.optimizers._base import (
    FusedOptimizerBase,
    master_copy_tree,
    resolve_found_inf,
    zeros_like_tree,
)


class FusedAdam(FusedOptimizerBase):
    """Adam/AdamW.

    Args mirror the reference (apex/optimizers/fused_adam.py:60-103):
      lr, bias_correction, betas, eps, adam_w_mode, weight_decay, amsgrad
      (unsupported, as in the reference), set_grad_none (meaningless in JAX),
      capturable (always-on under jit), master_weights (keep fp32 masters for
      low-precision params).
    """

    def __init__(self, lr=1e-3, bias_correction=True, betas=(0.9, 0.999),
                 eps=1e-8, adam_w_mode=True, weight_decay=0.0, amsgrad=False,
                 set_grad_none=True, capturable=True, master_weights=False):
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.master_weights = master_weights

    def init(self, params):
        state = {
            "step": jnp.zeros((), jnp.int32),
            "exp_avg": zeros_like_tree(params),
            "exp_avg_sq": zeros_like_tree(params),
        }
        if self.master_weights:
            state["master"] = master_copy_tree(params)
        return state

    @jax.named_scope("fused_adam")
    def step(self, grads, state, params, *, lr: Optional[float] = None,
             found_inf=None, scale: float = 1.0):
        lr = self.lr if lr is None else lr
        noop = resolve_found_inf(found_inf)
        # Step only advances on non-overflow iterations (capturable semantics,
        # reference fused_adam.py:196-204).
        step = state["step"] + jnp.where(noop > 0, 0, 1).astype(jnp.int32)
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = treedef.flatten_up_to(state["exp_avg"])
        v_leaves = treedef.flatten_up_to(state["exp_avg_sq"])
        mode = 1 if self.adam_w_mode else 0
        inv_scale = 1.0 / scale
        if self.master_weights:
            mw_leaves = treedef.flatten_up_to(state["master"])
            new_p, new_m, new_v, new_mw, _ = multi_tensor_applier(
                multi_tensor_adam_capturable_master, noop,
                [g_leaves, p_leaves, m_leaves, v_leaves, mw_leaves],
                lr, self.betas[0], self.betas[1], self.eps, step, mode,
                self.bias_correction, self.weight_decay, inv_scale)
        else:
            g_leaves = [g.astype(jnp.float32) * inv_scale for g in g_leaves]
            new_p, new_m, new_v, _ = multi_tensor_applier(
                multi_tensor_adam, noop,
                [g_leaves, p_leaves, m_leaves, v_leaves],
                lr, self.betas[0], self.betas[1], self.eps, step, mode,
                self.bias_correction, self.weight_decay)
        new_state = {
            "step": step,
            "exp_avg": jax.tree_util.tree_unflatten(treedef, new_m),
            "exp_avg_sq": jax.tree_util.tree_unflatten(treedef, new_v),
        }
        if self.master_weights:
            new_state["master"] = jax.tree_util.tree_unflatten(treedef, new_mw)
        return jax.tree_util.tree_unflatten(treedef, new_p), new_state
