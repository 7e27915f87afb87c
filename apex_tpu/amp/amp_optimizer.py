"""AmpOptimizer — the optimizer wrapper produced by ``amp.initialize``.

Parity: reference apex/amp/_process_optimizer.py:321-489, which attaches
master-weight management and grad unscale hooks to a torch optimizer. Here
the same responsibilities are one functional stepper:

    state = opt.init(params)
    new_params, new_state = opt.step(grads, state, params)

per step it (1) unscales grads by the live loss scale, (2) detects
inf/nan, (3) runs the wrapped optimizer's update branch-free-skipped on
overflow (reference handle.py:128-154 step patching), (4) updates the
dynamic scaler state, (5) for O2, keeps fp32 master weights and re-casts
into the low-precision model params (reference
_process_optimizer.py:28-90 ``lazy_init_with_master_weights``).
"""

import jax
import jax.numpy as jnp

from apex_tpu.amp.scaler import LossScaler, ScalerState
from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.ops import multi_tensor_scale


class AmpOptimizer(object):
    def __init__(self, optimizer, scaler: LossScaler, master_weights=False,
                 model_dtype=None):
        self.inner = optimizer
        self.scaler = scaler
        self.master_weights = master_weights
        self.model_dtype = model_dtype
        self.last_state = None

    # accessors forwarded for parity with torch optimizer interface
    @property
    def lr(self):
        return self.inner.lr

    def init(self, params):
        inner_state = self.inner.init(params)
        # If the wrapped optimizer maintains its own fp32 masters
        # (e.g. FusedAdam(master_weights=True)), defer to it entirely.
        # Amp-owned masters live under a distinct key so ownership is
        # derivable from a (possibly checkpoint-restored) state alone.
        if self.master_weights and "master" not in inner_state:
            # alias-free copy: astype is a no-op on already-fp32 leaves
            # (all norm params under O2) and would alias masters to the
            # live params — donating both then trips XLA's
            # donate-same-buffer-twice check (the double-donation lint
            # rule in apex_tpu.analysis catches this at trace time)
            from apex_tpu.optimizers._base import master_copy_tree

            inner_state["amp_master"] = master_copy_tree(params)
        return {"inner": inner_state, "scaler": self.scaler.init_state()}

    def step(self, grads, state, params, *, lr=None):
        scaler_state: ScalerState = state["scaler"]
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        with jax.named_scope("amp/unscale"):
            inv = 1.0 / scaler_state.loss_scale
            unscaled, found_inf = multi_tensor_applier(
                multi_tensor_scale, jnp.zeros((), jnp.float32),
                [leaves, leaves], inv)
        grads = jax.tree_util.tree_unflatten(treedef, unscaled)

        if "amp_master" in state["inner"]:
            # Update runs on fp32 masters; model params are re-cast copies.
            masters = state["inner"]["amp_master"]
            inner_wo_master = {k: v for k, v in state["inner"].items()
                               if k != "amp_master"}
            with jax.named_scope("optimizer"):
                new_masters, new_inner = self.inner.step(
                    grads, inner_wo_master, masters, lr=lr,
                    found_inf=found_inf)
            with jax.named_scope("amp/master_to_model"):
                new_params = jax.tree_util.tree_map(
                    lambda m, p: m.astype(p.dtype), new_masters, params)
            new_inner["amp_master"] = new_masters
        else:
            with jax.named_scope("optimizer"):
                new_params, new_inner = self.inner.step(
                    grads, state["inner"], params, lr=lr,
                    found_inf=found_inf)

        with jax.named_scope("amp/scaler_update"):
            new_scaler = self.scaler.update(scaler_state, found_inf)
        new_state = {"inner": new_inner, "scaler": new_scaler}
        self.last_state = new_state
        return new_params, new_state

    @jax.named_scope("loss")
    def scale_loss(self, loss, state=None):
        sstate = state["scaler"] if state is not None else self.scaler._state
        return loss.astype(jnp.float32) * sstate.loss_scale

    # torch-optimizer-style checkpoint hooks
    def state_dict(self):
        return {"scaler": self.scaler.state_dict()}

    def load_state_dict(self, sd):
        self.scaler.load_state_dict(sd["scaler"])
