"""Expert-parallel Switch/GShard MLP layer.

Dataflow per device (T local tokens, E global experts, C slots/expert,
ep-way expert parallelism, tp-way tensor parallelism inside each expert):

    [s, b, h] -> [T, h] -> router -> dispatch [T, E, C]
    einsum dispatch: [E, C, h]
    all_to_all over 'ep': [E/ep, ep*C, h]     (experts gain all ranks' slots)
    grouped FFN (einsum over leading E/ep dim; ffn dim sharded over 'tp')
    all_to_all back: [E, C, h]
    einsum combine: [T, h] -> [s, b, h]

Everything is static-shaped; dropped tokens get zero combine weight and
ride the residual. Expert weights are per-(ep, tp)-rank shards initialized
from rank-folded keys (the partitioned-init discipline of
tensor_parallel/layers.py); dense params (router gate) replicate over ep
and must be grad-synced over the full dp x ep set — see
``parallel_state.get_data_parallel_axes`` and ``is_expert_param``.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.kernels.grouped_matmul import grouped_matmul
from apex_tpu.kernels.row_gather import (
    gather_rows,
    row_tile,
    scatter_add_rows,
)
from apex_tpu.transformer.moe.router import (
    TopKRouter,
    expert_capacity,
    sequence_balance_loss,
)
from apex_tpu.transformer.parallel_state import (
    EXPERT_PARALLEL_AXIS,
    TENSOR_PARALLEL_AXIS,
    get_expert_model_parallel_world_size,
    get_tensor_model_parallel_world_size,
)
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    reduce_from_tensor_model_parallel_region,
    scatter_to_sequence_parallel_region,
)
from apex_tpu.transformer.tensor_parallel.utils import divide


def moe_loss_from_variables(variables, aux_loss_coeff: float = 1e-2,
                            z_loss_coeff: float = 0.0):
    """Total auxiliary MoE loss from the 'moe_losses' collection returned
    by ``model.apply(..., mutable=["moe_losses"])``. Accepts either the
    full mutated-variables dict or the collection itself."""
    import flax

    losses = variables.get("moe_losses", variables)
    aux = jnp.zeros((), jnp.float32)
    z = jnp.zeros((), jnp.float32)
    for path, val in flax.traverse_util.flatten_dict(dict(losses)).items():
        total = sum(val) if isinstance(val, (tuple, list)) else val
        total = jnp.sum(total)  # scan-stacked layers sow [L]-shaped entries
        if path[-1] == "aux_loss":
            aux = aux + total
        elif path[-1] == "z_loss":
            z = z + total
    return aux_loss_coeff * aux + z_loss_coeff * z


def sown_total(variables, name: str):
    """The sum over layers of the scalars sown under ``name`` into the
    ``moe_losses`` collection of ``model.apply(...,
    mutable=["moe_losses"])`` (the mutated-variables dict or the
    collection itself; scan-stacked layers sow ``[L]``-shaped entries)."""
    import flax

    losses = variables.get("moe_losses", variables)
    total = jnp.zeros((), jnp.float32)
    for path, val in flax.traverse_util.flatten_dict(dict(losses)).items():
        if path[-1] == name:
            total = total + jnp.sum(
                sum(val) if isinstance(val, (tuple, list)) else val)
    return total


def seq_aux_loss_from_variables(variables):
    """The sum over layers of the sequence-wise balance losses
    (``seq_aux_loss``, sown where ``SwitchMLP.seq_aux_loss`` is on); the
    caller multiplies by its coefficient
    (``TransformerConfig.moe_seq_aux_loss_coeff``)."""
    return sown_total(variables, "seq_aux_loss")


_WARNED_DROPPED_LOSSES = False


def _warn_dropped_losses_once():
    global _WARNED_DROPPED_LOSSES
    if _WARNED_DROPPED_LOSSES:
        return
    _WARNED_DROPPED_LOSSES = True
    import warnings

    warnings.warn(
        "SwitchMLP router aux/z losses were discarded: apply the model "
        "with mutable=['moe_losses'] and add moe_loss_from_variables(...) "
        "to the training loss (for inference/eval, construct with "
        "warn_on_dropped_losses=False).", stacklevel=3)


def is_expert_param(path: str) -> bool:
    """Param-path predicate: expert shards (different on every ep/tp rank)
    vs dense params. Grad-sync rule: expert params average over 'dp' only;
    dense params over ``get_data_parallel_axes()`` (dp and ep). Matches the
    whole 'experts' path segment (a user module merely *containing* the
    substring, e.g. 'experts_gate', holds dense params)."""
    return "experts" in path.split("/")


def _expert_rank_key(key):
    """Fold ep and tp ranks into an init key so every expert shard draws
    distinct weights (partitioned-init parity, tensor_parallel/layers.py:76)."""
    for axis in (EXPERT_PARALLEL_AXIS, TENSOR_PARALLEL_AXIS):
        try:
            rank = lax.axis_index(axis)
        except Exception:
            rank = 0
        key = jax.random.fold_in(key, rank)
    return key


class ExpertMLP(nn.Module):
    """Grouped FFN over experts: h -> ffn/tp -> h per expert, activation
    in fp32, tp-reduced output. Two input layouts, identical params:

    - slotted [E_local, S, h] (default): per-expert einsum over the
      leading dim — the all_to_all-compatible layout.
    - ragged [N, h] with ``group_sizes`` [E_local] (rows grouped by
      expert, consecutively; rows past their sum belong to no expert and
      give zeros): ``kernels.grouped_matmul`` — zero capacity padding,
      the dropless serving layout. On a TPU, from one row tile of rows
      up, a Pallas grouped matmul over the row tiles that hold rows;
      ``lax.ragged_dot`` (XLA's TPU grouped-matmul kernel), its oracle,
      elsewhere.

    ``activation="swiglu"`` makes w1 a fused per-rank [gate | up]
    projection (2 * ffn/tp local columns, bias-free — the Llama/Mixtral
    expert shape); "gelu" is the Switch-Transformer shape with biases
    (ragged layout gathers per-row biases via ``expert_idx``); "relu2"
    is the ungated ``w2 relu(w1 x)^2`` without biases (Nemotron-H).
    """

    hidden_size: int
    ffn_hidden_size: int
    num_local_experts: int
    activation: str = "gelu"
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, group_sizes=None, expert_idx=None):
        tp = get_tensor_model_parallel_world_size()
        ffn_local = divide(self.ffn_hidden_size, tp)
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        swiglu = self.activation == "swiglu"
        relu2 = self.activation == "relu2"
        biased = not (swiglu or relu2)
        if biased and self.activation != "gelu":
            raise ValueError(f"unknown activation {self.activation!r}")
        ragged = group_sizes is not None
        if biased and ragged and expert_idx is None:
            raise ValueError("ragged gelu experts need expert_idx for "
                             "per-row bias gathers")

        def shard_init(key, shape, dtype):
            return init(_expert_rank_key(key), shape, dtype)

        w1 = self.param("w1", shard_init,
                        (self.num_local_experts, self.hidden_size,
                         ffn_local * (2 if swiglu else 1)),
                        self.params_dtype)
        w2 = self.param("w2", shard_init,
                        (self.num_local_experts, ffn_local, self.hidden_size),
                        self.params_dtype)
        if biased:
            b1 = self.param("b1", nn.initializers.zeros,
                            (self.num_local_experts, ffn_local),
                            self.params_dtype)
            b2 = self.param("b2", nn.initializers.zeros,
                            (self.num_local_experts, self.hidden_size),
                            self.params_dtype)

        # Column-parallel in, row-parallel out (identity/psum vjp pairing).
        x = copy_to_tensor_model_parallel_region(x)
        x = x.astype(self.compute_dtype)
        if ragged:
            h1 = grouped_matmul(x, w1.astype(self.compute_dtype),
                                group_sizes)
        else:
            h1 = jnp.einsum("ech,ehf->ecf", x, w1.astype(self.compute_dtype),
                            preferred_element_type=jnp.float32)
        if swiglu:
            gate, up = jnp.split(h1, 2, axis=-1)
            a = (jax.nn.silu(gate) * up).astype(self.compute_dtype)
        elif relu2:
            a = jnp.square(jax.nn.relu(h1)).astype(self.compute_dtype)
        else:
            bias1 = (b1[expert_idx] if ragged else b1[:, None, :])
            h1 = h1 + bias1.astype(jnp.float32)
            a = jax.nn.gelu(h1).astype(self.compute_dtype)
        if ragged:
            y = grouped_matmul(a, w2.astype(self.compute_dtype),
                               group_sizes)
        else:
            y = jnp.einsum("ecf,efh->ech", a, w2.astype(self.compute_dtype),
                           preferred_element_type=jnp.float32)
        y = reduce_from_tensor_model_parallel_region(y)
        if not biased:
            return y
        bias2 = (b2[expert_idx] if ragged else b2[:, None, :])
        return y + bias2.astype(jnp.float32)


class SharedExpertMoE(nn.Module):
    """Routed SwitchMLP plus an always-on shared expert (the Qwen2-MoE
    block shape): out = routed(x) + sigmoid(gate(x)) * shared(x), the
    scalar sigmoid gate optional. The shared expert is a dense SwiGLU
    MLP (column-parallel fused [gate | up], row-parallel down) of its
    own width — distinct from DeepSeek's ungated shared expert, which
    lives in models/mla.py. With ``activation="relu2"`` routed and
    shared experts are both the ungated ``down(relu(up x)^2)``
    (Nemotron-H; params ``shared_up`` / ``shared_down``, scope
    ``moe/shared``), the shared one added unweighted where
    ``shared_expert_gated`` is off. ``local_experts``,
    ``expert_offset``, ``router_score`` and ``routed_scaling_factor`` go
    to the routed SwitchMLP: in its held-share mode the shared expert is
    whole on every rank. Aux losses sow through the nested SwitchMLP as
    usual."""

    hidden_size: int
    ffn_hidden_size: int            # routed expert width
    shared_expert_size: int         # shared expert width
    num_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    jitter_eps: float = 0.0
    normalize_topk: bool = True
    dispatch_mode: str = "auto"
    # the block shape is tied to top-k routing over SwiGLU experts; other
    # router/activation combinations raise rather than silently ignore
    # the request (a config-driven caller would otherwise train a
    # different model than it asked for)
    router_type: str = "top_k"
    activation: str = "swiglu"
    shared_expert_gated: bool = True
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    sequence_parallel_enabled: bool = False
    warn_on_dropped_losses: bool = True
    local_experts: Optional[int] = None
    expert_offset: int = 0
    router_score: str = "softmax"
    routed_scaling_factor: float = 1.0
    seq_aux_loss: bool = False

    @nn.compact
    def __call__(self, hidden_states):
        from apex_tpu.transformer.tensor_parallel.layers import (
            ColumnParallelLinear,
            RowParallelLinear,
        )

        if self.router_type != "top_k":
            raise ValueError(
                f"SharedExpertMoE supports top_k routing only, got "
                f"{self.router_type!r}")
        if self.activation not in ("swiglu", "relu2"):
            raise ValueError(
                f"SharedExpertMoE experts are SwiGLU (the Qwen2-MoE "
                f"shape) or relu2 (Nemotron-H), got activation "
                f"{self.activation!r}")
        routed = SwitchMLP(
            hidden_size=self.hidden_size,
            ffn_hidden_size=self.ffn_hidden_size,
            num_experts=self.num_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            jitter_eps=self.jitter_eps,
            normalize_topk=self.normalize_topk,
            dispatch_mode=self.dispatch_mode, activation=self.activation,
            params_dtype=self.params_dtype,
            compute_dtype=self.compute_dtype,
            sequence_parallel_enabled=self.sequence_parallel_enabled,
            warn_on_dropped_losses=self.warn_on_dropped_losses,
            local_experts=self.local_experts,
            expert_offset=self.expert_offset,
            router_score=self.router_score,
            routed_scaling_factor=self.routed_scaling_factor,
            seq_aux_loss=self.seq_aux_loss,
            name="routed")(hidden_states)

        x = hidden_states.astype(self.compute_dtype)
        if self.activation == "relu2":
            with jax.named_scope("moe/shared"):
                up = ColumnParallelLinear(
                    input_size=self.hidden_size,
                    output_size=self.shared_expert_size,
                    gather_output=False, bias=False,
                    sequence_parallel_enabled=self.sequence_parallel_enabled,
                    params_dtype=self.params_dtype, name="shared_up")(x)
                h = jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(
                    self.compute_dtype)
                shared = RowParallelLinear(
                    input_size=self.shared_expert_size,
                    output_size=self.hidden_size, input_is_parallel=True,
                    bias=False,
                    sequence_parallel_enabled=self.sequence_parallel_enabled,
                    params_dtype=self.params_dtype, name="shared_down")(h)
            return routed + self._gated(shared, x).astype(routed.dtype)
        with jax.named_scope("moe/shared"):
            gate_up = ColumnParallelLinear(
                input_size=self.hidden_size,
                output_size=2 * self.shared_expert_size,
                gather_output=False, bias=False,
                sequence_parallel_enabled=self.sequence_parallel_enabled,
                params_dtype=self.params_dtype, name="shared_gate_up")(x)
            g, up = jnp.split(gate_up.astype(jnp.float32), 2, axis=-1)
            h = (jax.nn.silu(g) * up).astype(self.compute_dtype)
            shared = RowParallelLinear(
                input_size=self.shared_expert_size,
                output_size=self.hidden_size, input_is_parallel=True,
                bias=False,
                sequence_parallel_enabled=self.sequence_parallel_enabled,
                params_dtype=self.params_dtype, name="shared_down")(h)
        return routed + self._gated(shared, x).astype(routed.dtype)

    def _gated(self, shared, x):
        if not self.shared_expert_gated:
            return shared
        gate_w = self.param("shared_expert_gate", nn.initializers.zeros,
                            (self.hidden_size, 1), self.params_dtype)
        scale = jax.nn.sigmoid(
            (x.astype(jnp.float32) @ gate_w.astype(jnp.float32)))
        return shared * scale.astype(shared.dtype)


class SwitchMLP(nn.Module):
    """Drop-in MoE replacement for ParallelMLP (Megatron names this
    SwitchMLP). Sows 'aux_loss'/'z_loss' into the 'moe_losses' collection;
    apply with ``mutable=["moe_losses"]`` to collect them.

    ``dispatch_mode`` picks the dispatch/combine algorithm:

    - "einsum": dense [T, E, C] one-hot einsums. O(T*E*C) — quadratic in
      T once C ~ T (the dropless capacity serving converted checkpoints
      uses). Kept as the reference formulation and ep-compatible.
    - "scatter": sort assignments by expert, invert the slot map with an
      int scatter, dispatch/combine as gathers + one scatter-add.
      O(T log T + T*E) routing + O(T*h) data movement; same [E, C, h]
      slot layout, so expert parallelism (all_to_all) and capacity-drop
      semantics are unchanged — drop decisions are bit-identical to
      "einsum" (see compute_routing_sorted).
    - "ragged": no capacity slots at all — tokens sorted by expert feed
      ``lax.ragged_dot`` grouped matmuls ([k*T, h] rows, zero padding).
      Truly dropless and the fastest serving path; ep must be 1 (the
      all_to_all needs static per-rank splits).
    - "auto" (default): "scatter" when ep > 1 or when the capacity can
      actually drop tokens (capacity < T — preserving drop semantics),
      else "ragged". expert_choice routing always uses its dense path
      (C is small by design there).

    ``local_experts`` is the held-share mode: the layer holds that many
    of the ``num_experts`` it routes over, those from ``expert_offset``
    on, and no 'ep' mesh axis exists: one rank's share of an
    expert-parallel layer, run without its exchange. The router, its
    top-k, the gates' renormalisation and its losses are over all
    ``num_experts``; assignments to experts that are not held are dropped
    before the gather, and the output is the held experts' part of the
    routed sum (the shares of all ranks add up to the uncut layer's
    output). Ragged path only. The held assignments are gathered into
    ``capacity_factor`` times their expected number of rows (a static
    shape); what would not fit is dropped and shows in
    ``dropped_fraction``. Of those rows only the ones that hold an
    assignment are moved: dispatch and combine
    (``kernels.row_gather``) and the experts' matmuls
    (``kernels.grouped_matmul``) walk the row tiles below the count.
    Sows, beside the losses, ``held_assignments`` (the share of the k*T
    assignments that fell on held experts), ``held_load_max_over_mean``,
    ``held_dropped_fraction`` and ``held_row_tiles`` (the share of the
    row tiles walked).
    """

    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    jitter_eps: float = 0.0
    router_type: str = "top_k"  # or "expert_choice" (balanced, no aux)
    # renormalize the selected top-k gates to sum to 1 (Mixtral); False
    # keeps raw softmax mass (DeepSeek greedy gate, norm_topk_prob=False)
    normalize_topk: bool = True
    activation: str = "gelu"  # or "swiglu" (Llama/Mixtral-style experts)
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    sequence_parallel_enabled: bool = False
    dispatch_mode: str = "auto"  # auto | einsum | scatter | ragged
    # Warn (once per process) when aux losses are silently dropped because
    # the caller didn't pass mutable=["moe_losses"]; set False for
    # inference/eval modules where dropping them is intended.
    warn_on_dropped_losses: bool = True
    local_experts: Optional[int] = None
    expert_offset: int = 0
    # the router's scoring ("softmax" or "sigmoid_bias") and the factor
    # on its gates: TopKRouter.score, compute_routing_sorted
    router_score: str = "softmax"
    routed_scaling_factor: float = 1.0
    # sow ``seq_aux_loss``, the sequence-wise balance loss of the
    # sigmoid_bias router (router.py ``sequence_balance_loss``), beside
    # the other losses; off -> nothing of it is traced
    seq_aux_loss: bool = False

    def _resolve_dispatch(self, ep: int, capacity: int, num_tokens: int):
        mode = self.dispatch_mode
        if mode not in ("auto", "einsum", "scatter", "ragged"):
            raise ValueError(f"unknown dispatch_mode {mode!r}")
        if self.local_experts is not None:
            if (mode not in ("auto", "ragged") or ep > 1
                    or self.router_type != "top_k"):
                raise ValueError(
                    "local_experts (the held-share mode) runs the ragged "
                    "path of the top_k router without an 'ep' mesh axis")
            return "ragged"
        if self.router_type != "top_k":
            if mode in ("scatter", "ragged"):
                raise ValueError(
                    f"dispatch_mode {mode!r} requires the top_k router; "
                    "expert_choice routing has only its dense path")
            return "einsum"
        if mode == "auto":
            if ep > 1 or capacity < num_tokens:
                return "scatter"
            return "ragged"
        if mode == "ragged" and ep > 1:
            raise ValueError(
                "ragged dispatch has no static per-rank slot layout for "
                "the expert-parallel all_to_all; use 'scatter' with ep > 1")
        return mode

    @nn.compact
    def __call__(self, hidden_states):
        ep = get_expert_model_parallel_world_size()
        n_local = (divide(self.num_experts, ep) if self.local_experts is None
                   else self.local_experts)

        if self.sequence_parallel_enabled:
            # Full sequence on every tp rank; routing is deterministic so
            # tp ranks agree. The dispatch-path input grad is already
            # tp-psummed by the copy_to region inside ExpertMLP and the
            # router-path grad is tp-replicated, so the gather's backward
            # must be a plain split (tensor_parallel_output_grad=False),
            # and the exit below a plain scatter — a reduce-scatter pair
            # here would double-count by tp.
            hidden_states = gather_from_sequence_parallel_region(
                hidden_states, False)
        orig_shape = hidden_states.shape  # [s, b, h]
        tokens = hidden_states.reshape(-1, orig_shape[-1])

        num_tokens = tokens.shape[0]
        capacity = expert_capacity(num_tokens, self.num_experts, self.top_k,
                                   self.capacity_factor)
        mode = self._resolve_dispatch(ep, capacity, num_tokens)
        router = TopKRouter(
            num_experts=self.num_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor, jitter_eps=self.jitter_eps,
            router_type=self.router_type,
            normalize_topk=self.normalize_topk,
            routing_format={"einsum": "dense", "scatter": "sorted",
                            "ragged": "sorted_dropless"}[mode],
            score=self.router_score,
            routed_scaling_factor=self.routed_scaling_factor,
            params_dtype=self.params_dtype, name="router")
        with jax.named_scope("moe/router"):
            routing = router(tokens)
        sown = self.sow("moe_losses", "aux_loss", routing.aux_loss)
        self.sow("moe_losses", "z_loss", routing.z_loss)
        # observability, not a loss: moe_loss_from_variables sums only the
        # *_loss keys; watch this to tune capacity_factor
        self.sow("moe_losses", "dropped_fraction", routing.dropped_fraction)
        if self.seq_aux_loss:
            if self.router_score != "sigmoid_bias" or len(orig_shape) != 3:
                raise ValueError(
                    "seq_aux_loss is the sigmoid_bias router's, over "
                    "[s, b, h] hidden states")
            with jax.named_scope("moe/router"):
                self.sow("moe_losses", "seq_aux_loss", sequence_balance_loss(
                    routing.probs, routing.chosen, orig_shape[1]))
        if (not sown and not self.is_initializing()
                and self.warn_on_dropped_losses):
            # sow() into a non-mutable collection is a silent no-op; a
            # training step that forgets mutable=["moe_losses"] would run
            # with zero load-balancing pressure and collapse the router.
            _warn_dropped_losses_once()

        experts = ExpertMLP(
            hidden_size=self.hidden_size,
            ffn_hidden_size=self.ffn_hidden_size,
            num_local_experts=n_local, activation=self.activation,
            params_dtype=self.params_dtype,
            compute_dtype=self.compute_dtype, name="experts")
        x = tokens.astype(self.compute_dtype)
        hidden = orig_shape[-1]

        if self.local_experts is not None:
            # every pass over the rows walks the row tiles below ``kept``,
            # the rows that hold an assignment, as the grouped matmuls do
            token_idx, expert_idx, gate, counts, kept = self._held_share(
                routing, num_tokens)
            with jax.named_scope("moe/dispatch"):
                sorted_x = gather_rows(x, token_idx, kept)
            with jax.named_scope("moe/experts"):
                y = experts(sorted_x, group_sizes=counts,
                            expert_idx=expert_idx)
            with jax.named_scope("moe/combine"):
                out = scatter_add_rows(y, token_idx, kept, num_tokens,
                                       weights=gate)
        elif mode == "ragged":
            # Zero-padding dropless path: gather rows into expert-sorted
            # order (grad = scatter-add, the gather's XLA transpose), run
            # the grouped matmuls, weight by gate, scatter-add back.
            sorted_x = x[routing.token_idx]  # [N, h]
            y = experts(sorted_x, group_sizes=routing.counts,
                        expert_idx=routing.expert_idx)
            contrib = y.astype(jnp.float32) * routing.gate[:, None]
            out = jnp.zeros((num_tokens, hidden), jnp.float32)
            out = out.at[routing.token_idx].add(contrib)
        elif mode == "scatter":
            EC = self.num_experts * capacity
            # Invert slot -> source token with an int scatter (N int32
            # elements, not N*h floats), then dispatch is one gather.
            # Dropped assignments hit the sentinel row EC (discarded);
            # empty slots read the zero row appended at token index T.
            inv = jnp.full((EC + 1,), num_tokens, jnp.int32)
            inv = inv.at[routing.slot].set(routing.token_idx)
            x_pad = jnp.concatenate(
                [x, jnp.zeros((1, hidden), x.dtype)], axis=0)
            expert_in = x_pad[inv[:EC]].reshape(
                self.num_experts, capacity, hidden)
            if ep > 1:
                # [E, C, h] -> [E/ep, ep*C, h] (tiled: see einsum branch).
                expert_in = lax.all_to_all(expert_in, EXPERT_PARALLEL_AXIS,
                                           split_axis=0, concat_axis=1,
                                           tiled=True)
            expert_out = experts(expert_in).astype(self.compute_dtype)
            if ep > 1:
                expert_out = lax.all_to_all(expert_out, EXPERT_PARALLEL_AXIS,
                                            split_axis=1, concat_axis=0,
                                            tiled=True)
            flat = expert_out.reshape(EC, hidden)
            # Dropped rows gather garbage through the clamped index but
            # carry gate 0, so they contribute (and backprop) nothing.
            safe = jnp.minimum(routing.slot, EC - 1)
            contrib = flat[safe].astype(jnp.float32) * routing.gate[:, None]
            out = jnp.zeros((num_tokens, hidden), jnp.float32)
            out = out.at[routing.token_idx].add(contrib)
        else:  # einsum
            # Dispatch: [T, h] x [T, E, C] -> [E, C, h]
            expert_in = jnp.einsum(
                "th,tec->ech", x,
                routing.dispatch_mask.astype(self.compute_dtype))
            if ep > 1:
                # [E, C, h] -> [E/ep, ep*C, h]: local expert shards gain
                # every ep rank's capacity slots (rank r's block at offset
                # r*C). Tiled form: the non-tiled reshape/all_to_all/
                # reshape chain trips a JAX transpose bug when two
                # all_to_alls are chained through reshapes (wrong
                # cotangent shape at lowering).
                expert_in = lax.all_to_all(expert_in, EXPERT_PARALLEL_AXIS,
                                           split_axis=0, concat_axis=1,
                                           tiled=True)
            # compute_dtype over the wire: the return all_to_all otherwise
            # ships fp32 (2x the dispatch path's ICI bytes).
            expert_out = experts(expert_in).astype(self.compute_dtype)
            if ep > 1:
                # [E/ep, ep*C, h] -> [E, C, h]: return each rank's slots.
                expert_out = lax.all_to_all(expert_out, EXPERT_PARALLEL_AXIS,
                                            split_axis=1, concat_axis=0,
                                            tiled=True)
            # Combine: [E, C, h] x [T, E, C] -> [T, h]; bf16 operands on
            # the MXU (gates are probabilities — bf16 rounding is on par
            # with the activations), fp32 accumulation.
            out = jnp.einsum("ech,tec->th", expert_out,
                             routing.combine_weights.astype(
                                 self.compute_dtype),
                             preferred_element_type=jnp.float32)

        out = out.reshape(orig_shape).astype(self.compute_dtype)
        if self.sequence_parallel_enabled:
            out = scatter_to_sequence_parallel_region(out)
        return out

    def _held_share(self, routing, num_tokens):
        """The held experts' rows of a dropless sorted routing, in a
        static number of rows: -> (token_idx, expert_idx local, gate,
        counts, kept), each over ``rows`` rows but ``counts``
        ``[local_experts]`` and ``kept`` a scalar. The sorted order puts
        the held experts' assignments in one run, so the three are one
        window of it from the run's start on (a slice of the sorted
        arrays, padded where the window runs off their end), and
        ``counts`` are the run's groups: they sum to ``kept``, the rows
        that hold an assignment, not to ``rows``. Rows from ``kept`` on
        are in no group: the grouped matmul gives them zeros and visits
        none of their tiles, ``gather_rows`` gives them a zero input and
        ``scatter_add_rows`` does not read them; they carry gate 0."""
        from apex_tpu.telemetry.registry import get_registry

        n, off, E = self.local_experts, self.expert_offset, self.num_experts
        N = self.top_k * num_tokens
        rows = min(N, -(-int(N * n / E * self.capacity_factor) // 8) * 8)
        get_registry().gauge("moe/held_experts").set(n)
        get_registry().gauge("moe/held_rows").set(rows)
        get_registry().gauge("moe/published_experts").set(E)
        tile = row_tile(rows)
        get_registry().gauge("moe/row_tile").set(tile)
        ends = jnp.cumsum(routing.counts)
        start = ends[off] - routing.counts[off]
        held = routing.counts[off:off + n]
        # group ends within the gather, the overflow cut off the last ones
        local_ends = jnp.minimum(ends[off:off + n] - start, rows)
        counts = jnp.diff(local_ends, prepend=0)
        kept = local_ends[-1]
        valid = jnp.arange(rows, dtype=jnp.int32) < kept
        total = jnp.sum(held)
        self.sow("moe_losses", "held_assignments", total / N)
        self.sow("moe_losses", "held_load_max_over_mean",
                 jnp.max(held) * n / jnp.maximum(total, 1))
        self.sow("moe_losses", "held_dropped_fraction",
                 jax.lax.stop_gradient(1.0 - kept / jnp.maximum(total, 1)))
        # the share of the row tiles that the walks over the rows take
        self.sow("moe_losses", "held_row_tiles",
                 jax.lax.stop_gradient(
                     (-(-kept // tile)) / (-(-rows // tile))))

        def window(sorted_array):
            return lax.dynamic_slice_in_dim(
                jnp.pad(sorted_array, (0, rows)), start, rows)

        return (window(routing.token_idx),
                jnp.clip(window(routing.expert_idx) - off, 0, n - 1),
                jnp.where(valid, window(routing.gate), 0.0), counts, kept)
