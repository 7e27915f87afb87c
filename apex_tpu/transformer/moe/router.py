"""Top-k expert routing with static capacity (GShard / Switch Transformer).

The routing decision is materialized as dense one-hot dispatch/combine
tensors so the whole layer is static-shaped einsums — the TPU-idiomatic
formulation (no gather/scatter, everything lands on the MXU and fuses).

``compute_routing`` is the functional core; ``TopKRouter`` wraps it as a
flax module owning the (dense, replicated) gate projection.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass
class RoutingResult:
    """Static-shaped routing tensors for T tokens, E experts, capacity C."""

    dispatch_mask: jnp.ndarray    # [T, E, C] {0,1} — token t fills slot (e, c)
    combine_weights: jnp.ndarray  # [T, E, C] fp32 — gate weight per filled slot
    aux_loss: jnp.ndarray         # scalar load-balancing loss (Switch eq. 4-6)
    z_loss: jnp.ndarray           # scalar router z-loss (ST-MoE eq. 5)
    probs: jnp.ndarray            # [T, E] softmax router probabilities
    dropped_fraction: jnp.ndarray = None  # scalar: routed slots lost to capacity


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Static per-expert slot count; always a multiple of 8 for TPU lane
    layout, capped near num_tokens (ADVICE r2: tiny configs otherwise get
    more slots per expert than there are tokens, pure padding waste; the
    cap itself rounds up to 8 so the lane invariant survives)."""
    raw = max(1, int(num_tokens * top_k * capacity_factor / num_experts))
    rounded = -(-raw // 8) * 8
    cap = -(-max(1, num_tokens) // 8) * 8
    return min(rounded, cap)


def _router_losses(logits, probs, expert_fractions):
    """Shared Switch aux loss + ST-MoE z-loss. ``expert_fractions`` [E]
    is the PRE-DROP fraction of routed assignments per expert — both the
    dense and sorted formulations must feed the same quantity, or the
    dispatch-mode parity contract (test_moe_dispatch.py) breaks."""
    E = logits.shape[-1]
    aux_loss = E * jnp.sum(expert_fractions * probs.mean(axis=0))
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    return aux_loss, jnp.mean(z * z)


def compute_routing(logits, top_k: int, capacity: int,
                    normalize_topk: bool = True) -> RoutingResult:
    """Route tokens from fp32 router ``logits`` [T, E].

    Position-in-expert is a cumsum over the token dim (arrival order, the
    GShard discipline); tokens beyond ``capacity`` are dropped — their
    combine weights are zero, so they ride the residual connection.
    """
    logits = logits.astype(jnp.float32)
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    # Iterative top-k: mask out prior choices and re-argmax.
    choice_masks = []
    remaining = probs
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        choice_masks.append(onehot)
        remaining = remaining * (1.0 - onehot)

    gates = [jnp.sum(probs * m, axis=-1) for m in choice_masks]  # k x [T]
    if normalize_topk and top_k > 1:
        denom = sum(gates)
        gates = [g / jnp.maximum(denom, 1e-9) for g in gates]

    # Slot assignment: earlier choices claim slots before later ones.
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    expert_fill = jnp.zeros((1, E), jnp.float32)
    for onehot, gate in zip(choice_masks, gates):
        pos = jnp.cumsum(onehot, axis=0) - 1.0 + expert_fill  # [T, E]
        expert_fill = expert_fill + jnp.sum(onehot, axis=0, keepdims=True)
        keep = onehot * (pos < capacity)
        slot = jax.nn.one_hot(jnp.sum(pos * onehot, axis=-1).astype(jnp.int32),
                              capacity, dtype=jnp.float32)  # [T, C]
        dispatch = dispatch + keep[:, :, None] * slot[:, None, :]
        combine = combine + (keep * gate[:, None])[:, :, None] * slot[:, None, :]

    # Load-balancing aux loss: E * sum_e f_e * P_e with f_e the fraction of
    # routed (pre-drop) assignments and P_e the mean router probability.
    f = sum(choice_masks).sum(axis=0) / (top_k * T)  # [E]
    aux_loss, z_loss = _router_losses(logits, probs, f)
    dropped = 1.0 - jnp.sum(dispatch) / (top_k * T)
    return RoutingResult(dispatch, combine, aux_loss, z_loss, probs,
                         lax.stop_gradient(dropped))


@dataclasses.dataclass
class SortedRouting:
    """Sorted token->expert assignments for T tokens, E experts, k choices.

    N = k*T assignment rows, ordered by expert id (stable within an
    expert: choice rank major, then token order — exactly the slot-fill
    order of ``compute_routing``'s cumsum, so capacity drops are
    bit-identical between the dense and sorted formulations). This is
    the O(T log T + T E) routing representation: no [T, E, C] one-hot
    tensors anywhere, so dispatch/combine cost scales linearly in T
    instead of quadratically (the dropless C ~ T regime that serves
    converted Mixtral/DeepSeek checkpoints at real sequence lengths).
    """

    token_idx: jnp.ndarray   # [N] int32 — source token of assignment i
    expert_idx: jnp.ndarray  # [N] int32 — expert of assignment i (ascending)
    gate: jnp.ndarray        # [N] fp32 — combine weight (0 for dropped rows)
    counts: jnp.ndarray      # [E] int32 — pre-drop assignments per expert
    slot: jnp.ndarray        # [N] int32 in [0, E*C]; E*C = dropped sentinel
                             # (None when capacity is None: dropless)
    aux_loss: jnp.ndarray    # scalar load-balancing loss (same formula as
                             # compute_routing — counts are pre-drop)
    z_loss: jnp.ndarray      # scalar router z-loss
    probs: jnp.ndarray       # [T, E] softmax router probabilities
    dropped_fraction: jnp.ndarray = None
    chosen: jnp.ndarray = None   # [T, k] int32 — each token's experts


def compute_routing_sorted(logits, top_k: int, capacity: Optional[int],
                           normalize_topk: bool = True, *, score_bias=None,
                           routed_scaling_factor: float = 1.0
                           ) -> SortedRouting:
    """Sort-based routing from fp32 ``logits`` [T, E].

    With ``score_bias`` [E] the scores are sigmoids, not a softmax
    (DeepSeek-V3's auxiliary-loss-free balancing, Nemotron-H's router):
    the ``top_k`` experts are those of largest ``score + score_bias``, the
    gates are the chosen experts' *unbiased* scores, over their sum if
    ``normalize_topk``, times ``routed_scaling_factor``. The bias steers
    the choice alone and no gradient reaches it; there is no auxiliary
    loss (``aux_loss`` and ``z_loss`` are 0) and ``probs`` are the
    sigmoids.

    ``capacity=None`` is truly dropless (every assignment kept, no slot
    layout — feed ``ExpertMLP`` via ragged grouping). With a capacity,
    assignments beyond C per expert get zero gate and the E*C slot
    sentinel; the kept set matches ``compute_routing`` exactly because
    the pre-sort order (choice rank major, token minor) reproduces its
    "earlier choices claim slots first" cumsum discipline.
    """
    logits = logits.astype(jnp.float32)
    T, E = logits.shape
    N = top_k * T
    if score_bias is None:
        probs = jax.nn.softmax(logits, axis=-1)

        # lax.top_k returns descending values, ties broken toward the lower
        # index — the same choice sequence as compute_routing's iterative
        # argmax-and-mask.
        topv, topi = lax.top_k(probs, top_k)  # [T, k], [T, k]
        gates = topv
        if normalize_topk and top_k > 1:
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.sigmoid(logits)
        _, topi = lax.top_k(
            probs + lax.stop_gradient(score_bias.astype(jnp.float32)), top_k)
        gates = jnp.take_along_axis(probs, topi, axis=-1)
        if normalize_topk:
            gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        gates = gates * routed_scaling_factor

    # Choice-rank-major flatten, then a stable sort by expert: within an
    # expert, rows appear in (rank, token) order — compute_routing's fill
    # order — so "first C rows win" is the identical drop rule.
    flat_e = topi.T.reshape(N)
    flat_t = jnp.tile(jnp.arange(T, dtype=jnp.int32), top_k)
    flat_g = gates.T.reshape(N)
    order = jnp.argsort(flat_e, stable=True)
    expert_sorted = flat_e[order].astype(jnp.int32)
    token_sorted = flat_t[order]
    gate_sorted = flat_g[order]

    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)  # pre-drop
    group_start = jnp.cumsum(counts) - counts  # exclusive prefix
    pos_in_expert = jnp.arange(N, dtype=jnp.int32) - group_start[expert_sorted]

    if capacity is None:
        slot = None
        dropped = jnp.zeros((), jnp.float32)
    else:
        kept = pos_in_expert < capacity
        slot = jnp.where(kept, expert_sorted * capacity + pos_in_expert,
                         E * capacity).astype(jnp.int32)
        gate_sorted = jnp.where(kept, gate_sorted, 0.0)
        dropped = 1.0 - jnp.sum(kept) / N

    f = counts.astype(jnp.float32) / N  # pre-drop fraction, as compute_routing
    if score_bias is None:
        aux_loss, z_loss = _router_losses(logits, probs, f)
    else:
        aux_loss = z_loss = jnp.zeros((), jnp.float32)
    return SortedRouting(token_sorted, expert_sorted, gate_sorted, counts,
                         slot, aux_loss, z_loss, probs,
                         lax.stop_gradient(dropped), topi)


def sequence_balance_loss(probs, chosen, num_sequences: int):
    """DeepSeek-V3's complementary sequence-wise balance loss (its
    technical report, eq. 17-20; ``seq_aux`` in the published configs),
    before its coefficient: for a sequence of ``T`` tokens
    ``sum_i f_i P_i`` with ``f_i = E / (k T) * #{t : expert i chosen at
    t}`` (no gradient) and ``P_i = (1 / T) sum_t s_it / sum_j s_jt``,
    averaged over the sequences. ``probs`` ``[T_all, E]`` are the sigmoid
    scores and ``chosen`` ``[T_all, k]`` each token's experts
    (``SortedRouting.chosen``); tokens are laid out ``[s, b]`` flattened (a
    sequence is every ``num_sequences``-th row), as ``SwitchMLP`` flattens
    them. Over all ``E`` experts the router sees, held here or not."""
    T_all, E = probs.shape
    T, k = T_all // num_sequences, chosen.shape[-1]
    share = probs / jnp.sum(probs, axis=-1, keepdims=True)
    P = jnp.mean(share.reshape(T, num_sequences, E), axis=0)
    picked = jnp.zeros((T_all, E), jnp.float32).at[
        jnp.arange(T_all)[:, None], chosen].set(1.0)
    f = jnp.sum(picked.reshape(T, num_sequences, E), axis=0) * (E / (k * T))
    return jnp.mean(jnp.sum(f * P, axis=-1))


def compute_expert_choice_routing(logits, capacity: int) -> RoutingResult:
    """Expert-choice routing (Zhou et al. 2022, arXiv 2202.09368): each
    expert picks its top-``capacity`` tokens by router probability.

    Perfectly load-balanced by construction (every expert fills exactly C
    slots), so the Switch aux loss degenerates — it is returned as 0. A
    token may be chosen by several experts (contributions sum) or by none
    (rides the residual; tracked in ``dropped_fraction``). TPU-friendly:
    one ``lax.top_k`` over tokens per expert plus the same one-hot
    dispatch/combine einsums as top-k routing.
    """
    logits = logits.astype(jnp.float32)
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    # per expert: weights + token indices of its top-C tokens
    gates, idx = lax.top_k(probs.T, min(capacity, T))  # [E, C], [E, C]
    dispatch = jax.nn.one_hot(idx, T, dtype=jnp.float32)  # [E, C, T]
    dispatch = dispatch.transpose(2, 0, 1)                # [T, E, C]
    combine = dispatch * gates[None, :, :]
    picked = jnp.clip(jnp.sum(dispatch, axis=(1, 2)), 0.0, 1.0)  # [T]
    dropped = 1.0 - jnp.mean(picked)
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    z_loss = jnp.mean(z * z)
    return RoutingResult(dispatch, combine, jnp.zeros((), jnp.float32),
                         z_loss, probs, lax.stop_gradient(dropped))


def _tp_uniform_key(key):
    """Broadcast tp-rank-0's rng key across the tp axis (no-op outside
    shard_map / when tp is unbound)."""
    from jax import lax

    from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS

    try:
        rank = lax.axis_index(TENSOR_PARALLEL_AXIS)
    except Exception:
        return key
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
        data = lax.psum(jnp.where(rank == 0, data, jnp.zeros_like(data)),
                        TENSOR_PARALLEL_AXIS)
        return jax.random.wrap_key_data(data)
    return lax.psum(jnp.where(rank == 0, key, jnp.zeros_like(key)),
                    TENSOR_PARALLEL_AXIS)


class TopKRouter(nn.Module):
    """Learned gate: fp32 projection to expert logits + optional jitter.

    ``router_type`` selects the assignment rule: "top_k" (tokens choose
    experts — GShard/Switch) or "expert_choice" (experts choose tokens —
    balanced by construction, no aux loss). The gate weight is a dense
    (replicated) param — with expert parallelism its grads must sync over
    the full dp x ep replica set like any other dense param.
    """

    num_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    jitter_eps: float = 0.0
    normalize_topk: bool = True
    router_type: str = "top_k"
    # "softmax", or "sigmoid_bias": sigmoid scores with the choice steered
    # by the ``e_score_correction_bias`` parameter (a buffer in the
    # published models: zeros at init, moved by the trainer's balancing
    # rule and by no gradient), gates times ``routed_scaling_factor``
    # (compute_routing_sorted). Counted at trace time as
    # ``moe/router/sigmoid_bias``.
    score: str = "softmax"
    routed_scaling_factor: float = 1.0
    params_dtype: Any = jnp.float32
    capacity: Optional[int] = None  # override for tests
    # "dense" -> RoutingResult ([T,E,C] one-hots for the einsum path);
    # "sorted" -> SortedRouting with capacity slots (scatter dispatch);
    # "sorted_dropless" -> SortedRouting, capacity=None (ragged dispatch).
    routing_format: str = "dense"

    @nn.compact
    def __call__(self, tokens) -> RoutingResult:
        """tokens: [T, h] -> RoutingResult with C from ``expert_capacity``.

        Jitter activates when ``jitter_eps > 0`` AND the caller supplies a
        'jitter' rng stream (``apply(..., rngs={"jitter": key})``) — eval
        runs without the stream are deterministic by construction.
        """
        T = tokens.shape[0]
        gate = self.param("gate_weight", nn.initializers.lecun_normal(),
                          (tokens.shape[-1], self.num_experts),
                          self.params_dtype)
        x = tokens.astype(jnp.float32)
        if self.jitter_eps > 0.0 and self.has_rng("jitter"):
            # Routing must agree across tp ranks (the ExpertMLP copy/reduce
            # pairing assumes identical dispatch per rank), so the jitter
            # key is forced tp-uniform even if the caller folded the tp
            # rank into it (the dropout-key discipline would).
            key = _tp_uniform_key(self.make_rng("jitter"))
            x = x * jax.random.uniform(
                key, x.shape, jnp.float32,
                1.0 - self.jitter_eps, 1.0 + self.jitter_eps)
        logits = x @ gate.astype(jnp.float32)
        cap = self.capacity if self.capacity is not None else expert_capacity(
            T, self.num_experts, self.top_k, self.capacity_factor)
        if self.router_type == "expert_choice":
            return compute_expert_choice_routing(logits, cap)
        if self.router_type != "top_k":
            raise ValueError(f"unknown router_type {self.router_type!r}; "
                             "expected 'top_k' or 'expert_choice'")
        how = {}
        if self.score == "sigmoid_bias":
            from apex_tpu.telemetry.registry import get_registry

            if self.routing_format == "dense":
                raise ValueError("the sigmoid_bias router has the sorted "
                                 "routing formats only")
            get_registry().counter("moe/router/sigmoid_bias").inc()
            how = dict(
                score_bias=self.param(
                    "e_score_correction_bias", nn.initializers.zeros,
                    (self.num_experts,), jnp.float32),
                routed_scaling_factor=self.routed_scaling_factor)
        elif self.score != "softmax":
            raise ValueError(f"unknown router score {self.score!r}")
        if self.routing_format == "sorted":
            return compute_routing_sorted(logits, self.top_k, cap,
                                          self.normalize_topk, **how)
        if self.routing_format == "sorted_dropless":
            return compute_routing_sorted(logits, self.top_k, None,
                                          self.normalize_topk, **how)
        if self.routing_format != "dense":
            raise ValueError(
                f"unknown routing_format {self.routing_format!r}")
        return compute_routing(logits, self.top_k, cap, self.normalize_topk)
