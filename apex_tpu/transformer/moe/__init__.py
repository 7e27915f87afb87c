"""Mixture-of-experts with expert parallelism (TPU-native).

No reference equivalent: juncongmoo/apex has no MoE / expert parallelism
(SURVEY.md §2.3 note). This subsystem is a new capability, designed
TPU-first: capacity-based GShard/Switch routing (one-hot einsums or the
O(T log T) sorted formulation), grouped expert FFNs — batched over a
leading expert dim, or ragged via ``lax.ragged_dot`` grouped matmuls
with zero capacity padding (the dropless serving path) — and
expert-parallel dispatch via ``lax.all_to_all`` over the 'ep' mesh axis
(ICI all-to-all), with the expert hidden dim tensor-parallel over 'tp'.
"""

from apex_tpu.transformer.moe.layer import (
    ExpertMLP,
    SharedExpertMoE,
    SwitchMLP,
    is_expert_param,
    moe_loss_from_variables,
    seq_aux_loss_from_variables,
    sown_total,
)
from apex_tpu.transformer.moe.router import (
    SortedRouting,
    TopKRouter,
    compute_expert_choice_routing,
    compute_routing,
    compute_routing_sorted,
    sequence_balance_loss,
)

__all__ = [
    "ExpertMLP",
    "SharedExpertMoE",
    "SortedRouting",
    "SwitchMLP",
    "TopKRouter",
    "compute_expert_choice_routing",
    "compute_routing",
    "compute_routing_sorted",
    "is_expert_param",
    "moe_loss_from_variables",
    "seq_aux_loss_from_variables",
    "sequence_balance_loss",
    "sown_total",
]
