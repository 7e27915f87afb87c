"""Operations the algorithm needs, from shapes. Kept with the benchmark so
that no PR which claims a gain can move them.

Model FLOPs per token come from the architecture's family
(``benchmark/families/<family>.py``; the transformer families follow the
PaLM convention, Chowdhery et al. 2022, appendix B: 2 per matrix
parameter touched, plus the attention matrices ``4 * seq * hidden`` per
layer with no causal discount). A training step is three forward passes'
worth (forward + backward), recomputation not counted. Copied from
``bench.py`` ``_transformer_fwd_flops_per_token`` (listed in PERF.md for
a later PR to delete there).
"""

from benchmark import families


def matmul_params(arch: dict) -> int:
    """Parameters that sit in a matrix product on a token's path."""
    return families.of(arch).matmul_params(arch)


def fwd_flops_per_token(arch: dict, seq: int) -> float:
    return families.of(arch).fwd_flops_per_token(arch, seq)


def train_flops_per_token(arch: dict, seq: int) -> float:
    return 3.0 * fwd_flops_per_token(arch, seq)


def attention_train_flops_per_step(arch: dict, batch: int, seq: int,
                                   causal: bool) -> float:
    """What the attention kernel itself has to do in one training step:
    QK^T and PV forward (``4 * s^2 * h`` per row and layer), twice that
    again backward (dV, dP, dQ, dK; the scores a flash backward computes
    again are recomputation and not counted), halved where the causal
    mask lets the kernel skip the upper triangle."""
    fwd = 4.0 * seq * seq * arch["hidden"] * batch * arch["layers"]
    total = fwd * 3.0
    return total / 2 if causal else total


def serve_flops(arch: dict, context_lengths) -> float:
    """FLOPs to process one token at each of ``context_lengths`` (the
    number of positions it attends over, itself included): the matrices
    once, plus ``4 * context * hidden`` per layer of attention."""
    n = len(context_lengths)
    total_ctx = float(sum(context_lengths))
    return (2.0 * matmul_params(arch) * n
            + 4.0 * arch["hidden"] * arch["layers"] * total_ctx)
