"""The numbers that decide ``correct``, from two sets of readings.

Training: each step's loss, the first gradient's norm per tensor and the
parameters' change per tensor over the checked steps, the program's
against the reference's. Norms are compared by the worst tensor: the gap
between the two norms (not the norm of the difference), against the
reference's norm of that tensor or of the median tensor, whichever is
larger, since some gradients are all but zero. Tensors whose reference
gradient is under a thousandth of the median tensor's (a key's bias under
softmax) move under Adam by round-off alone and are left out of the
change.

Serving: over a sample of finished requests, the widest gap by which a
served token's logit lies below the reference's best at its position.
"""

import numpy as np

from benchmark.reference.train import flatten_norms

TINY_GRADIENT = 1e-3    # of the median tensor's gradient norm
TAIL = 0.03             # the share of tensors that ``*_p97`` looks past


def norm_gaps(got: np.ndarray, want: np.ndarray, keep=None) -> np.ndarray:
    """Per tensor: the gap between the two norms over the reference's norm
    of that tensor or of the median tensor, whichever is larger."""
    floor = float(np.median(want))
    gap = np.abs(got - want) / np.maximum(want, floor)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return gap


def worst_norm_gap(got: np.ndarray, want: np.ndarray, keep=None):
    """-> (gap, index) of the worst tensor."""
    gap = norm_gaps(got, want, keep)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def tail_norm_gap(got: np.ndarray, want: np.ndarray, keep=None) -> float:
    """The gap of the tensor at the 97th percentile: the worst once the
    worst ``TAIL`` of the tensors (12 of BERT-large's 398) is set aside.
    Steady where the worst tensors are small ones whose gradient is a
    near-cancelling sum over few rows (BERT's two-element NSP bias, the
    MLM head's biases), and still moved by anything that touches a whole
    layer's tensors. Of the shares tried on the chip's readings (1, 3, 5,
    10, 20%), this one sets the control furthest from the program."""
    gap = np.sort(norm_gaps(got, want, keep))[::-1]
    return float(gap[int(np.ceil(TAIL * len(gap)))])


class _Readings:
    """Both sides' readings as vectors, one entry per tensor."""

    def __init__(self, program: dict, reference: dict):
        self.names, self.g_ref = flatten_norms(reference["grad_norms"])
        _, self.g_got = flatten_norms(program["grad_norms"])
        _, self.c_ref = flatten_norms(reference["change_norms"])
        _, self.c_got = flatten_norms(program["change_norms"])
        self.l_ref = np.asarray(reference["losses"], np.float64)
        self.l_got = np.asarray(program["losses"], np.float64)
        self.moved = self.g_ref >= TINY_GRADIENT * float(
            np.median(self.g_ref))


def _median_change_gap(r):
    got, want = np.median(r.c_got[r.moved]), np.median(r.c_ref[r.moved])
    return float(abs(got - want) / want)


# what a training cell's limits file may name; a run works out those its
# file names and no others
TRAIN_NUMBERS = {
    "loss_gap": lambda r: float(np.max(np.abs(r.l_got - r.l_ref)
                                       / np.abs(r.l_ref))),
    "grad_norm_gap": lambda r: worst_norm_gap(r.g_got, r.g_ref)[0],
    "grad_gap_p97": lambda r: tail_norm_gap(r.g_got, r.g_ref),
    "change_norm_gap": lambda r: worst_norm_gap(r.c_got, r.c_ref,
                                                keep=r.moved)[0],
    "median_change_gap": _median_change_gap,
}


def train_numbers(program: dict, reference: dict, wanted=None) -> tuple:
    """-> (numbers, notes). ``program`` and ``reference`` hold
    ``losses``, ``grad_norms`` and ``change_norms`` (canonical names).
    ``wanted`` names the numbers to work out (a cell's limits; those that
    are not a training number are the caller's); ``None`` is all of them,
    for a calibration."""
    r = _Readings(program, reference)
    names = TRAIN_NUMBERS if wanted is None \
        else [n for n in TRAIN_NUMBERS if n in wanted]
    numbers = {n: TRAIN_NUMBERS[n](r) for n in names}
    notes = {
        "worst_grad_tensor": r.names[worst_norm_gap(r.g_got, r.g_ref)[1]],
        "tensors": len(r.names),
        "left_out_of_change": int((~r.moved).sum()),
        "losses_program": [float(x) for x in r.l_got],
        "losses_reference": [float(x) for x in r.l_ref],
    }
    return numbers, notes


def serve_gap(ref_logits: np.ndarray, served: np.ndarray) -> float:
    """``ref_logits`` ``[n, vocab]`` are the reference's logits at the
    positions that produced the ``n`` ``served`` tokens. The widest gap of
    a served token's logit below the reference's best, in units of the
    spread (standard deviation) of the logits at that position."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    spread = ref_logits.std(axis=-1)
    return float(np.max((best - got) / spread))
