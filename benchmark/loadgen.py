"""The one general traffic generator. A mix is a data file under
``traffic/``; everything here is drawn from ``--seed`` and the file.

Train mixes (``kind: train``) are batch feeders: an endless, seeded
stream of batches, a fresh one every step, rows all different. A mix
names its ``task``; the configuration's family says which feeder that is
(``families/<family>.py`` ``TASKS``), and one that needs another kind of
batch brings its feeder with it.

Serve mixes (``kind: serve``) are open-loop request schedules on the wall
clock. So that ``--seed`` reorders the work and does not change it, time
is cut into periods of ``period_s``; period *k* holds the same multiset of
arrival gaps and of (prompt, output) lengths for every seed (drawn from
the file's ``shape_seed`` and *k*), and the seed permutes which request
gets which gap and length and draws the prompt's tokens. The gaps of a
period are exponential draws scaled to fill it exactly, i.e. a Poisson
process conditioned on its count.
"""

import dataclasses

import numpy as np

MASK_ID = 103  # [MASK] in BERT's uncased vocabulary


def _rng(*words):
    return np.random.default_rng([int(w) & 0xFFFFFFFF for w in words]
                                 + [int(words[0]) >> 32])


# ---------------------------------------------------------------- train

def causal_lm_batches(mix: dict, arch: dict, seed: int):
    """Endless ``{"tokens", "labels"}`` ``[batch, seq]`` int32: labels
    are the tokens shifted by one. Ids are drawn from the published
    vocabulary (``arch["vocab_real"]``), not the padded table."""
    rng = _rng(seed, 1)
    vocab = arch["vocab_real"]
    while True:
        ids = rng.integers(0, vocab, (mix["batch"], mix["seq"] + 1),
                           dtype=np.int32)
        yield {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def mlm_nsp_batches(mix: dict, arch: dict, seed: int):
    """Endless BERT pre-training batches: two segments to a row, real
    length uniform in ``[min_len, seq]`` and the rest padding,
    ``mask_prob`` of the real positions replaced by ``[MASK]`` and scored,
    a random next-sentence label."""
    rng = _rng(seed, 2)
    vocab = arch["vocab_real"]
    b, s = mix["batch"], mix["seq"]
    pos = np.arange(s)[None, :]
    while True:
        ids = rng.integers(0, vocab, (b, s), dtype=np.int32)
        length = rng.integers(mix["min_len"], s + 1, (b, 1))
        split = (length * rng.uniform(0.3, 0.7, (b, 1))).astype(np.int64)
        real = pos < length
        chosen = (rng.random((b, s)) < mix["mask_prob"]) & real
        # every row scores at least its first token, so no row is idle
        chosen[:, 0] |= ~chosen.any(axis=1)
        yield {
            "tokens": np.where(chosen, MASK_ID, ids).astype(np.int32)
            * real,
            "labels": ids,
            "loss_mask": chosen.astype(np.float32),
            "padding_mask": real.astype(np.int32),
            "segments": ((pos >= split) & real).astype(np.int32),
            "nsp_labels": rng.integers(0, 2, (b,), dtype=np.int32),
        }


# ---------------------------------------------------------------- serve

@dataclasses.dataclass
class Arrival:
    rid: int
    due_s: float          # seconds after the generator's start
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def _lognormal_lengths(rng, n, spec):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def period_shape(mix: dict, k: int):
    """Period *k*'s multiset, the same for every seed: arrival gaps
    (summing to ``period_s``), prompt lengths and output lengths."""
    rng = _rng(mix["shape_seed"], k)
    n = max(1, int(round(mix["rate_rps"] * mix["period_s"])))
    gaps = rng.exponential(1.0, n + 1)
    gaps = gaps[:n] / gaps.sum() * mix["period_s"]
    prompts = _lognormal_lengths(rng, n, mix["prompt_len"])
    outputs = _lognormal_lengths(rng, n, mix["output_len"])
    outputs = np.minimum(outputs, mix["max_total_len"] - prompts)
    return gaps, prompts, outputs


def schedule(mix: dict, vocab: int, seed: int, horizon_s: float,
             lead_s: float = 0.0, cycle_s: float = None):
    """Every arrival due in ``[0, horizon_s)``, in due order.

    With ``cycle_s`` the traffic is periodic: the slot of length
    ``period_s`` that starts at ``lead_s + t`` holds the same gaps and
    lengths, in the same order, as the one at ``lead_s + t + cycle_s``, and
    the lead (a cell's ramp) replays the end of the cycle. A window of one
    cycle after the lead then takes over from the lead what it hands on at
    its close, so the tokens it emits are those it is offered, whichever
    requests the seed puts at its edges. Prompt tokens are drawn afresh."""
    period = mix["period_s"]
    lead = int(round(lead_s / period))
    cycle = max(1, int(round(cycle_s / period))) if cycle_s else None
    out, slot, rid = [], 0, 0
    while slot * period < horizon_s:
        k = slot if cycle is None else (slot - lead) % cycle
        gaps, prompts, outputs = period_shape(mix, k)
        rng = _rng(seed, 3, k)
        due = slot * period + np.cumsum(rng.permutation(gaps))
        order = rng.permutation(len(prompts))
        rng = _rng(seed, 5, slot)      # the prompts' tokens: fresh per slot
        for t, j in zip(due, order):
            if t >= horizon_s:
                break
            out.append(Arrival(
                rid, float(t),
                rng.integers(0, vocab, int(prompts[j]), dtype=np.int32),
                int(outputs[j])))
            rid += 1
        slot += 1
    return out
