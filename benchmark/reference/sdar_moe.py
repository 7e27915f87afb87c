"""SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json),
one chip's share of it, trained by diffusion over blocks, written out
plainly in float32 at ``highest`` matmul precision.

**Published** (the row's ``config``): 48 identical layers, hidden 2048,
RMSNorm 1e-6, no biases, 32 query heads and 4 key-value heads of 128,
``rope_theta`` 1e6, no rope scaling, no sliding window; every layer a
mixture of 128 SwiGLU experts of width 768, 8 a token, softmax router with
``norm_topk_prob``; untied 151,936-row tables; 32,768 positions.

**The rows.** A data sequence ``x0`` of ``L`` tokens is cut into blocks of
``bl`` tokens, block ``B(i) = i // bl``. Each block draws a noise level
``t_b``; each token of the block is replaced by the mask token with
probability ``t_b``, independently: ``xt``, with ``m_i = 1`` where it was
replaced. The model runs ONE row of ``2L``: ``[x0 ; xt]``, position
``p_i = i mod L`` for both copies. With ``c(i) = (i < L)`` ("clean") and
``b(i) = B(i mod L)``, query ``i`` sees key ``j`` iff

    ( c(i) and  c(j) and b(j) <= b(i))    clean -> clean, block-causal
 or (!c(i) and  c(j) and b(j) <  b(i))    noisy -> the clean copy of every
                                          earlier block
 or (!c(i) and !c(j) and b(j) == b(i))    noisy -> its own noisy block,
                                          both directions

(a clean query never sees a noisy key; every query sees at least its own
block, so no row of the softmax is empty). This is the training layout of
Block Diffusion (Arriola et al., arXiv:2503.09573, "efficient training":
the vectorised ``x_t (+) x_0`` pass with its block-diagonal, offset
block-causal and block-causal parts), which SDAR (arXiv:2510.06303) takes
over.

**The layer**, for one row ``x [2L, hidden]``, ``u = RMSNorm(x)``:
``q = u Wq -> [2L, 32, 128]``, ``k = u Wk``, ``v = u Wv -> [2L, 4, 128]``;
``q``, ``k`` <- RMSNorm over the 128 of each head with a gain shared by
the heads (Qwen3's; *assumed*: the row's ``config`` does not list the key),
rotary (rotate-half, theta ``rope_theta``, all 128) at ``p_i``;
``s_ij = q_i . k_j / sqrt(128)`` under the rule above, head ``n`` on
key-value head ``n // 8``, softmax in float32; ``x' = x + concat(o) Wo``.
Then, ``u' = RMSNorm(x')``: ``r = softmax(u' Wr)`` over all 128 experts,
``T`` its top 8, ``w_e = r_e / sum_{e' in T} r_e'``;
``x'' = x' + sum_{e in T, e held here} w_e Wd_e (silu(Wg_e u') * Wu_e u')``.
What the absent experts would have added is left out, here as in the
program (``keye_vl2.experts``: the same held share).

**Head and loss.** Final RMSNorm and the head on rows ``L..2L-1`` alone.
Over the ``L`` data tokens of each of the ``B`` sequences,

    loss = 1/(B L) * sum_i  m_i / t_{B(i)} * CE(logits_i, x0_i)
           + c_aux * sum_layers L_aux

``CE`` over the table's slice (padded rows included, as the program's loss
has them); **no shift**: the logit at a masked position predicts that
position's token (*assumed*, as the released generation code reads it).
``L_aux``: the Switch load-balancing loss over all 128 experts and all
``2L`` rows, ``E * sum_e f_e P_e`` as the program's router has it
(coefficient *assumed*: Qwen3-MoE's 0.001). A product of means over the
batch does not split into blocks of rows: ``loss_part`` forms it over its
block, so the cell's ``reference_block_rows`` is its whole batch.

*Assumed* besides (each in the configuration's ``assumed``): ``bl`` = 4;
``t_b`` uniform on [0.25, 1], weight ``1 / t``; the mask token is the last
real row of the held vocabulary slice. The noise is part of the batch:
``tokens`` ``[B, L]``, ``noisy`` ``[B, L]`` and ``weights`` ``[B, L]``
(``m / t``) come from the family's feeder.

Departures, all for memory and none for a number: attention runs in
blocks of ``QUERY_BLOCK`` queries against every key under the boolean
mask (``[block, 2L]`` scores a head), the cross-entropy in blocks of
positions, each row of each layer is rematerialised in the backward pass,
and each held expert runs over every row with a zero gate where it was
not chosen.
"""

import jax
import jax.numpy as jnp

from benchmark.reference import keye_vl2 as K
from benchmark.reference import transformer as T

QUERY_BLOCK = 256


def rule_rows(first, count, length, block):
    """The rule for the queries ``first .. first + count`` of a row of
    ``2 * length`` against every key: booleans ``[count, 2 * length]``,
    the three lines of the docstring."""
    i = (first + jnp.arange(count))[:, None]
    j = jnp.arange(2 * length)[None, :]
    ci, cj = i < length, j < length
    bi, bj = (i % length) // block, (j % length) // block
    return ((ci & cj & (bj <= bi)) | (~ci & cj & (bj < bi))
            | (~ci & ~cj & (bj == bi)))


def attention(q, k, v, block_length, quant):
    """``o [2L, heads, d]`` under the rule, in blocks of queries, each
    rematerialised."""
    s = q.shape[0]
    c = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def rows(block):
        first, qc = block
        mask = rule_rows(first, c, s // 2, block_length)
        return K.attend_rows(qc, k, v, mask, quant)[0]

    out = jax.lax.map(rows, (jnp.arange(0, s, c),
                             q.reshape((s // c, c) + q.shape[1:])))
    return out.reshape(q.shape)


def block(x, lp, arch, quant):
    """One layer on one row ``[2L, hidden]``: -> (output, ``f``, ``P``)."""
    s = x.shape[0]
    heads, kv_heads, d = arch["heads"], arch["kv_heads"], arch["head_dim"]
    eps = arch["eps"]
    positions = jnp.arange(s) % (s // 2)
    a = K.rms_norm(x, lp["ln1_g"], eps)
    q = T.matmul(a, lp["wq"], quant).reshape(s, heads, d)
    k = T.matmul(a, lp["wk"], quant).reshape(s, kv_heads, d)
    v = T.matmul(a, lp["wv"], quant).reshape(s, kv_heads, d)
    q = K.rotary(K.rms_norm(q, lp["qn_g"], eps), positions, arch["theta"])
    k = K.rotary(K.rms_norm(k, lp["kn_g"], eps), positions, arch["theta"])
    o = attention(q, k, v, arch["block_length"], quant)
    x = x + T.matmul(o.reshape(s, heads * d), lp["wo"], quant)
    m, f, p = K.experts(K.rms_norm(x, lp["ln2_g"], eps), lp, arch, quant)
    return x + m, f, p


def noisy_states(params, arch, batch, quant=T.identity):
    """-> (the noisy half after the final norm ``[rows, L, hidden]``, the
    layers' ``L_aux`` ``[layers]`` over this block of rows)."""
    layers = {k[len("layers."):]: v for k, v in params.items()
              if k.startswith("layers.")}

    def body(x, lp):
        x, f, p = jax.lax.map(
            jax.checkpoint(lambda row: block(row, lp, arch, quant)), x)
        return x, arch["experts"] * jnp.sum(jnp.mean(f, axis=0)
                                            * jnp.mean(p, axis=0))

    ids = jnp.concatenate([batch["tokens"], batch["noisy"]], axis=1)
    x, aux = jax.lax.scan(body, params["wte"][ids], layers)
    length = batch["tokens"].shape[1]
    x = K.rms_norm(x[:, length:], params["lnf_g"], arch["eps"])
    return x, aux


def logits(params, arch, batch, quant=T.identity):
    """The noisy half's logits ``[rows, L, vocab]``, all at once (for a
    comparison at a small size)."""
    return T.matmul(noisy_states(params, arch, batch, quant)[0],
                    params["head"], quant)


def loss_part(params, arch, batch, totals, quant=T.identity):
    """This block of rows' part of the batch loss (the docstring's
    ``loss``): the parts of all blocks add up to it, but for ``L_aux``,
    which is formed over the block's rows and is the batch's only when the
    block is the batch. Layers outside, rows inside, each rematerialised,
    as ``keye_vl2.loss_part``."""
    x, aux = noisy_states(params, arch, batch, quant)
    rows, length, _ = x.shape
    c = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length

    @jax.checkpoint
    def weighted_nll(xlw):
        xc, labels, weights = xlw
        return jnp.sum(weights * T.token_nll(
            T.matmul(xc, params["head"], quant), labels))

    def split(t):
        return t.reshape((rows * length // c, c) + t.shape[2:])

    total = jnp.sum(jax.lax.map(weighted_nll, (
        split(x), split(batch["tokens"]), split(batch["weights"]))))
    return (total / totals["tokens"]
            + arch["aux_coef"] * jnp.sum(aux) * rows / totals["rows"])


def totals(batch):
    """What a block's part is divided by, from the whole batch: its data
    tokens (``B * L``, masked or not) and its rows."""
    return {"tokens": float(batch["tokens"].size),
            "rows": float(batch["tokens"].shape[0])}
