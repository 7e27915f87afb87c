"""Keye-VL-2.0-30B-A3B's language model (``model_type`` ``KeyeVL2``,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json),
one chip's share of it, written out plainly in float32 at ``highest``
matmul precision: grouped-query attention under a DeepSeek-Sparse-Attention
indexer, 128 routed experts with 8 a token of which this chip holds some,
RMSNorm, no biases, untied embedding and head.

**The layer**, for one sequence ``x [s, hidden]``; RMSNorm with the
configuration's eps and a learned gain throughout:

1. ``a = RMSNorm(x)``. ``q = a Wq -> [s, heads, d]``, ``k = a Wk``,
   ``v = a Wv -> [s, kv_heads, d]``. ``q``, ``k`` <- RMSNorm over the
   ``d`` of each head with a gain shared by the heads (*assumed*: the
   source has no key for it; the convention of the family whose sizes the
   configuration carries).
2. Rotary, rotate-half, theta ``rope_theta``, on all ``d``: frequency
   ``i`` of the ``d / 2`` takes its position from the component whose
   section of ``mrope_section`` it falls in; positions are ``[3, s]``.
3. Indexer (DeepSeek-V3.2-Exp technical report,
   github.com/deepseek-ai/DeepSeek-V3.2-Exp, "DeepSeek Sparse Attention",
   eq. 1-2, and its ``inference/model.py`` ``Indexer``), from
   ``â = stop_gradient(a)``: ``qI = â WqI -> [s, H, D]``,
   ``kI = LayerNorm(â WkI) -> [s, D]`` (one key head), ``w = â Ww ->
   [s, H]``; rotary on the whole ``D`` of ``qI`` and ``kI`` at the same
   theta from component 0 (*assumed*);
   ``I[t, u] = sum_j w[t, j] * H^-1/2 * D^-1/2 * relu(qI[t, j] . kI[u])``
   for ``u <= t``. ``S_t`` = the ``min(t + 1, topk)`` positions ``u <= t``
   of largest ``I[t, u]``, ties to the lower ``u`` (``lax.top_k``).
   ``q_chunk_size`` / ``kv_chunk_size`` are taken as tile sizes and change
   no number (*assumed*).
4. Attention over ``S_t`` alone, head ``n`` on KV head
   ``n // (heads / kv_heads)``: ``o[t, n] = sum_{u in S_t}
   softmax_{u in S_t}(q[t, n] . k[u] / sqrt(d)) v[u]``;
   ``x' = x + concat(o) Wo``.
5. ``b = RMSNorm(x')``. ``r = softmax(b Wr)`` over all experts; ``T_t``
   its top ``k``; ``g_e = r_e / sum_{e' in T_t} r_e'``.
   ``m[t] = sum_{e in T_t, e held here} g_e Wd_e (silu(b Wg_e) * (b Wu_e))``.
   Output ``x' + m``. What the absent experts would have added is left
   out, here as in the program.
6. Loss ``L = CE + c_aux * sum_layers L_aux + sum_layers L_I``. ``CE``:
   mean cross-entropy over the table's slice (padded rows included, as
   the program's loss has them). ``L_aux``: the Switch load-balancing
   loss over all experts as the program's router has it, ``E * sum_e f_e
   P_e`` with ``f_e`` the share of the batch's assignments that fell on
   expert ``e`` and ``P_e`` the mean of ``r_e`` over the batch's tokens
   (coefficient *assumed*). A product of means over the batch does not
   split into blocks of rows: ``loss_part`` forms it over its block, so
   the cell's ``reference_block_rows`` is its whole batch.
   ``L_I = mean_t KL(p_t ||
   softmax_{u in S_t} I[t, u])`` with ``p_t[u] = sum_n P[t, n, u]`` over
   ``S_t``, L1-normalised, ``P`` the probabilities of step 4, detached
   (V3.2's sparse training stage: the indexer learns from ``L_I`` alone,
   on a detached input; the rest of the model from the language-model
   loss alone).

Departures, all for memory and none for a number: index scores,
selection, attention and the indexer's loss run in blocks of
``QUERY_BLOCK`` queries, the cross-entropy in blocks of positions, each
row of each layer is rematerialised in the backward pass, and each held
expert runs over every token with a zero gate where it was not chosen.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import transformer as T

QUERY_BLOCK = 256
NEG = -1e30


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rotary(x, positions, theta, sections=None):
    """Rotate-half on all of ``x [s, n, d]``. ``positions`` ``[s]``, or
    ``[c, s]`` with ``sections``: frequency ``i`` then reads the component
    whose section it falls in."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if sections is None:
        pos = positions.astype(jnp.float32)[:, None]             # [s, 1]
    else:
        component = np.repeat(np.arange(len(sections)), sections)
        pos = positions.astype(jnp.float32)[component].T         # [s, d/2]
    angle = (pos * inv)[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(s):
    return QUERY_BLOCK if s % QUERY_BLOCK == 0 else s


def index_projections(a, lp, arch, positions, quant):
    """Step 3's ``qI [s, H, D]``, ``kI [s, D]`` and scaled ``w [s, H]``."""
    H, D = arch["indexer_heads"], arch["indexer_dim"]
    s = a.shape[0]
    qi = T.matmul(a, lp["iwq"], quant).reshape(s, H, D)
    ki = T.layer_norm(T.matmul(a, lp["iwk"], quant), lp["ikn_g"],
                      lp["ikn_b"], arch["eps"])
    w = T.matmul(a, lp["iww"], quant) * (H ** -0.5 * D ** -0.5)
    qi = rotary(qi, positions[0], arch["theta"])
    ki = rotary(ki[:, None, :], positions[0], arch["theta"])[:, 0, :]
    return qi, ki, w


def index_rows(qi, w, ki, quant):
    """``I[t, u]`` for some queries ``t`` (``qi [c, H, D]``, ``w [c, H]``)
    against every key: ``[c, s]``."""
    logits = jnp.einsum("chd,ud->hcu", quant(qi), quant(ki),
                        precision=T.HIGHEST)
    return jnp.einsum("hcu,ch->cu", jax.nn.relu(logits), w,
                      precision=T.HIGHEST)


def index_scores(a, lp, arch, positions, quant):
    """Step 3's ``I [s, s]`` (entries above the diagonal are not used)."""
    qi, ki, w = index_projections(a, lp, arch, positions, quant)
    return index_rows(qi, w, ki, quant)


def exact_selection(scores, topk, first=0):
    """``S_t`` as a mask ``[c, s]`` for the queries ``first .. first + c``
    whose scores against every key are given: an exact top-k a row, ties
    to the lower position."""
    c, s = scores.shape
    t = first + jnp.arange(c)
    causal = jnp.arange(s)[None, :] <= t[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, s))
    chosen = jnp.zeros((c, s), bool).at[jnp.arange(c)[:, None],
                                        idx].set(True)
    return chosen & causal


def attend_rows(q, k, v, mask, quant):
    """Step 4 for some queries (``q [c, heads, d]``, ``mask [c, s]``):
    -> (``o [c, heads, d]``, the head-summed probabilities ``[c, s]``)."""
    c, heads, d = q.shape
    rep = heads // k.shape[1]
    qg = q.reshape(c, k.shape[1], rep, d)
    scores = jnp.einsum("cgrd,ugd->grcu", quant(qg), quant(k),
                        precision=T.HIGHEST) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG), axis=-1)
    probs = jnp.where(mask, probs, 0.0)
    out = jnp.einsum("grcu,ugd->cgrd", quant(probs), quant(v),
                     precision=T.HIGHEST)
    return out.reshape(c, heads, d), jnp.sum(probs, axis=(0, 1))


def indexer_kl(scores, mask, summed):
    """Step 6's ``KL(p_t || softmax_{S_t} I[t])`` for some queries,
    summed over them."""
    target = summed / jnp.sum(summed, axis=-1, keepdims=True)
    logq = jax.nn.log_softmax(jnp.where(mask, scores, NEG), axis=-1)
    live = mask & (target > 0)
    return jnp.sum(jnp.where(
        live, target * (jnp.log(jnp.where(live, target, 1.0)) - logq), 0.0))


def indexed_attention(a, q, k, v, lp, arch, positions, quant):
    """Steps 3, 4 and ``L_I`` in blocks of queries, each rematerialised:
    a block's index scores, its selection, its attention over the
    selection and its part of the indexer's loss, so that no ``[s, s]``
    array is ever held. -> (``o [s, heads, d]``, ``L_I``)."""
    s = a.shape[0]
    c = _blocks(s)
    qi, ki, w = index_projections(jax.lax.stop_gradient(a), lp, arch,
                                  positions, quant)

    @jax.checkpoint
    def rows(block):
        first, qc, qic, wc = block
        scores = index_rows(qic, wc, ki, quant)
        mask = exact_selection(jax.lax.stop_gradient(scores),
                               arch["indexer_topk"], first)
        out, summed = attend_rows(qc, k, v, mask, quant)
        return out, indexer_kl(scores, mask, jax.lax.stop_gradient(summed))

    def split(x):
        return x.reshape((s // c, c) + x.shape[1:])

    out, kl = jax.lax.map(rows, (jnp.arange(0, s, c), split(q), split(qi),
                                 split(w)))
    return out.reshape(q.shape), jnp.sum(kl) / s


def experts(b, lp, arch, quant):
    """Step 5: -> (the held experts' part ``m [s, hidden]``, and for
    ``L_aux`` this sequence's ``f [experts]`` and ``P [experts]``)."""
    k = arch["top_k"]
    r = jax.nn.softmax(T.matmul(b, lp["router"], quant), axis=-1)
    top, idx = jax.lax.top_k(r, k)
    chosen = jnp.zeros(r.shape, bool).at[
        jnp.arange(r.shape[0])[:, None], idx].set(True)
    gates = jnp.where(chosen, r, 0.0) / jnp.sum(top, axis=-1, keepdims=True)
    f = jnp.mean(chosen.astype(jnp.float32), axis=0) / k
    off, n = arch["expert_offset"], arch["experts_held"]

    def one(m, ew):
        gate_w, up_w, down_w, g = ew
        h = jax.nn.silu(T.matmul(b, gate_w, quant)) \
            * T.matmul(b, up_w, quant)
        return m + g[:, None] * T.matmul(h, down_w, quant), None

    m, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(b),
        (lp["egate"], lp["eup"], lp["edown"], gates[:, off:off + n].T))
    return m, f, jnp.mean(r, axis=0)


def block(x, lp, arch, positions, quant):
    """One layer on one sequence: -> (output, ``f``, ``P``, ``L_I``)."""
    s = x.shape[0]
    heads, kv_heads, d = arch["heads"], arch["kv_heads"], arch["head_dim"]
    eps = arch["eps"]
    a = rms_norm(x, lp["ln1_g"], eps)
    q = T.matmul(a, lp["wq"], quant).reshape(s, heads, d)
    k = T.matmul(a, lp["wk"], quant).reshape(s, kv_heads, d)
    v = T.matmul(a, lp["wv"], quant).reshape(s, kv_heads, d)
    q = rotary(rms_norm(q, lp["qn_g"], eps), positions, arch["theta"],
               arch["sections"])
    k = rotary(rms_norm(k, lp["kn_g"], eps), positions, arch["theta"],
               arch["sections"])
    o, li = indexed_attention(a, q, k, v, lp, arch, positions, quant)
    x = x + T.matmul(o.reshape(s, heads * d), lp["wo"], quant)
    m, f, p = experts(rms_norm(x, lp["ln2_g"], eps), lp, arch, quant)
    return x + m, f, p, li


def loss_part(params, arch, batch, totals, quant=T.identity):
    """This block of rows' part of the batch loss: the parts of all
    blocks add up to step 6's ``L`` over the whole batch, but for
    ``L_aux``, which is formed over the block's rows and is the batch's
    only when the block is the batch. The layers are the outer loop and
    the block's rows the inner one (each row of each layer rematerialised
    in the backward pass), so that the gradient of a layer's tensors is
    summed over the rows inside that layer and only one gradient of the
    whole model is ever held."""
    layers = {k[len("layers."):]: v for k, v in params.items()
              if k.startswith("layers.")}

    def body(x, lp):
        @jax.checkpoint
        def row(xp):
            return block(xp[0], lp, arch, xp[1], quant)

        x, f, p, li = jax.lax.map(row, (x, batch["positions"]))
        aux = arch["experts"] * jnp.sum(jnp.mean(f, axis=0)
                                        * jnp.mean(p, axis=0))
        return x, (aux, jnp.sum(li))

    x, (aux, li) = jax.lax.scan(body, params["wte"][batch["tokens"]],
                                layers)
    x = rms_norm(x, params["lnf_g"], arch["eps"])
    rows, s, h = x.shape
    c = _blocks(s)

    @jax.checkpoint
    def nll(xl):
        return jnp.sum(T.token_nll(
            T.matmul(xl[0], params["head"], quant), xl[1]))

    total = jnp.sum(jax.lax.map(nll, (
        x.reshape(rows * s // c, c, h),
        batch["labels"].reshape(rows * s // c, c))))
    return (total / totals["tokens"]
            + (arch["aux_coef"] * jnp.sum(aux) * rows + jnp.sum(li))
            / totals["rows"])


def totals(batch):
    """What a block's part is divided by, from the whole batch."""
    return {"tokens": float(batch["tokens"].size),
            "rows": float(batch["tokens"].shape[0])}
