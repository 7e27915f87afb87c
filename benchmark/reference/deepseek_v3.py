"""Moonlight-16B-A3B (``model_type`` ``deepseek_v3``,
https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json),
one chip's share of it, written out plainly in float32 at ``highest``
matmul precision from the published description (DeepSeek-V2,
arXiv:2405.04434, section 2.1, for the attention; DeepSeek-V3,
arXiv:2412.19437, section 2.1.2, for the experts and their balancing).
Imports nothing of the program.

Pre-norm residual stream, ``h <- h + Attn(RMSNorm(h))`` then ``h <- h +
FFN(RMSNorm(h))`` with the configuration's eps and a learned gain each, a
final RMSNorm, an untied head. No biases anywhere.

- **Attention** (multi-head latent attention, ``q_lora_rank`` null), ``n``
  heads, for one sequence ``u [s, hidden]``: ``q = u W_Q`` in ``n x (d_c +
  d_r)``, a head's ``[q^C (d_c = 128) ; q^R (d_r = 64)]``; ``[c (512) ;
  k^R (64)] = u W_DKV``; ``c <- RMSNorm(c)`` with a gain of its own;
  ``[k^C_i (128) ; v_i (128)] = c W_UKV`` for head ``i``. Rotary
  (``rope_theta``, no scaling) on ``q^R`` of every head and on the one
  ``k^R`` a token, which all heads share: the pair ``(x[2j], x[2j + 1])``
  turns by ``t * theta^(-2j / d_r)`` at position ``t`` (the published
  weights' convention; the published code's de-interleave is the same
  rotation followed by one permutation of both operands, which the dot
  product does not see). Scores ``(q^C_ti . k^C_ji + q^R_ti . k^R_j) /
  sqrt(d_c + d_r)``, causal softmax, ``o_ti = sum_j p v_ji``, output
  ``W_O [o_t1 .. o_tn]``.
- **Layers before** ``first_k_dense_replace``: SwiGLU, ``W_down (silu(W_gate
  u) * W_up u)``, of the dense width.
- **The other layers**: ``s = sigmoid(u W_r)`` over all the published
  experts; chosen = the ``k`` largest of ``s + b`` (``b`` the
  ``e_score_correction_bias``: zero and not updated here, *assumed*;
  ``n_group`` = ``topk_group`` = 1, so the group limit is the identity);
  gates ``g = factor * s_chosen / (sum s_chosen + 1e-20)``; ``out = sum_{e
  chosen and held here} g_e E_e(u) + S(u)``, ``E_e`` SwiGLU of the
  experts' width and ``S`` one SwiGLU of ``n_shared_experts`` times that
  width, added unweighted. What the absent experts would have added is
  left out, here as in the program.
- **Loss**: mean cross-entropy over the table's slice plus (``seq_aux``)
  ``alpha`` times, for each expert layer, the mean over the batch's
  sequences of ``sum_i f_i P_i`` with ``f_i = E / (k T) * #{t : i chosen at
  t}`` (no gradient) and ``P_i = (1 / T) sum_t s_it / sum_j s_jt``, over
  all ``E`` published experts (the router is whole on every chip).

Departures, all for memory and none for a number: a row of a sub-block at
a time, each rematerialised in the backward pass, and the layer around
them once more (one input a layer is kept); attention in blocks of
``QUERY_BLOCK`` queries, so that no ``[n, s, s]`` array stands whole; each
held expert over every token with a zero gate where it was not chosen;
the feed-forwards (dense, routed and shared) in blocks of ``TOKEN_BLOCK``
tokens, so that a layer's gradient is summed over the blocks; the
cross-entropy in blocks of positions.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference import transformer as T

QUERY_BLOCK = 128
TOKEN_BLOCK = 1024    # tokens of a feed-forward at a time
NEG = -1e30
ROUTER_BIAS = 0.0    # e_score_correction_bias: a buffer, zero, not updated


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rotary(x, theta):
    """``x [s, ..., d]`` at positions ``0 .. s - 1``: the pair ``(x[2j],
    x[2j + 1])`` turned by ``t * theta^(-2j / d)``."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    angle = angle.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(u, lp, arch, quant):
    s = u.shape[0]
    n, dc, dr, dv = (arch["heads"], arch["nope_dim"], arch["rope_dim"],
                     arch["v_dim"])
    lat, theta = arch["kv_rank"], arch["theta"]
    q = T.matmul(u, lp["wq"], quant).reshape(s, n, dc + dr)
    q = jnp.concatenate([q[..., :dc], rotary(q[..., dc:], theta)], axis=-1)
    down = T.matmul(u, lp["wdkv"], quant)
    c = rms_norm(down[:, :lat], lp["kvn_g"], arch["eps"])
    k_rope = rotary(down[:, lat:], theta)
    kv = T.matmul(c, lp["wukv"], quant).reshape(s, n, dc + dv)
    k = jnp.concatenate(
        [kv[..., :dc], jnp.broadcast_to(k_rope[:, None, :], (s, n, dr))],
        axis=-1)
    v = kv[..., dc:]
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def rows(first_and_q):
        first, qc = first_and_q
        scores = jnp.einsum("cnd,und->ncu", quant(qc), quant(k),
                            precision=T.HIGHEST) / math.sqrt(dc + dr)
        seen = jnp.arange(s)[None, :] <= (first + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, NEG), axis=-1)
        return jnp.einsum("ncu,und->cnd", quant(probs), quant(v),
                          precision=T.HIGHEST)

    o = jax.lax.map(rows, (jnp.arange(0, s, block),
                           q.reshape(s // block, block, n, dc + dr)))
    return T.matmul(o.reshape(s, n * dv), lp["wo"], quant)


def swiglu(u, gate, up, down, quant):
    return T.matmul(jax.nn.silu(T.matmul(u, gate, quant))
                    * T.matmul(u, up, quant), down, quant)


def dense_ffn(u, lp, arch, quant):
    return swiglu(u, lp["d_gate"], lp["d_up"], lp["d_down"], quant)


def route(u, lp, arch, quant):
    """-> (scores ``[T, E]``, chosen ``[T, E]`` bool, gates ``[T, E]``,
    zero off the chosen)."""
    scores = jax.nn.sigmoid(T.matmul(u, lp["e_router"], quant))
    _, idx = jax.lax.top_k(scores + ROUTER_BIAS, arch["top_k"])
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    picked = jnp.where(chosen, scores, 0.0)
    gates = arch["routed_scale"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return scores, chosen, gates


def balance_loss(scores, chosen, arch):
    """One sequence's ``sum_i f_i P_i`` (``scores``, ``chosen`` ``[T,
    E]``)."""
    T_, E = scores.shape
    f = jax.lax.stop_gradient(
        jnp.sum(chosen, axis=0) * (E / (arch["top_k"] * T_)))
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    return jnp.sum(f * P)


def expert_ffn(u, lp, arch, quant):
    """The held experts' part of the routed sum plus the shared expert,
    over the tokens ``u [T, hidden]``; -> (out, scores, chosen)."""
    scores, chosen, gates = route(u, lp, arch, quant)
    off, n = arch["expert_offset"], arch["experts_held"]

    @jax.checkpoint
    def one(ew):
        gate, up, down, g = ew
        return g[:, None] * swiglu(u, gate, up, down, quant)

    # the sum is taken outside the checkpoint: the backward then keeps no
    # running sum an expert
    routed, _ = jax.lax.scan(
        lambda m, ew: (m + one(ew), None), jnp.zeros_like(u),
        (lp["e_gate"], lp["e_up"], lp["e_down"], gates[:, off:off + n].T))
    shared = jax.checkpoint(
        lambda t: swiglu(t, lp["s_gate"], lp["s_up"], lp["s_down"], quant))
    return routed + shared(u), scores, chosen


def layer_params(params, i):
    """Layer ``i``'s tensors under their own names (``l<i>.`` cut off)."""
    prefix = f"l{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def loss_part(params, arch, batch, totals, quant=T.identity):
    """This block of rows' part of the batch loss: the parts of all blocks
    add up to the batch's mean cross-entropy plus ``alpha`` times the
    balance loss averaged over the batch's sequences. The layers are the
    outer loop and the block's rows the inner one, so that a layer's
    gradient is summed over the rows inside that layer."""
    eps = arch["eps"]
    x = params["wte"][batch["tokens"]]
    rows, s, h = x.shape
    block = TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else s
    balance = 0.0
    for i in range(arch["layers"]):
        lp = layer_params(params, i)

        @jax.checkpoint
        def attend(xr, lp=lp):
            return xr + attention(rms_norm(xr, lp["ln1_g"], eps), lp, arch,
                                  quant)

        @jax.checkpoint
        def feed(xt, lp=lp):
            return xt + dense_ffn(rms_norm(xt, lp["ln2_g"], eps), lp, arch,
                                  quant)

        @jax.checkpoint
        def experts(xt, lp=lp):
            out, scores, chosen = expert_ffn(
                rms_norm(xt, lp["ln2_g"], eps), lp, arch, quant)
            return xt + out, scores, chosen

        # the whole layer once more under a checkpoint of its own: one
        # input a layer is kept, not one a sub-block
        @jax.checkpoint
        def layer(x, i=i, attend=attend, feed=feed, experts=experts):
            x = jax.lax.map(attend, x)
            # a feed-forward sees tokens, not sequences: in blocks of
            # them, so that its wide activations stand a block at a time;
            # the balance loss sees sequences, from the blocks' scores and
            # choices put together
            blocks = x.reshape(-1, block, h)
            if i < arch["dense_layers"]:
                return jax.lax.map(feed, blocks).reshape(rows, s, h), 0.0
            blocks, scores, chosen = jax.lax.map(experts, blocks)
            E = scores.shape[-1]
            per_row = jax.vmap(lambda sc, ch: balance_loss(sc, ch, arch))(
                scores.reshape(rows, s, E), chosen.reshape(rows, s, E))
            return blocks.reshape(rows, s, h), jnp.sum(per_row)

        x, layer_balance = layer(x)
        balance = balance + layer_balance
    x = rms_norm(x, params["lnf_g"], eps)
    c = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def nll(xl):
        return jnp.sum(T.token_nll(
            T.matmul(xl[0], params["head"], quant), xl[1]))

    total = jnp.sum(jax.lax.map(nll, (
        x.reshape(rows * s // c, c, h),
        batch["labels"].reshape(rows * s // c, c))))
    return total / totals["tokens"] \
        + arch["aux_alpha"] * balance / totals["rows"]


def totals(batch):
    """What a block's part is divided by, from the whole batch."""
    return {"tokens": float(batch["tokens"].size),
            "rows": float(batch["tokens"].shape[0])}
