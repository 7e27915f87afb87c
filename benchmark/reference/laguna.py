"""Laguna-XS.2 (``model_type`` ``laguna``,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json), one
chip's share of it, written out plainly in float32 at ``highest`` matmul
precision from the configuration and the published descriptions it names
(YaRN, arXiv:2309.00071, and HF ``_compute_yarn_parameters`` for the
frequencies; DeepSeek-V3, arXiv:2412.19437, section 2.1.2, for the sigmoid
router). Imports nothing of the program.

Pre-norm residual stream, ``h <- h + Attn(RMSNorm(h))`` then ``h <- h +
FFN(RMSNorm(h))`` with the configuration's eps and a learned gain each, a
final RMSNorm, an untied head. No biases anywhere.

- **Attention**, for one sequence ``u [s, hidden]`` and a layer of ``n``
  query heads (the layer's ``num_attention_heads_per_layer``) over 8 key /
  value heads of 128: ``q = u W_Q``, ``k = u W_K``, ``v = u W_V``; query
  head ``i`` reads key / value head ``i // (n / 8)``. Rotary on q and k
  by rotate-half pairs ``(x[j], x[j + r / 2])`` of the first ``r``
  channels of a head, turned by ``t * inv[j]`` at position ``t``, cos and
  sin times ``m``; the other ``128 - r`` channels pass through. A full
  layer (``full_attention``): ``r`` = 64, ``inv`` YaRN's over 32
  frequencies of base 500000 (the extrapolated ``base^(-2j / r)`` below
  the correction range, divided by ``factor`` above it, a linear ramp
  between; the range ``floor`` / ``ceil`` of ``r ln(L / (2 pi beta)) /
  (2 ln base)`` at ``beta_fast`` and ``beta_slow``, ``L`` the original
  4096 positions), ``m`` the attention factor; causal softmax of ``q k /
  sqrt(128)``. A window layer (``sliding_attention``): ``r`` = 128, base
  10000, ``m`` = 1; query ``t`` sees key ``t'`` iff ``0 <= t - t' <
  512``. Then the gate: ``o_ti <- sigmoid(u_t W_G)_i o_ti``; output ``W_O
  [o_t1 .. o_tn]``.
- **Layer 0** (``mlp_layer_types`` ``dense``): SwiGLU, ``W_down (silu(W_gate
  u) * W_up u)``, of the dense width.
- **The other layers**: ``s = sigmoid(u W_r)`` over all the published
  experts; chosen = the ``k`` largest of ``s + b`` (``b`` zero and not
  updated, *assumed*); gates ``g = factor * s_chosen / (sum s_chosen +
  1e-20)``; ``out = sum_{e chosen and held here} g_e E_e(u) + S(u)``,
  ``E_e`` SwiGLU of the experts' width and ``S`` the shared SwiGLU expert,
  added unweighted. What the absent experts would have added is left out,
  here as in the program.
- **Loss**: mean cross-entropy over the table's slice.

Departures, all for memory and none for a number: a row of a sub-block at
a time, each rematerialised in the backward pass, and the layer around
them once more (one input a layer is kept); attention in blocks of
``QUERY_BLOCK`` queries, so that no ``[n, s, s]`` array stands whole, a
window layer's block against the ``512 + QUERY_BLOCK`` keys that hold its
band; each held expert over every token with a zero gate where it was not
chosen; the feed-forwards in blocks of ``TOKEN_BLOCK`` tokens; the
cross-entropy in blocks of positions.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference import deepseek_v3 as D
from benchmark.reference import transformer as T

QUERY_BLOCK = 128
TOKEN_BLOCK = 1024    # tokens of a feed-forward at a time
NEG = -1e30
FULL, WINDOW = "full_attention", "sliding_attention"


def yarn_inverse_frequencies(arch, dim):
    """-> (inverse frequencies ``[dim / 2]``, attention factor) of a full
    layer's rotary channels."""
    base, factor = arch["full_theta"], arch["yarn_factor"]

    def correction(rotations):
        return dim * math.log(arch["yarn_original"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(arch["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction(arch["yarn_beta_slow"])), dim - 1)
    extrapolated = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv = extrapolated / factor * ramp + extrapolated * (1.0 - ramp)
    return inv, arch["yarn_attention_factor"]


def rotary(x, inv, scale):
    """``x [s, heads, d]`` at positions ``0 .. s - 1``: the first ``2 *
    len(inv)`` channels turned by rotate-half pairs, cos and sin times
    ``scale``; the rest as they are."""
    s, r = x.shape[0], 2 * inv.shape[0]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def layer_rotary(arch, i, d):
    """(inverse frequencies, scale) of layer ``i``'s rotary."""
    if arch["layer_types"][i] == FULL:
        return yarn_inverse_frequencies(arch, int(d * arch["full_percent"]))
    r = int(d * arch["window_percent"])
    theta = arch["window_theta"]
    return theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r), 1.0


def attention(u, lp, arch, i, quant):
    s = u.shape[0]
    n, g, d = arch["layer_heads"][i], arch["kv_heads"], arch["head_dim"]
    inv, scale = layer_rotary(arch, i, d)
    q = rotary(T.matmul(u, lp["wq"], quant).reshape(s, n, d), inv, scale)
    k = rotary(T.matmul(u, lp["wk"], quant).reshape(s, g, d), inv, scale)
    v = T.matmul(u, lp["wv"], quant).reshape(s, g, d)
    window = arch["window"] if arch["layer_types"][i] == WINDOW else None
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    # the keys a block of queries may see: all of them, or the band's
    span = s if window is None else min(s, window + block)

    @jax.checkpoint
    def rows(first_and_q):
        first, qc = first_and_q
        start = jnp.clip(first + block - span, 0, s - span)
        kc = jax.lax.dynamic_slice_in_dim(k, start, span)
        vc = jax.lax.dynamic_slice_in_dim(v, start, span)
        scores = jnp.einsum("cgrd,ugd->grcu",
                            quant(qc.reshape(block, g, n // g, d)),
                            quant(kc), precision=T.HIGHEST) / math.sqrt(d)
        behind = ((first + jnp.arange(block))[:, None]
                  - (start + jnp.arange(span))[None, :])
        seen = behind >= 0
        if window is not None:
            seen = seen & (behind < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, NEG), axis=-1)
        return jnp.einsum("grcu,ugd->cgrd", quant(probs), quant(vc),
                          precision=T.HIGHEST).reshape(block, n, d)

    o = jax.lax.map(rows, (jnp.arange(0, s, block),
                           q.reshape(s // block, block, n, d)))
    o = o.reshape(s, n, d)
    if arch["gate"]:
        o = o * jax.nn.sigmoid(T.matmul(u, lp["wg"], quant))[..., None]
    return T.matmul(o.reshape(s, n * d), lp["wo"], quant)


def dense_ffn(u, lp, arch, quant):
    return D.swiglu(u, lp["d_gate"], lp["d_up"], lp["d_down"], quant)


def expert_ffn(u, lp, arch, quant):
    """The held experts' part of the routed sum plus the shared expert,
    over the tokens ``u [T, hidden]`` (no auxiliary loss)."""
    return D.expert_ffn(u, lp, arch, quant)[0]


def loss_part(params, arch, batch, totals, quant=T.identity):
    """This block of rows' part of the batch's mean cross-entropy. The
    layers are the outer loop and the block's rows the inner one, so that a
    layer's gradient is summed over the rows inside that layer."""
    eps = arch["eps"]
    x = params["wte"][batch["tokens"]]
    rows, s, h = x.shape
    block = TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else s
    for i in range(arch["layers"]):
        lp = D.layer_params(params, i)
        ffn = dense_ffn if arch["mlp_types"][i] == "dense" else expert_ffn

        @jax.checkpoint
        def attend(xr, lp=lp, i=i):
            return xr + attention(D.rms_norm(xr, lp["ln1_g"], eps), lp, arch,
                                  i, quant)

        @jax.checkpoint
        def feed(xt, lp=lp, ffn=ffn):
            return xt + ffn(D.rms_norm(xt, lp["ln2_g"], eps), lp, arch,
                            quant)

        # the whole layer once more under a checkpoint of its own: one
        # input a layer is kept, not one a sub-block
        @jax.checkpoint
        def layer(x, attend=attend, feed=feed):
            x = jax.lax.map(attend, x)
            return jax.lax.map(feed, x.reshape(-1, block, h)).reshape(
                rows, s, h)

        x = layer(x)
    x = D.rms_norm(x, params["lnf_g"], eps)
    c = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def nll(xl):
        return jnp.sum(T.token_nll(
            T.matmul(xl[0], params["head"], quant), xl[1]))

    total = jnp.sum(jax.lax.map(nll, (
        x.reshape(rows * s // c, c, h),
        batch["labels"].reshape(rows * s // c, c))))
    return total / totals["tokens"]


def totals(batch):
    """What a block's part is divided by, from the whole batch."""
    return {"tokens": float(batch["tokens"].size)}
