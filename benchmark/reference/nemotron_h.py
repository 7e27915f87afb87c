"""NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (``model_type`` ``nemotron_h``,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json),
one chip's share of it, written out plainly in float32 at ``highest``
matmul precision. Imports nothing of the program.

Pre-norm residual stream, **one sub-block a layer**: ``x <- x + f(RMSNorm(x))``
with the configuration's eps and a learned gain, ``f`` by the letter of
``hybrid_override_pattern``; a final RMSNorm; an untied head.

- ``M``, Mamba-2 (Dao & Gu 2024, arXiv:2405.21060), ``H`` heads of ``P``
  channels in ``G`` groups, ``N`` states a channel, for one sequence
  ``u [s, hidden]``: ``[z | xBC | dt] = u W_in`` with widths ``H P |
  H P + 2 G N | H``; ``xBC = silu(conv(xBC) + b_conv)`` with a causal
  depthwise convolution of ``conv_kernel`` taps (``out[t] = sum_k w[k]
  xBC[t - 3 + k]``, zeros before the row's start), split into ``x [H,
  P]``, ``B [G, N]``, ``C [G, N]``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; for head ``h`` in group ``g = h // (H / G)``:
  ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T`` (``S`` is ``[P, N]``,
  zero before the row's start), ``y_t = S_t C_t + D_h x_t``;
  ``y = RMSNorm_grouped(y * silu(z))`` over groups of ``H P / G``
  channels with a gain (the gate before the norm: *assumed*);
  ``out = y W_out``. **The recurrence itself** runs here, a step at a
  time (:func:`recurrence`), not the chunked algorithm the program uses.
- ``E``: ``s = sigmoid(u W_r)`` over all the published experts; chosen =
  the top ``k`` of ``s + b`` (``b`` the ``e_score_correction_bias``, a
  buffer: zero and not updated here, *assumed*; ``n_group`` =
  ``topk_group`` = 1, no group limit); ``w = factor * s_chosen /
  (sum s_chosen + 1e-20)``; ``out = sum_{e chosen and held here} w_e W2_e
  relu(W1_e u)^2 + Ws2 relu(Ws1 u)^2``, the shared expert unweighted. No
  auxiliary loss. What the absent experts would have added is left out,
  here as in the program.
- ``*``: grouped-query attention, no bias, causal, scale ``d^-1/2``, **no
  positional encoding** (*assumed*: the config carries ``rope_theta``,
  Nemotron-H's published modelling code applies none).

The chip holds a share of the mixers' heads too (the configuration's
``deployment``): this file runs the heads it is given, and the partial
result goes on to the next layer.

Loss: mean cross-entropy over the table's slice.

**Parameters whose published start is not one of** ``benchmark/weights.py``'s
**three** (normal 0.02, ones, zeros): ``A_log = log(uniform 1..16)``,
``dt_bias`` the inverse softplus of a log-uniform step in
``time_step_min..max``, the convolution's taps and bias uniform in
``+-conv_kernel^-1/2`` (torch's ``Conv1d`` default). Each is that start,
drawn **once from a fixed generator and not from the seed**
(:func:`init_offsets`), plus the canonical tensor, which the seed makes
(normal 0.02; zeros for the bias) and which is what the optimizer steps.
Adam without decay moves a parameter by its gradient alone, so this is
training the parameter itself from ``offset + canonical``.

Departures, all for memory and none for a number: a row of a layer at a
time, each rematerialised in the backward pass (and ``LAYER_GROUP`` layers
together above that, so that a third of the layers' inputs are kept;
the control runs them ungrouped, see :func:`loss_part`), the mixer's
projection and its gated norm once more inside that; the recurrence in
blocks of
``SCAN_BLOCK`` steps under ``jax.checkpoint`` (the backward keeps a
state a block and not a state a step); attention in blocks of
``QUERY_BLOCK`` queries; each held expert over every token with a zero
weight where it was not chosen; the cross-entropy in blocks of positions.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import transformer as T

QUERY_BLOCK = 256
SCAN_BLOCK = 128
LAYER_GROUP = 3      # layers rematerialised together, then one by one
NEG = -1e30
OFFSET_SEED = 33     # the fixed generator of init_offsets
ROUTER_BIAS = 0.0    # e_score_correction_bias: a buffer, zero, not updated


def init_offsets(arch: dict) -> dict:
    """canonical name -> float32 array: the published start of the
    tensors above, one draw for every run, seed and side."""
    rng = np.random.default_rng(OFFSET_SEED)
    H, K = arch["m_heads"], arch["conv_kernel"]
    C = H * arch["m_head_dim"] + 2 * arch["m_groups"] * arch["state"]
    lo, hi = math.log(arch["dt_min"]), math.log(arch["dt_max"])
    bound = K ** -0.5
    out = {}
    for i, kind in enumerate(arch["pattern"]):
        if kind != "M":
            continue
        dt = np.maximum(np.exp(rng.uniform(lo, hi, H)), arch["dt_floor"])
        out[f"l{i}.m_a_log"] = np.log(rng.uniform(1.0, 16.0, H))
        out[f"l{i}.m_dt_bias"] = dt + np.log(-np.expm1(-dt))
        out[f"l{i}.m_conv_w"] = rng.uniform(-bound, bound, (K, C))
        out[f"l{i}.m_conv_b"] = rng.uniform(-bound, bound, C)
    return {k: v.astype(np.float32) for k, v in out.items()}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def causal_conv(x, w, b):
    """``x [s, C]``, ``w [K, C]``: ``out[t] = b + sum_k w[k] x[t-(K-1)+k]``."""
    K, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return b + sum(w[k] * padded[k:k + s] for k in range(K))


def recurrence(x, dt, A, B, C):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, one step after another. ``x [s, H, P]``, ``dt [s, H]``,
    ``A [H]``, ``B``, ``C`` ``[s, G, N]`` -> ``[s, H, P]``."""
    s, H, P = x.shape
    G, N = B.shape[1:]
    R = H // G

    def step(S, t):
        xt, dtt, Bt, Ct = t
        decay = jnp.exp(dtt * A).reshape(G, R, 1, 1)
        S = decay * S + ((dtt[:, None] * xt).reshape(G, R, P, 1)
                         * Bt[:, None, None, :])
        return S, jnp.sum(S * Ct[:, None, None, :], axis=-1)

    @jax.checkpoint
    def block(S, ts):
        return jax.lax.scan(step, S, ts)

    n = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    blocks = jax.tree_util.tree_map(
        lambda t: t.reshape((s // n, n) + t.shape[1:]), (x, dt, B, C))
    _, y = jax.lax.scan(block, jnp.zeros((G, R, P, N), jnp.float32), blocks)
    return y.reshape(s, H, P)


def mamba(u, lp, arch, quant):
    s = u.shape[0]
    H, P = arch["m_heads"], arch["m_head_dim"]
    G, N = arch["m_groups"], arch["state"]
    inner, bc = H * P, G * N

    @jax.checkpoint
    def project(u):
        proj = T.matmul(u, lp["m_in"], quant)
        z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * bc],
                      proj[:, 2 * inner + 2 * bc:])
        xbc = jax.nn.silu(causal_conv(xbc, lp["m_conv_w"], lp["m_conv_b"]))
        return (z, xbc[:, :inner].reshape(s, H, P),
                xbc[:, inner:inner + bc].reshape(s, G, N),
                xbc[:, inner + bc:].reshape(s, G, N),
                jax.nn.softplus(dt + lp["m_dt_bias"]))

    @jax.checkpoint
    def gate_norm_out(y, x, z):
        y = y + lp["m_d_g"][:, None] * x
        y = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, G, inner // G)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + arch["eps"])
        return T.matmul(y.reshape(s, inner) * lp["m_norm_g"], lp["m_out"],
                        quant)

    z, x, B, C, dt = project(u)
    # the products' operands go through ``quant``, as a matmul's do
    y = recurrence(quant(x), dt, -jnp.exp(lp["m_a_log"]), quant(B), quant(C))
    return gate_norm_out(y, x, z)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def experts(u, lp, arch, quant):
    """The held experts' part of the routed sum plus the shared expert."""
    k = arch["top_k"]
    scores = jax.nn.sigmoid(T.matmul(u, lp["e_router"], quant))
    _, idx = jax.lax.top_k(scores + ROUTER_BIAS, k)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    picked = jnp.where(chosen, scores, 0.0)
    w = arch["routed_scale"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    off, n = arch["expert_offset"], arch["experts_held"]

    @jax.checkpoint
    def one(ew):
        up, down, g = ew
        return g[:, None] * T.matmul(relu2(T.matmul(u, up, quant)), down,
                                     quant)

    # the sum is taken outside the checkpoint: the backward then keeps no
    # running sum an expert
    m, _ = jax.lax.scan(lambda m, ew: (m + one(ew), None), jnp.zeros_like(u),
                        (lp["e_up"], lp["e_down"], w[:, off:off + n].T))
    return m + T.matmul(relu2(T.matmul(u, lp["e_sup"], quant)),
                        lp["e_sdown"], quant)


def attention(u, lp, arch, quant):
    s = u.shape[0]
    heads, kv, d = arch["heads"], arch["kv_heads"], arch["head_dim"]
    rep = heads // kv
    q = T.matmul(u, lp["a_wq"], quant).reshape(s, kv, rep, d)
    k = T.matmul(u, lp["a_wk"], quant).reshape(s, kv, d)
    v = T.matmul(u, lp["a_wv"], quant).reshape(s, kv, d)
    c = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def rows(block):
        first, qc = block
        scores = jnp.einsum("cgrd,ugd->grcu", quant(qc), quant(k),
                            precision=T.HIGHEST) / math.sqrt(d)
        seen = jnp.arange(s)[None, :] <= (first + jnp.arange(c))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, NEG), axis=-1)
        return jnp.einsum("grcu,ugd->cgrd", quant(probs), quant(v),
                          precision=T.HIGHEST)

    o = jax.lax.map(rows, (jnp.arange(0, s, c),
                           q.reshape(s // c, c, kv, rep, d)))
    return T.matmul(o.reshape(s, heads * d), lp["a_wo"], quant)


SUB_BLOCKS = {"M": mamba, "E": experts, "*": attention}


def layer_params(params, arch, i):
    """Layer ``i``'s tensors under their own names (``l<i>.`` cut off),
    the offsets added."""
    offsets = init_offsets(arch)
    prefix = f"l{i}."
    return {k[len(prefix):]: v + offsets.get(k, 0.0)
            for k, v in params.items() if k.startswith(prefix)}


def loss_part(params, arch, batch, totals, quant=T.identity):
    """This block of rows' part of the batch loss (the parts of all blocks
    add up to the batch's mean cross-entropy). The layers are the outer
    loop and the block's rows the inner one, so that a layer's gradient is
    summed over the rows inside that layer and one gradient of the whole
    model is ever held."""
    def layer(x, i):
        lp, f = layer_params(params, arch, i), SUB_BLOCKS[arch["pattern"][i]]

        @jax.checkpoint
        def row(xr):
            return xr + f(rms_norm(xr, lp["ln_g"], arch["eps"]), lp, arch,
                          quant)

        # an expert layer sees tokens, not sequences: the block's rows go
        # through it as one, so that its gradient (most of the model's) is
        # formed once and not summed over rows beside a copy of itself
        if arch["pattern"][i] == "E":
            return row(x.reshape(-1, x.shape[-1])).reshape(x.shape)
        return jax.lax.map(row, x)

    # the control's rounding keeps more alive under the nested
    # rematerialisation than it saves (5.03 GB of temporaries against 3.89
    # ungrouped, where the reference itself needs 2.69 against 3.63)
    size = LAYER_GROUP if quant is T.identity else 1
    x = params["wte"][batch["tokens"]]
    for first in range(0, arch["layers"], size):
        @jax.checkpoint
        def group(x, first=first):
            for i in range(first, min(first + size, arch["layers"])):
                x = layer(x, i)
            return x

        x = group(x)
    x = rms_norm(x, params["lnf_g"], arch["eps"])
    rows, s, h = x.shape
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
