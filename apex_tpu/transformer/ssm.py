"""Mamba-2 mixer: a selective state-space layer (Dao & Gu 2024,
"Transformers are SSMs", arXiv:2405.21060) as the hybrid models publish
it (``model_type`` ``nemotron_h``, ``mamba2``).

For one sequence, ``H`` heads of ``P`` channels in ``G`` groups that share
``B`` and ``C``, ``N`` states a channel::

    [z | xBC | dt] = u W_in                  widths H P | H P + 2 G N | H
    xBC = silu(conv1d_causal_depthwise(xBC) + b_conv)   -> x [H, P], B, C [G, N]
    dt  = softplus(dt + dt_bias),   A = -exp(A_log)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        S [P, N], S_{-1} = 0
    y_t = S_t C_t + D_h x_t
    out = RMSNorm_grouped(y * silu(z)) W_out            groups of H P / G channels

:func:`ssd_chunked` computes the recurrence by the paper's chunked
algorithm, as matrix products over chunks of ``chunk_size`` steps, so
that the MXU does the work: the diagonal blocks (a chunk's own steps
against each other), each chunk's state, the carry of the state from
chunk to chunk, and the carried state's part of each output. Decays,
their cumulative sums and the carried state are float32; the matrix
products take their operands in the compute dtype and accumulate in
float32. No kernel: the function is its own oracle
(``tests/L0/test_mamba2.py`` holds it to the recurrence step by step).
"""

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


def ssd_chunked(x, dt, A, B, C, chunk_size: int):
    """``y_t = S_t C_t`` of the recurrence above, for every ``t``.

    ``x [b, s, H, P]``, ``B``, ``C`` ``[b, s, G, N]`` in the compute dtype,
    ``dt [b, s, H]`` (after the softplus) and ``A [H]`` (negative) in
    float32. Head ``h`` reads group ``h // (H / G)``. -> ``[b, s, H, P]``
    float32. Each row starts from a zero state. A sequence that
    ``chunk_size`` does not divide is padded with steps of ``dt = 0``,
    which neither decay the state nor add to it."""
    b, s, H, P = x.shape
    G, N = B.shape[-2:]
    R = H // G
    Q = min(chunk_size, s)
    pad = -s % Q
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    c = (s + pad) // Q
    dtype, f32 = x.dtype, jnp.float32
    x = x.reshape(b, c, Q, G, R, P)
    B = B.reshape(b, c, Q, G, N)
    C = C.reshape(b, c, Q, G, N)
    dt = dt.astype(f32).reshape(b, c, Q, G, R)
    # log of the decay a step (<= 0) and its running sum inside a chunk
    a = jnp.cumsum(dt * A.astype(f32).reshape(G, R), axis=2)
    at = a.transpose(0, 1, 3, 4, 2)                       # [b, c, G, R, Q]

    # a chunk's own steps: y_i += sum_{j <= i} (C_i . B_j) decay(j -> i)
    # dt_j x_j
    seg = at[..., :, None] - at[..., None, :]             # [.., Q(i), Q(j)]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    within = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcgij", C, B, preferred_element_type=f32)
    scores = (cb[:, :, :, None] * within).astype(dtype)
    xdt = (x.astype(f32) * dt[..., None]).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", scores, xdt,
                   preferred_element_type=f32)

    # each chunk's state, as if it started from zero: what its steps
    # leave at its end
    to_end = jnp.exp(at[..., -1:] - at).transpose(0, 1, 4, 2, 3)
    xw = (x.astype(f32) * (dt * to_end)[..., None]).astype(dtype)
    states = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xw, B,
                        preferred_element_type=f32)

    # the carry: the state each chunk starts from
    def carry(S, chunk):
        state, decay = chunk
        return S * decay[..., None, None] + state, S

    _, entering = jax.lax.scan(
        carry, jnp.zeros((b, G, R, P, N), f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(jnp.exp(at[..., -1]), 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)               # [b, c, G, R, P, N]

    # the carried state's part of each output
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", C, entering.astype(dtype),
                       preferred_element_type=f32) * jnp.exp(a)[..., None]
    return y.reshape(b, c * Q, H, P)[:, :s]


def causal_depthwise_conv(x, weight, bias):
    """``out[t] = bias + sum_k weight[k] x[t - (K - 1) + k]`` a channel,
    ``x [b, s, C]``, ``weight [K, C]``, zeros before the row's start:
    ``K`` shifted copies, no convolution operation. float32."""
    K, s = weight.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for k in range(K):
        out = out + w[k] * padded[:, k:k + s]
    return out


def _dt_bias_init(dt_min, dt_max, dt_floor):
    """The published start of ``dt_bias``: the inverse softplus of a
    log-uniform step size in ``[dt_min, dt_max]``, floored."""
    def init(key, shape, dtype):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def _conv_init(kernel):
    bound = 1.0 / math.sqrt(kernel)     # torch's Conv1d default, fan-in K

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


class Mamba2Mixer(nn.Module):
    """The mixer of a ``M`` layer (``TransformerConfig.layer_pattern``):
    ``[s, b, hidden] -> [s, b, hidden]``. The heads and groups it builds
    are the configuration's counts (a chip's share of a tensor-parallel
    deployment passes the heads it holds); there is no tensor-parallel
    mode of its own and no decode path yet. Scopes ``ssm/{in_proj,conv,
    scan,gate_norm,out_proj}``; counts itself as ``ssm/layers`` at trace
    time."""

    config: Any   # models.transformer_lm.TransformerConfig

    @nn.compact
    def __call__(self, hidden_states):
        from apex_tpu.telemetry.registry import get_registry
        from apex_tpu.transformer.parallel_state import (
            get_tensor_model_parallel_world_size,
        )

        cfg = self.config
        if get_tensor_model_parallel_world_size() > 1:
            raise ValueError(
                "Mamba2Mixer has no tensor-parallel mode: pass the heads "
                "and groups this rank holds as mamba_num_heads / "
                "mamba_n_groups")
        get_registry().counter("ssm/layers").inc()
        H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
        G, N, K = cfg.mamba_n_groups, cfg.mamba_state_size, cfg.mamba_conv_kernel
        inner, bc = H * P, G * N
        hidden = hidden_states.shape[-1]
        dtype, f32 = cfg.compute_dtype, jnp.float32
        matrix = nn.initializers.normal(0.02)

        w_in = self.param("in_proj", matrix, (hidden, 2 * inner + 2 * bc + H),
                          cfg.params_dtype)
        conv_w = self.param("conv_weight", _conv_init(K), (K, inner + 2 * bc),
                            cfg.params_dtype)
        conv_b = self.param("conv_bias", _conv_init(K), (inner + 2 * bc,),
                            cfg.params_dtype)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(cfg.mamba_dt_min, cfg.mamba_dt_max,
                                     cfg.mamba_dt_floor), (H,), f32)
        a_log = self.param("A_log", _a_log_init, (H,), f32)
        d_skip = self.param("D", nn.initializers.ones, (H,), f32)
        gain = self.param("norm_weight", nn.initializers.ones, (inner,), f32)
        w_out = self.param("out_proj", matrix, (inner, hidden),
                           cfg.params_dtype)

        with jax.named_scope("ssm/in_proj"):
            # [s, b, h] -> [b, s, ...]: XLA folds the transpose into the
            # matmul
            u = hidden_states.astype(dtype).transpose(1, 0, 2)
            proj = jnp.einsum("bsh,hk->bsk", u, w_in.astype(dtype),
                              preferred_element_type=f32)
            z = proj[..., :inner]
            xbc = proj[..., inner:2 * inner + 2 * bc]
            dt = proj[..., 2 * inner + 2 * bc:]
        with jax.named_scope("ssm/conv"):
            xbc = jax.nn.silu(causal_depthwise_conv(xbc, conv_w, conv_b))
            xbc = xbc.astype(dtype)
            b, s = xbc.shape[:2]
            x = xbc[..., :inner].reshape(b, s, H, P)
            B = xbc[..., inner:inner + bc].reshape(b, s, G, N)
            C = xbc[..., inner + bc:].reshape(b, s, G, N)
        with jax.named_scope("ssm/scan"):
            dt = jax.nn.softplus(dt + dt_bias.astype(f32))
            A = -jnp.exp(a_log.astype(f32))
            y = ssd_chunked(x, dt, A, B, C, cfg.mamba_chunk_size)
            y = y + d_skip.astype(f32)[:, None] * x.astype(f32)
        with jax.named_scope("ssm/gate_norm"):
            y = y.reshape(b, s, G, inner // G) * jax.nn.silu(z).reshape(
                b, s, G, inner // G)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                                  + cfg.layernorm_epsilon)
            y = (y.reshape(b, s, inner) * gain.astype(f32)).astype(dtype)
        with jax.named_scope("ssm/out_proj"):
            out = jnp.einsum("bsk,kh->bsh", y, w_out.astype(dtype),
                             preferred_element_type=f32)
        return out.astype(dtype).transpose(1, 0, 2)
