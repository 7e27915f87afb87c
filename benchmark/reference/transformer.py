"""The transformer both families share, written out plainly.

Tensors are batch-major ``[b, s, h]`` float32. ``params`` is the canonical
flat dict of ``benchmark/weights.py``; per-layer tensors carry a leading
``[L]`` axis and the stack is one ``lax.scan`` (so a 24-layer reference
compiles as fast as a one-layer one), each layer rematerialised in the
backward pass so that a full-width batch block fits beside the weights.

Blocks are pre-LN: ``x + attn(ln1(x))`` then ``x + mlp(ln2(x))``, a final
LayerNorm after the stack. That is GPT-2 as published, and BERT as
Megatron-LM rearranged it (arXiv:1909.08053, figure 7), which is the BERT
the program implements.

The fused QKV matrix is laid out per head, ``[h, heads, (q|k|v), d]``
flattened (Megatron's layout).

``quant`` is applied to both operands of every matrix product. The
reference passes the identity; the *control* (``lowp.py``) passes a
rounding to the precision below the configuration's, to show that the
comparison which decides ``correct`` would catch it.
"""

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def identity(x):
    return x


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def matmul(x, w, quant):
    return jnp.matmul(quant(x), quant(w), precision=HIGHEST)


def attention(x, lp, heads, key_bias, causal, quant):
    """Multi-head self-attention over ``x`` ``[b, s, h]``. ``key_bias``
    ``[b, s]`` is 0 on keys that may be seen and -1e9 on padding."""
    b, s, h = x.shape
    d = h // heads
    qkv = matmul(x, lp["qkv_w"], quant) + lp["qkv_b"]
    qkv = qkv.reshape(b, s, heads, 3, d)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    scores = jnp.einsum("bqnd,bknd->bnqk", quant(q), quant(k),
                        precision=HIGHEST) / math.sqrt(d)
    if causal:
        future = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]
        scores = jnp.where(future[None, None], -1e9, scores)
    if key_bias is not None:
        scores = scores + key_bias[:, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnqk,bknd->bqnd", quant(probs), quant(v),
                     precision=HIGHEST).reshape(b, s, h)
    return matmul(ctx, lp["proj_w"], quant) + lp["proj_b"]


def block(x, lp, arch, key_bias, causal, quant):
    eps = arch["eps"]
    act = gelu_tanh if arch["act"] == "gelu_tanh" else gelu_erf
    x = x + attention(layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps), lp,
                      arch["heads"], key_bias, causal, quant)
    y = layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)
    y = act(matmul(y, lp["fc_w"], quant) + lp["fc_b"])
    return x + matmul(y, lp["out_w"], quant) + lp["out_b"]


def stack(x, params, arch, key_bias, causal, quant):
    """All layers, then the final LayerNorm."""
    layers = {k[len("layers."):]: v for k, v in params.items()
              if k.startswith("layers.")}

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(x, lp):
        return block(x, lp, arch, key_bias, causal, quant), None

    x, _ = jax.lax.scan(body, x, layers)
    return layer_norm(x, params["lnf_g"], params["lnf_b"], arch["eps"])


def token_nll(logits, labels):
    """Per-position negative log likelihood of ``labels``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
