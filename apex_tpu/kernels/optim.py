"""Fused multi-tensor Adam / LAMB update kernels over bucket-domain state.

Parity: reference csrc/multi_tensor_adam.cu / multi_tensor_lamb.cu —
the ``multi_tensor_applier`` kernels that run one fused elementwise
pass over a chunked flat view of many tensors instead of launching one
op chain per tensor. On this container the ZeRO optimizers
(:mod:`apex_tpu.contrib.optimizers`) already hold their state as flat
fp32 shards — and since PR 10 the overlapped path holds it as
block-aligned per-bucket buffers — so the multi-tensor marshalling is
already done: the fused kernel is ONE ``pallas_call`` per bucket/shard
viewing the flat buffer as ``[nblocks, 256]`` (the same 256-lane block
domain the int8 compression uses), reading g/p/m/v and writing the
three outputs in a single VMEM pass instead of the ~10-op XLA chain.

Scalars that depend on the traced step (``lr``, the bias corrections)
ride in SMEM; the static hyperparameters are baked into the kernel.
The jnp oracles below are the exact expressions the optimizers ran
before this module existed (same operation order, same promotions), so
the gate-off path is bit-identical to the pre-kernel code and the
interpret-mode kernels are bit-identical to the oracle — the parity
tests assert equality.

LAMB's per-tensor trust ratio needs cross-bucket segment norms, so it
stays OUTSIDE the kernel (the existing segment-sum + scalar-join in
``DistributedFusedLAMB``); the kernel fuses the m/v/update production
(:func:`fused_lamb_mvu`) and the ratio apply remains one jnp multiply.
"""

import functools

import jax
import jax.numpy as jnp

from apex_tpu.kernels.registry import kernel_gate

GATE_ADAM = kernel_gate("adam")
GATE_LAMB = kernel_gate("lamb")

BLOCK = 256      # lanes per row — the compression block domain
_ROWS = 8        # fp32 sublane tile


def _to_blocks(flat):
    """[n] -> [R, 256] fp32 with R a multiple of the sublane tile; the
    zero pad tail produces zero updates (m=v=0 -> update 0)."""
    n = flat.shape[0]
    rows = -(-n // BLOCK)
    rows = -(-rows // _ROWS) * _ROWS
    out = jnp.pad(flat, (0, rows * BLOCK - n))
    return out.reshape(rows, BLOCK), n


def _blocked_call(name, kernel, scalars, arrays, n_out, out_dtype,
                  interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blocked = []
    n = None
    for a in arrays:
        b, n = _to_blocks(a)
        blocked.append(b)
    rows = blocked[0].shape[0]
    s = jnp.stack([jnp.asarray(v, jnp.float32) for v in scalars]) \
        .reshape(1, -1)
    spec = pl.BlockSpec((_ROWS, BLOCK), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    outs = pl.pallas_call(
        kernel,
        grid=(rows // _ROWS,),
        in_specs=[pl.BlockSpec((1, s.shape[1]), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)]
        + [spec] * len(blocked),
        out_specs=[spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows, BLOCK), out_dtype)] * n_out,
        interpret=interpret,
        name=name,
    )(s, *blocked)
    return [o.reshape(-1)[:n] for o in outs]


# ---------------------------------------------------------------------------
# Adam
# ---------------------------------------------------------------------------

def _adam_kernel(s_ref, g_ref, p_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *, b1, b2, eps, wd, adam_w):
    lr = s_ref[0, 0]
    bc1 = s_ref[0, 1]
    bc2 = s_ref[0, 2]
    g = g_ref[...]
    p = p_ref[...]
    if not adam_w:
        g = g + wd * p
    m = b1 * m_ref[...] + (1 - b1) * g
    v = b2 * v_ref[...] + (1 - b2) * jnp.square(g)
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w and wd != 0:
        update = update + wd * p
    po_ref[...] = p - lr * update
    mo_ref[...] = m
    vo_ref[...] = v


def fused_adam_update(g, p, m, v, *, lr, bc1, bc2, b1, b2, eps,
                      weight_decay, adam_w):
    """One fused Adam update over a flat fp32 shard/bucket: returns
    ``(p_new, m_new, v_new)``. The oracle is byte-for-byte the update
    the ZeRO optimizers ran before the kernel existed."""
    if GATE_ADAM.path() != "oracle":
        kernel = functools.partial(
            _adam_kernel, b1=b1, b2=b2, eps=eps, wd=weight_decay,
            adam_w=adam_w)
        p_new, m_new, v_new = _blocked_call(
            "fused_adam", kernel, (lr, bc1, bc2), (g, p, m, v), 3,
            jnp.float32, GATE_ADAM.interpret)
        return p_new, m_new, v_new
    if not adam_w:
        g = g + weight_decay * p
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * jnp.square(g)
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if adam_w and weight_decay != 0:
        update = update + weight_decay * p
    return p - lr * update, m_new, v_new


# ---------------------------------------------------------------------------
# LAMB (m/v/update production; trust ratio stays on the scalar join)
# ---------------------------------------------------------------------------

def _lamb_kernel(s_ref, g_ref, p_ref, m_ref, v_ref,
                 mo_ref, vo_ref, uo_ref, *, b1, b2, beta3, eps, wd,
                 adam_w):
    bc1 = s_ref[0, 0]
    bc2 = s_ref[0, 1]
    g = g_ref[...]
    p = p_ref[...]
    if not adam_w and wd != 0:
        g = g + wd * p
    m = b1 * m_ref[...] + beta3 * g
    v = b2 * v_ref[...] + (1 - b2) * jnp.square(g)
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w and wd != 0:
        update = update + wd * p
    mo_ref[...] = m
    vo_ref[...] = v
    uo_ref[...] = update


def fused_lamb_mvu(g, p, m, v, *, bc1, bc2, b1, b2, beta3, eps,
                   weight_decay, adam_w):
    """The fused LAMB moment + raw-update pass over a flat shard/bucket:
    returns ``(m_new, v_new, update)``. The per-tensor trust ratio and
    the ``p - lr * ratio * update`` apply stay with the caller — the
    ratio couples buckets through the existing segment-norm scalar
    join, which a bucket-local kernel must not absorb."""
    if GATE_LAMB.path() != "oracle":
        kernel = functools.partial(
            _lamb_kernel, b1=b1, b2=b2, beta3=beta3, eps=eps,
            wd=weight_decay, adam_w=adam_w)
        m_new, v_new, update = _blocked_call(
            "fused_lamb", kernel, (bc1, bc2), (g, p, m, v), 3,
            jnp.float32, GATE_LAMB.interpret)
        return m_new, v_new, update
    if not adam_w and weight_decay != 0:
        g = g + weight_decay * p
    m_new = b1 * m + beta3 * g
    v_new = b2 * v + (1 - b2) * jnp.square(g)
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if adam_w and weight_decay != 0:
        update = update + weight_decay * p
    return m_new, v_new, update
