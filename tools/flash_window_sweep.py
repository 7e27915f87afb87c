"""On-chip numbers for the round-3 flash kernels (VERDICT r3 item 4).

Two tables, one JSON line per config:

A) Windowed flash scaling — fwd+bwd wall time across seq x window; the
   band block-skip should make time scale ~ seq*window instead of seq^2
   (each row reports the time ratio vs the full-causal run at the same
   seq, next to the ideal window/seq work ratio).
B) ALiBi-flash vs the XLA-materialized reference path on a BLOOM-shaped
   head config (the reference fmha's reason to exist is speed,
   /root/reference README fmha section).

Run:  python tools/flash_window_sweep.py [a|b|all]
CPU note: the Pallas kernels need a real TPU; on CPU this exits with a
clear message instead of silently timing the fallback.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.tree_util.tree_map(
        lambda a: a.block_until_ready(), out)
    # a host fetch of a scalar reduction over every output waits for
    # the whole call
    float(sum(jnp.sum(x.astype(jnp.float32))
              for x in jax.tree_util.tree_leaves(out)))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(sum(jnp.sum(x.astype(jnp.float32))
              for x in jax.tree_util.tree_leaves(out)))
    return (time.perf_counter() - t0) / iters


def _qkv(seq, heads=16, d=64, batch=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch, heads, seq, d)
    return tuple(jax.random.normal(k, shape, jnp.bfloat16) for k in ks)


TINY = os.environ.get("APEX_TPU_SWEEP_TINY") == "1"


def table_a():
    from apex_tpu.contrib.fmha import flash_attention

    for seq in ((256,) if TINY else (8192, 16384, 32768)):
        q, k, v = _qkv(seq)
        base_dt = None
        for window in ((None, 128) if TINY else (None, 4096, 1024)):
            @jax.jit
            def fwd_bwd(q, k, v, w=window):
                def f(q, k, v):
                    return jnp.sum(flash_attention(
                        q, k, v, causal=True, window=w
                    ).astype(jnp.float32))
                l, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
                return l, grads

            dt = _time(fwd_bwd, q, k, v)
            if window is None:
                base_dt = dt
            # ideal work ratio for a banded causal kernel
            ideal = 1.0 if window is None else min(
                1.0, (window * seq - window * (window - 1) / 2)
                / (seq * (seq + 1) / 2))
            print(json.dumps({
                "table": "windowed_flash", "seq": seq,
                "window": window or "full",
                "ms_fwd_bwd": round(dt * 1e3, 2),
                "vs_full_causal": round(dt / base_dt, 3),
                "ideal_work_ratio": round(ideal, 3),
                "platform": jax.devices()[0].platform}), flush=True)


def table_b():
    from apex_tpu.contrib.fmha import (_attention_reference,
                                       flash_attention)
    from apex_tpu.models.transformer_lm import alibi_slopes

    # BLOOM-7b-shaped heads: 32 heads x 128, seq 2048, batch 4
    heads, d, seq, batch = ((4, 64, 256, 1) if TINY
                        else (32, 128, 2048, 4))
    q, k, v = _qkv(seq, heads=heads, d=d, batch=batch)
    slopes = alibi_slopes(heads)
    scale = 1.0 / np.sqrt(d)

    for name, fn in (
        ("alibi_flash", lambda q, k, v: flash_attention(
            q, k, v, causal=True, alibi_slopes=slopes)),
        ("alibi_xla_reference", lambda q, k, v: _attention_reference(
            q, k, v, scale, True, None, slopes)),
    ):
        @jax.jit
        def fwd_bwd(q, k, v, f=fn):
            def loss(q, k, v):
                return jnp.sum(f(q, k, v).astype(jnp.float32))
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

        dt = _time(fwd_bwd, q, k, v)
        print(json.dumps({
            "table": "alibi", "path": name,
            "config": f"b{batch} h{heads} d{d} s{seq}",
            "ms_fwd_bwd": round(dt * 1e3, 2),
            "platform": jax.devices()[0].platform}), flush=True)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if not TINY and jax.devices()[0].platform != "tpu":
        sys.exit("flash kernels need a real TPU; refusing to time the "
                 f"CPU path (platform: {jax.devices()[0].platform})")
    if which in ("a", "all"):
        table_a()
    if which in ("b", "all"):
        table_b()


if __name__ == "__main__":
    main()
