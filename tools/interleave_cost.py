"""Measure the interleaved schedule's masked-compute residual.

VERDICT r2 item 6: the SPMD tick machine executes (V-1)*P extra *masked*
forward unit-slots per rank vs the reference's asynchronous per-rank
schedule (schedules.py module doc). This tool puts a wall-clock number on
it: fixed total model depth L and microbatch count M on a P-rank pp mesh,
sweeping the virtual-chunk count V — V=1 (non-interleaved 1F1B) vs V=2,4.
Per-V it reports measured ms/step (jit-compiled, warmup excluded) next to
the tick-plan prediction, so the measured bubble can be compared with the
documented bound.

Tick-plan prediction: a rank executes fwd_ticks = M*V + V*P - 1 forward
unit-slots and bwd_ticks = M*V + P - 1 backward unit-slots (masked or
not — a masked unit computes on zeros and costs the same as a live one).
One unit is 1/V of the rank's layers, so with t_f the V=1 per-stage
forward time, predicted step time scales as
    T(V) ~ (M*V + V*P - 1) * (t_f/V) + (M*V + P - 1) * (t_b/V)
vs T(1) = (M + P - 1) * (t_f + t_b); with t_b ~ 2*t_f the predicted
overhead ratio is printed alongside the measurement.

Run:  python tools/interleave_cost.py [P] [M] [L] [steps]
      (CPU tick-proxy: XLA_FLAGS=--xla_force_host_platform_device_count=8
       JAX_PLATFORMS=cpu python tools/interleave_cost.py)
Prints one JSON line per V.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from apex_tpu.testing import shard_map  # noqa: E402
from apex_tpu.transformer import parallel_state  # noqa: E402
from apex_tpu.transformer.pipeline_parallel import (  # noqa: E402
    forward_backward_pipelining_with_interleaving,
    forward_backward_pipelining_without_interleaving,
    pipeline_schedule_plan,
)

HID = 512
MB = 8


def predicted_ratio(P_, M, V, tb_over_tf=2.0):
    plan = pipeline_schedule_plan(P_, M, V)
    t1 = pipeline_schedule_plan(P_, M, 1)
    cost_v = (plan["fwd_ticks"] + tb_over_tf * plan["bwd_ticks"]) / V
    cost_1 = t1["fwd_ticks"] + tb_over_tf * t1["bwd_ticks"]
    return cost_v / cost_1


def build_step(P_, M, V, L):
    layers_per_chunk = L // (P_ * V)
    mesh = Mesh(np.asarray(jax.devices()[:P_]), ("pp",))
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=P_, devices=jax.devices()[:P_])

    def stage_fn(params, h, mb, is_first):
        h = jnp.where(is_first, mb["x"], h)
        for i in range(layers_per_chunk):
            h = jax.nn.gelu(h @ params["w"][i] + params["b"][i])
        return h

    def loss_fn(params, y, mb):
        return jnp.mean((y - mb["t"]) ** 2)

    rng = np.random.RandomState(0)
    # per-rank params: [V, layers_per_chunk, HID, HID] (V=1: leading dim 1)
    ws = rng.randn(P_, V, layers_per_chunk, HID, HID).astype(
        np.float32) * 0.1
    bs = rng.randn(P_, V, layers_per_chunk, HID).astype(np.float32) * 0.1
    xs = rng.randn(M, MB, HID).astype(np.float32)
    ts = rng.randn(M, MB, HID).astype(np.float32)

    fwd_bwd = (forward_backward_pipelining_without_interleaving if V == 1
               else forward_backward_pipelining_with_interleaving)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P("pp"), P(), P()),
        out_specs=P("pp"))
    def run(p_stage, mb_x, mb_t):
        p = jax.tree_util.tree_map(lambda a: a[0], p_stage)
        if V == 1:
            p = jax.tree_util.tree_map(lambda a: a[0], p)  # drop V dim
        kwargs = {} if V == 1 else {"num_model_chunks": V}
        losses, grads = fwd_bwd(
            stage_fn, loss_fn, p, {"x": mb_x, "t": mb_t},
            num_microbatches=M, tensor_shape=(MB, HID),
            dtype=jnp.float32, pp_size=P_, **kwargs)
        return losses[None]

    jitted = jax.jit(run)
    args = ({"w": jnp.asarray(ws), "b": jnp.asarray(bs)},
            jnp.asarray(xs), jnp.asarray(ts))
    return jitted, args


def measure(P_, M, V, L, steps):
    step, args = build_step(P_, M, V, L)
    out = step(*args)
    jax.block_until_ready(out)  # compile + first run
    out = step(*args)
    float(np.asarray(out).sum())  # host fetch barrier
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step(*args)
    float(np.asarray(out).sum())
    dt = (time.perf_counter() - t0) / steps
    return dt


def main():
    P_ = int(sys.argv[1]) if len(sys.argv) > 1 else 2
    M = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    L = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    steps = int(sys.argv[4]) if len(sys.argv) > 4 else 10
    base = None
    for V in (1, 2, 4):
        if L % (P_ * V):
            continue
        dt = measure(P_, M, V, L, steps)
        base = base or dt
        print(json.dumps({
            "V": V, "P": P_, "M": M, "L": L,
            "ms_per_step": round(dt * 1e3, 2),
            "measured_ratio_vs_V1": round(dt / base, 3),
            "predicted_ratio_vs_V1": round(predicted_ratio(P_, M, V), 3),
            "platform": jax.devices()[0].platform,
        }), flush=True)


if __name__ == "__main__":
    main()
