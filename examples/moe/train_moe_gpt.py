"""Expert-parallel MoE GPT training walkthrough.

No reference counterpart (the reference has no MoE; this is an apex_tpu
capability beyond it — COVERAGE.md §2.3). Shows the full recipe: ep mesh
axis, SwitchMLP layers via TransformerConfig, aux-loss collection, the
split dense/expert grad-sync rule, and checkpointing.

Run (8 virtual devices on CPU, or a real slice):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/moe/train_moe_gpt.py --steps 20 --ep 2 --tp 2
"""

import argparse
import os
import sys

_d = os.path.dirname(os.path.abspath(__file__))
while _d != os.path.dirname(_d) and not os.path.isdir(os.path.join(_d, "apex_tpu")):
    _d = os.path.dirname(_d)
sys.path.insert(0, _d)  # repo root (walk up: examples may be nested)

import jax
import jax.numpy as jnp
import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--ep", type=int, default=2,
                   help="expert-parallel ways (experts sharded over 'ep')")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel ways inside each expert")
    p.add_argument("--experts", type=int, default=4)
    p.add_argument("--top-k", type=int, default=2)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--batch-per-replica", type=int, default=2)
    p.add_argument("--lr", type=float, default=5e-3)
    p.add_argument("--capacity-factor", type=float, default=1.5)
    p.add_argument("--save-dir", default=None,
                   help="optional checkpoint directory")
    p.add_argument("--cpu", action="store_true",
                   help="force the virtual CPU platform")
    args = p.parse_args()

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < args.ep * args.tp:
        raise SystemExit(
            f"need {args.ep * args.tp} devices (ep x tp), have "
            f"{len(jax.devices())}; pass --cpu with XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N for the virtual "
            f"CPU mesh")

    from apex_tpu.models.transformer_lm import TransformerConfig
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing.gpt_moe import build_gpt_moe_harness

    world = len(jax.devices())
    dp = world // (args.ep * args.tp)
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=args.tp,
        expert_model_parallel_size_=args.ep,
        devices=jax.devices()[:dp * args.ep * args.tp])
    print(f"mesh: {dict(mesh.shape)}  "
          f"dense-grad axes: {parallel_state.get_data_parallel_axes()}")

    cfg = TransformerConfig(
        hidden_size=args.hidden, num_layers=args.layers,
        num_attention_heads=4, vocab_size=256,
        max_position_embeddings=args.seq, compute_dtype=jnp.bfloat16,
        use_flash_attention=False, num_moe_experts=args.experts,
        moe_top_k=args.top_k, moe_capacity_factor=args.capacity_factor)

    B = args.batch_per_replica * dp * args.ep
    rng = np.random.RandomState(0)
    data = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, args.seq + 1)))
    tokens, labels = data[:, :-1], data[:, 1:]

    opt = FusedAdam(lr=args.lr)
    init_state, step = build_gpt_moe_harness(cfg, mesh, opt)
    params, opt_state = init_state(jax.random.PRNGKey(0), tokens)

    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")

    if args.save_dir:
        from apex_tpu import checkpoint

        path = checkpoint.save_training_state(
            args.save_dir, args.steps, params, opt_state)
        print("saved:", path)


if __name__ == "__main__":
    main()
