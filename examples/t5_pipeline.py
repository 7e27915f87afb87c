"""Encoder-decoder (T5-style) pipeline-parallel training walkthrough.

Parity target: the reference runs ModelType.encoder_and_decoder models
through its pipeline schedules with dual p2p tensor shapes
(apex/transformer/pipeline_parallel/schedules/fwd_bwd_pipelining_without_interleaving.py:29-86)
and places the encoder/decoder boundary at
pipeline_model_parallel_split_rank (apex/transformer/parallel_state.py:243-331).
apex_tpu's equivalent is `forward_backward_pipelining_with_split`: one
jitted SPMD tick machine whose cross-stage payload is an
{encoder, decoder} pytree pair, with the encoder stream forwarded to
decoder ranks as cross-attention memory.

Shown here: the split mesh, per-stage params, the schedule call, and a
FusedAdam update applied rank-locally to each stage's params.

Run (4 virtual devices on CPU, or a real slice):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python examples/t5_pipeline.py --steps 20 --pp 4 --split 2
"""

import argparse
import functools
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
    p.add_argument("--pp", type=int, default=4,
                   help="pipeline stages (encoder + decoder ranks)")
    p.add_argument("--split", type=int, default=2,
                   help="first decoder rank; ranks < split run the encoder")
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--batch", type=int, default=2,
                   help="microbatch size")
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--model", choices=["standalone", "real"],
                   default="standalone",
                   help="'real' runs the full T5Model family (relative-"
                        "position buckets, RMS norms, tied head) as the "
                        "pipeline stages; needs --pp 2 --split 1")
    args = p.parse_args()

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.testing import shard_map
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.pipeline_parallel import (
        forward_backward_pipelining_with_split,
        make_encoder_decoder_step,
    )
    from apex_tpu.transformer.testing.standalone_t5 import (
        decoder_block,
        encoder_block,
        init_stage_params,
        t5_loss,
        t5_test_config,
    )
    from jax.sharding import Mesh, PartitionSpec as P

    if len(jax.devices()) < args.pp:
        raise SystemExit(
            f"need {args.pp} devices; run with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={args.pp}")
    if not (0 < args.split < args.pp):
        raise SystemExit("--split must satisfy 0 < split < pp")

    cfg = t5_test_config(hidden=args.hidden, ffn=2 * args.hidden)
    M, B = args.microbatches, args.batch
    rng = np.random.RandomState(0)
    mbs = {
        "enc_tokens": jnp.asarray(
            rng.randint(0, cfg["vocab"], (M, B, cfg["enc_seq"]))),
        "dec_tokens": jnp.asarray(
            rng.randint(0, cfg["vocab"], (M, B, cfg["dec_seq"]))),
        "dec_targets": jnp.asarray(
            rng.randint(0, cfg["vocab"], (M, B, cfg["dec_seq"]))),
    }

    mesh = Mesh(np.asarray(jax.devices()[:args.pp]), ("pp",))
    parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=args.pp,
        pipeline_model_parallel_split_rank_=args.split,
        devices=jax.devices()[:args.pp])

    if args.model == "real":
        # the full T5 family (models/t5.py) as the pipeline stages: the
        # encoder rank runs T5Model.encode, the decoder rank runs
        # decode_hidden with the forwarded memory, the loss applies the
        # tied head. One whole side per rank -> pp=2/split=1.
        if (args.pp, args.split) != (2, 1):
            raise SystemExit("--model real needs --pp 2 --split 1 "
                             "(one full encoder rank + one decoder rank)")
        from apex_tpu.models.t5 import T5Config, T5Model, t5_loss_fn

        tcfg = T5Config(
            vocab_size=cfg["vocab"], d_model=args.hidden, d_kv=16,
            d_ff=2 * args.hidden, num_layers=2, num_decoder_layers=2,
            num_heads=cfg["heads"], compute_dtype=jnp.float32)
        model = T5Model(tcfg)

        def enc_fn(p, h, mb, is_first):
            del h, is_first
            return model.apply({"params": p}, mb["enc_tokens"],
                               method=T5Model.encode)

        def dec_fn(p, h, memory, mb, is_split):
            del h, is_split
            return model.apply({"params": p}, mb["dec_tokens"], memory,
                               method=T5Model.decode_hidden)

        step = make_encoder_decoder_step(enc_fn, dec_fn)

        def loss_func(params, payload, mb):
            logits = model.apply({"params": params}, payload["decoder"],
                                 method=T5Model.head)
            return t5_loss_fn(logits, mb["dec_targets"])

        init_rank = lambda r: model.init(
            jax.random.PRNGKey(r), mbs["enc_tokens"][0],
            mbs["dec_tokens"][0])["params"]
    else:
        step = make_encoder_decoder_step(
            functools.partial(encoder_block, cfg=cfg),
            functools.partial(decoder_block, cfg=cfg))

        def loss_func(params, payload, mb):
            return t5_loss(params, payload["decoder"], mb)

        init_rank = lambda r: init_stage_params(rng, cfg)

    opt = FusedAdam(lr=args.lr)
    # one stage's params per pp rank, stacked for shard_map entry
    stage_params = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[init_rank(r) for r in range(args.pp)])
    opt_state = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[opt.init(jax.tree_util.tree_map(lambda a: a[r], stage_params))
          for r in range(args.pp)])

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("pp"), P("pp"), P()),
        out_specs=(P("pp"), P("pp"), P("pp")))
    def train_step(p_stage, o_stage, mbs_):
        params = jax.tree_util.tree_map(lambda a: a[0], p_stage)
        o = jax.tree_util.tree_map(lambda a: a[0], o_stage)
        losses, grads = forward_backward_pipelining_with_split(
            step, loss_func, params, mbs_, num_microbatches=M,
            encoder_tensor_shape=(cfg["enc_seq"], B, cfg["hidden"]),
            decoder_tensor_shape=(cfg["dec_seq"], B, cfg["hidden"]),
            dtype=jnp.float32, pp_size=args.pp)
        params, o = opt.step(grads, o, params)
        lift = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
        return lift(params), lift(o), losses[None]

    for i in range(args.steps):
        stage_params, opt_state, losses = train_step(
            stage_params, opt_state, mbs)
        # per-microbatch losses live on the last stage's lane
        loss = float(np.asarray(losses)[args.pp - 1].mean())
        print(f"step {i:3d}  loss {loss:.4f}")


if __name__ == "__main__":
    main()
