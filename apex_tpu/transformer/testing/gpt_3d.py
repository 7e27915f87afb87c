"""Shared 3D-parallel (pp x dp x tp) GPT training harness.

One full training step — 1F1B pipeline schedule, DP grad pmean, sequence-
parallel grad allreduce, model-parallel GradScaler, fused optimizer —
shard_mapped over the global mesh. Used by both the driver entry
(``__graft_entry__.dryrun_multichip``) and the minimal end-to-end test
(tests/L0/test_gpt_minimal.py), mirroring how the reference ships its
integration-test harness inside the package
(apex/transformer/testing/standalone_gpt.py + commons.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt_stage import GPTStage
from apex_tpu.models.transformer_lm import is_sequence_parallel_param
from apex_tpu.transformer.pipeline_parallel.schedules import (
    forward_backward_pipelining_with_interleaving,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    allreduce_sequence_parallel_grads,
)


def boundary_tensor_shape(cfg, mesh, seq, microbatch):
    """Per-device activation shape crossing pipeline-stage boundaries:
    [s(/tp under SP), mb, h]. Under sequence parallelism the ppermute
    payload is the *seq shard*, i.e. 1/tp of the full activation — the
    layout-level realization of the reference's p2p scatter-gather
    compression (p2p_communication.py:117-400 splits the tensor over the
    TP group before isend and all-gathers after irecv; sharding makes
    that the resting state, no extra collectives)."""
    seq_shard = seq // mesh.shape.get("tp", 1) if cfg.sequence_parallel \
        else seq
    return (seq_shard, microbatch, cfg.hidden_size)


def build_gpt_3d_harness(cfg, mesh, opt, scaler, *, pp, seq, microbatch,
                         num_microbatches, vpp=None):
    """Return ``(init_state, step)`` for a pipelined GPT training loop.

    ``init_state(key, tokens, labels)`` builds per-stage stacked params,
    optimizer state, and scaler state. ``step(stacked_params, stacked_opt,
    scaler_state, tokens, labels)`` is jitted and returns the new state
    plus the per-(pp, dp)-cell loss grid; only the last pipeline stage's
    rows are nonzero.

    ``tokens``/``labels`` are [global_batch, seq] with
    global_batch = microbatch * num_microbatches * dp.

    ``vpp``: virtual-pipeline chunks per rank (interleaved 1F1B). Rank r
    holds chunks c with global stage c*pp + r; per-rank param leaves get
    a leading [vpp] axis and the step runs
    ``forward_backward_pipelining_with_interleaving`` (reference
    build_model virtual-chunk support, common.py:30-151).
    """
    moe = cfg.num_moe_experts is not None
    if "ep" in mesh.shape and mesh.shape["ep"] > 1:
        # This harness pmeans every grad over 'dp' alone; with an ep>1
        # axis, dense params replicated across ep need the dense-over-
        # (dp, ep) / expert-over-dp split sync (moe/layer.py:14-17,
        # testing/gpt_moe.py) — replicas would silently diverge here.
        raise ValueError(
            "the pipelined harness does not support expert parallelism "
            "(ep > 1); use transformer.testing.gpt_moe (dp x ep x tp)")
    # each stage numbers its layers 0..layers_per_stage-1
    cfg.check_uniform("the pipelined harness",
                      "; transformer.testing.gpt_moe (dp x ep x tp) "
                      "trains one unpipelined")
    V = vpp or 1
    if cfg.num_layers % (pp * V):
        raise ValueError(
            f"num_layers ({cfg.num_layers}) must be a multiple of "
            f"pp*vpp ({pp * V})")
    stage = GPTStage(cfg, cfg.num_layers // (pp * V))
    MB, M = microbatch, num_microbatches
    tensor_shape = boundary_tensor_shape(cfg, mesh, seq, microbatch)

    if moe:
        from apex_tpu.transformer.moe import moe_loss_from_variables

        def stage_fn(params, h, mb, is_first):
            # router aux/z losses are per-stage; the schedule's aux_loss
            # contract backprops them from each stage's own backward unit
            y, mut = stage.apply({"params": params}, mb["tokens"], h,
                                 is_first, mutable=["moe_losses"])
            return y, moe_loss_from_variables(
                mut, cfg.moe_aux_loss_coeff, cfg.moe_z_loss_coeff)
    else:
        def stage_fn(params, h, mb, is_first):
            return stage.apply({"params": params}, mb["tokens"], h,
                               is_first)

    def loss_fn(params, y, mb):
        return stage.apply({"params": params}, y, mb["labels"],
                           method=GPTStage.loss)

    def train_step(params, opt_state, scaler_state, tokens, labels):
        mbs = {"tokens": tokens.reshape(M, MB, seq),
               "labels": labels.reshape(M, MB, seq)}
        # scale the loss up by the live scale; unscale_grads divides it
        # back out (and pmaxes found_inf over tp x pp)
        # V=1 falls through to the non-interleaved schedule inside
        losses, grads = forward_backward_pipelining_with_interleaving(
            stage_fn, loss_fn, params, mbs, num_microbatches=M,
            tensor_shape=tensor_shape, dtype=cfg.compute_dtype,
            grad_scale=scaler_state.loss_scale, pp_size=pp,
            num_model_chunks=V, aux_loss=moe)
        # DP gradient sync (DDP semantics: average over the dp axis).
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, "dp"), grads)
        # SP: grads of tp-replicated params (layernorms, position
        # embeddings, row-parallel biases) are partial over seq shards —
        # allreduce them over tp (reference layer_norm.py:26-99 tagging).
        if cfg.sequence_parallel:
            grads = allreduce_sequence_parallel_grads(
                grads, is_sequence_parallel_param)
        grads, found_inf = scaler.unscale_grads(grads, scaler_state)
        new_params, new_opt_state = opt.step(
            grads, opt_state, params, found_inf=found_inf)
        new_scaler_state = scaler.update(scaler_state, found_inf)
        return new_params, new_opt_state, new_scaler_state, jnp.sum(losses)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("pp"), P("pp"), P(), P("dp"), P("dp")),
        out_specs=(P("pp"), P("pp"), P(), P(("pp", "dp"))),
        check_vma=False)
    def sharded_step(stacked_params, stacked_opt, scaler_state, tok, lab):
        params = jax.tree_util.tree_map(lambda a: a[0], stacked_params)
        opt_state = jax.tree_util.tree_map(lambda a: a[0], stacked_opt)
        p, o, s, l = train_step(params, opt_state, scaler_state,
                                tok.reshape(-1, seq), lab.reshape(-1, seq))
        stack = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)  # noqa: E731
        return stack(p), stack(o), s, l.reshape(1, 1)

    # Per-stage params: init under shard_map so TP layers see local shards.
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(), P(), P()), out_specs=P("pp"),
                       check_vma=False)
    def init_params(key, tok, lab):
        rank = jax.lax.axis_index("pp")
        h0 = jnp.zeros(tensor_shape, cfg.compute_dtype)

        def init_chunk(c):
            # chunk c on rank r is global stage c*pp + r
            k = jax.random.fold_in(key, c * pp + rank)
            return stage.init(k, tok[:MB], h0, jnp.asarray(False),
                              lab[:MB], method=GPTStage.full)["params"]

        if V > 1:
            chunks = [init_chunk(c) for c in range(V)]
            params = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *chunks)
        else:
            params = init_chunk(0)
        return jax.tree_util.tree_map(lambda a: a[None], params)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("pp"),
                       out_specs=P("pp"), check_vma=False)
    def init_opt(stacked_params):
        params = jax.tree_util.tree_map(lambda a: a[0], stacked_params)
        return jax.tree_util.tree_map(lambda a: a[None], opt.init(params))

    def init_state(key, tokens, labels, stacked_params=None):
        """``stacked_params``: pre-loaded per-rank params (e.g. from
        ``models.reshard.load_checkpoint_for_3d``) instead of a fresh
        init; optimizer/scaler state is built for them either way."""
        if stacked_params is None:
            stacked_params = init_params(key, tokens[:MB], labels[:MB])
        return stacked_params, init_opt(stacked_params), scaler.init_state()

    return init_state, jax.jit(sharded_step)
