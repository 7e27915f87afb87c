"""1F1B pipeline parallelism on a 3-D ``(data, model, pipe)`` mesh.

Why: pipeline parallelism is the last unreproduced parallelism axis
(ROADMAP item 1) — the production-pod topology is pipeline x tensor x
data, with DP bucket psums hidden inside pipeline bubbles (T3's
fine-grained compute/collective overlap, arXiv 2401.16677) and the
cross-replica weight-update sharding (arXiv 2004.13336) extended to a
three-axis shard table.

This module is two layers:

1. **The reference schedule machinery** — relocated verbatim from
   ``apex_tpu.transformer.pipeline_parallel.schedules`` /
   ``p2p_communication`` (those modules are now compat shims
   re-exporting this one): ``pipeline_schedule_plan``, the jitted
   ``lax.fori_loop`` tick machine ``_pipelined_fwd_bwd`` behind
   ``get_forward_backward_func``, and the ppermute p2p helpers. Their
   semantics and the reference parity notes are unchanged.

2. **The 3-D production substrate** — :func:`mesh_3d` /
   :func:`build_pipeline_step`: a stage-partitioned GPT-2 (mesh2d's
   column/row-parallel blocks per stage) driven by a **host-unrolled**
   1F1B schedule. Unrolling the same tick math as the fori_loop machine
   (forward unit ``k = t - rank``, backward unit
   ``kb = t - (P-1) - (P-1-rank)``, ring stash of ``min(M, 2P-1)``
   stage inputs, ``jax.vjp`` rematerialization per backward unit) buys
   what a traced loop cannot: a ``pp_tick_<t>`` telemetry span per
   tick, exactly one ``record_collective`` per *executed* stage
   transfer (so the measured ``comm/axis/pipe_*`` counters equal the
   static auditor's per-axis pricing), and the DP bucket psums traced
   into the cooldown region.

Axis-scoping rules (extends docs/parallelism.md's 2-D rules):

- **pipe collectives move stage boundaries**: one fp32
  ``collective_permute`` per executed activation/cotangent shift,
  priced at full payload on both the measured and static side. The
  host *skips* the shifts whose payload is an all-zeros constant (the
  tick-0 forward recv, the first backward tick's cotangent recv) —
  XLA would fold them away, and a folded op recorded as measured
  would diverge from the static audit.
- **data collectives move gradients** and compress (int8 + error
  feedback scoped to the ``data`` axis); **model collectives move
  activations** and stay fp32 — both exactly as on the 2-D mesh.
- **Edge (embedding / final-LN / LM-head) parameters** are replicated
  over ``pipe``; only their owning stage produces a nonzero gradient,
  and one fp32 psum over ``pipe`` rebroadcasts the true gradient to
  every stage (the tied-embedding idiom) before the DP sync.

Overlap-in-bubbles, stated honestly (the ``parallel/overlap.py``
convention): in one SPMD program the gradient accumulator is a single
tensor last written by the final backward tick, so the per-bucket DP
psums cannot be data-ready *during* earlier cooldown ticks — they are
traced after the final tick as K independent per-bucket collectives
(no chaining; the ``overlap-serialization`` lint rule proves it). On a
real TPU backend the latency-hiding scheduler is then free to overlap
them with the cooldown's trailing backward compute — the bubble slots
— because nothing downstream consumes them until the weight update.
On the 1-core CPU mesh this repo measures on, the win is eliminated
work, same as the 2-D overlapped step: the EF residual stays in the
bucket block domain (no per-step flatten/unflatten marshalling) and
``fold_average`` folds the ``1/dp`` averaging into the dequant scales.
``mode="baseline"`` keeps the identical bucket grid and wire bytes but
carries a leaf-domain residual with per-step marshalling and
divide-after averaging — the measured delta between the two is the
eliminated work, at provably identical per-axis comm bytes.

Elastic story: a ``(dp, tp, pp)`` run's per-stage ZeRO shard tables
consolidate/reshard through ``consolidate_zero_state_3d`` /
``reshard_zero_state_3d`` (contrib.optimizers.distributed_fused_adam),
and the supervisor's shrink policy gives up the *last* tuple axis
first — pipe, then model, then data (docs/resilience.md).

Import layering: this module is imported by the transformer-tree compat
shims *while* ``apex_tpu.transformer`` is mid-initialization, so it
imports nothing from ``apex_tpu.transformer`` or ``apex_tpu.parallel``
at module scope — only jax/numpy and telemetry. All substrate imports
(mesh2d, overlap, compression, resilience, parallel_state) are
function-local.
"""

import time
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.telemetry import comm as _telemetry_comm
from apex_tpu.telemetry import trace as _telemetry_trace
from apex_tpu.telemetry.registry import get_registry

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"

# The reference-API schedules below default to the transformer tree's
# 'pp' axis name. Kept as a literal: importing it from
# transformer.parallel_state at module scope would close the import
# cycle transformer/__init__ -> pipeline_parallel -> (shim) -> here.
PIPELINE_PARALLEL_AXIS = "pp"

_MOVED_WARNED = False


def _warn_moved(old_module):
    """One DeprecationWarning per process across BOTH compat shims —
    the first of ``schedules`` / ``p2p_communication`` to be imported
    warns, the second stays silent."""
    global _MOVED_WARNED
    if _MOVED_WARNED:
        return
    _MOVED_WARNED = True
    warnings.warn(
        f"{old_module} has moved to apex_tpu.parallel.pipeline; the "
        f"apex_tpu.transformer.pipeline_parallel modules are compat "
        f"shims re-exporting it",
        DeprecationWarning, stacklevel=3)


def _parallel_state():
    # lazy: see the PIPELINE_PARALLEL_AXIS layering note
    from apex_tpu.transformer import parallel_state
    return parallel_state


# ---------------------------------------------------------------------------
# p2p helpers (relocated from transformer.pipeline_parallel.p2p_communication)
# ---------------------------------------------------------------------------

def _perm_fwd(world, circular=False):
    if circular:
        return [(i, (i + 1) % world) for i in range(world)]
    return [(i, i + 1) for i in range(world - 1)]


def _perm_bwd(world, circular=False):
    if circular:
        return [(i, (i - 1) % world) for i in range(world)]
    return [(i + 1, i) for i in range(world - 1)]


def send_forward_recv_forward(output_tensor, axis_name=PIPELINE_PARALLEL_AXIS,
                              world: Optional[int] = None,
                              circular: bool = False):
    """Shift activations one stage forward: rank r's value arrives at r+1;
    rank 0 receives zeros (or rank P-1's value when ``circular``).
    (reference recv_forward + send_forward pair)"""
    world = (world if world is not None
             else _parallel_state().get_pipeline_model_parallel_world_size())
    if world == 1:
        return (output_tensor if circular
                else jax.tree_util.tree_map(jnp.zeros_like, output_tensor))
    perm = _perm_fwd(world, circular)
    return jax.tree_util.tree_map(
        lambda a: lax.ppermute(a, axis_name, perm), output_tensor)


def send_backward_recv_backward(input_tensor_grad,
                                axis_name=PIPELINE_PARALLEL_AXIS,
                                world: Optional[int] = None,
                                circular: bool = False):
    """Shift gradients one stage backward: rank r's value arrives at r-1;
    the last rank receives zeros (or rank 0's value when ``circular``)."""
    world = (world if world is not None
             else _parallel_state().get_pipeline_model_parallel_world_size())
    if world == 1:
        return (input_tensor_grad if circular
                else jax.tree_util.tree_map(jnp.zeros_like,
                                            input_tensor_grad))
    perm = _perm_bwd(world, circular)
    return jax.tree_util.tree_map(
        lambda a: lax.ppermute(a, axis_name, perm), input_tensor_grad)


# Aliases matching the reference wrapper names
# (fwd_bwd_pipelining_without_interleaving.py:87-240). Under SPMD every
# rank runs the same ppermute, so send and recv are one op.

def recv_forward(output_tensor, **kw):
    return send_forward_recv_forward(output_tensor, **kw)


def send_forward(output_tensor, **kw):
    return send_forward_recv_forward(output_tensor, **kw)


def recv_backward(input_tensor_grad, **kw):
    return send_backward_recv_backward(input_tensor_grad, **kw)


def send_backward(input_tensor_grad, **kw):
    return send_backward_recv_backward(input_tensor_grad, **kw)


def send_forward_recv_backward(output_tensor, input_tensor_grad, **kw):
    return (send_forward_recv_forward(output_tensor, **kw),
            send_backward_recv_backward(input_tensor_grad, **kw))


def send_backward_recv_forward(input_tensor_grad, output_tensor, **kw):
    return (send_backward_recv_backward(input_tensor_grad, **kw),
            send_forward_recv_forward(output_tensor, **kw))


# ---------------------------------------------------------------------------
# reference schedules (relocated from transformer.pipeline_parallel.schedules)
# ---------------------------------------------------------------------------

def listify_model(model):
    if isinstance(model, list):
        return model
    return [model]


def pipeline_schedule_plan(pp_size: int, num_microbatches: int,
                           num_model_chunks: int = 1) -> dict:
    """Static tick/memory plan of the pipelined schedules (pure Python).

    The schedules below derive their loop bounds and stash sizes from this
    function, so its numbers are the numbers — tests assert on them.

    Forward unit k = round*P*V + c*P + j of (chunk c, microbatch
    i = round*P + j) runs on rank r at tick k + r — microbatch groups of
    size P cycling through chunks, the reference's get_model_chunk_id
    order (V=1 degenerates to k = i) — and its backward mirrors it from
    tick V*P - 1 (the last global stage's backward shares its forward's
    tick). Chunk handoffs ride a circular ppermute with exactly-one-tick
    latency, so rank 0's warmup before its first backward is
    2(P-1) + (V-1)*P units, the reference's warmup formula
    (fwd_bwd_pipelining_with_interleaving.py num_warmup_microbatches).
    """
    P, M, V = pp_size, num_microbatches, num_model_chunks
    if V == 1:
        return {
            "warmup": P - 1,            # fwd-only ticks
            "steady": M,                # fwd+bwd ticks
            "cooldown": P - 1,          # bwd-only ticks
            "total": M + 2 * P - 2,
            "fwd_ticks": M + P - 1,     # ticks executing a fwd unit
            "bwd_ticks": M + P - 1,
            "stash": min(M, 2 * P - 1),  # in-flight stage inputs: O(P)
        }
    return {
        "warmup": V * P - 1,
        "steady": M * V,
        "cooldown": P - 1,
        "total": M * V + V * P + P - 2,
        "fwd_ticks": M * V + V * P - 1,
        "bwd_ticks": M * V + P - 1,
        "stash": min(M * V, 2 * V * P),  # O(P*V) chunk-stage inputs
    }


def get_forward_backward_func(virtual_pipeline_model_parallel_size=None,
                              pipeline_model_parallel_size=None):
    """Select a schedule (reference schedules/__init__.py:22-35).

    A pipeline split rank installed via ``initialize_model_parallel``
    selects the encoder-decoder schedule (the reference routes
    ``ModelType.encoder_and_decoder`` through the same selector; its
    interleaved schedule is encoder_or_decoder-only, and so is ours)."""
    ps = _parallel_state()
    if pipeline_model_parallel_size is None:
        pipeline_model_parallel_size = \
            ps.get_pipeline_model_parallel_world_size()
    if virtual_pipeline_model_parallel_size is None:
        virtual_pipeline_model_parallel_size = (
            ps.get_virtual_pipeline_model_parallel_world_size())
    if pipeline_model_parallel_size > 1:
        if ps.get_pipeline_model_parallel_split_rank() is not None:
            if virtual_pipeline_model_parallel_size is not None:
                raise ValueError(
                    "interleaved (virtual-pipeline) scheduling does not "
                    "compose with an encoder-decoder split rank")
            return forward_backward_pipelining_with_split
        if virtual_pipeline_model_parallel_size is not None:
            return forward_backward_pipelining_with_interleaving
        return forward_backward_pipelining_without_interleaving
    return forward_backward_no_pipelining


def forward_backward_no_pipelining(forward_step_func, loss_func, params,
                                   microbatches, *, num_microbatches,
                                   grad_scale=1.0, **unused):
    """Accumulate grads over microbatches without pipelining
    (reference fwd_bwd_no_pipelining.py:23-124; grad sync deferral to the
    last microbatch is automatic — sync happens once on the returned
    accumulated grads)."""

    def one_microbatch(params, mb):
        def full(p):
            y = forward_step_func(p, None, mb, jnp.asarray(True))
            return loss_func(p, y, mb)

        loss, grads = jax.value_and_grad(full)(params)
        return loss, grads

    def scan_body(carry, mb):
        loss_sum, grads_acc = carry
        loss, grads = one_microbatch(params, mb)
        grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
        return (loss_sum + loss, grads_acc), loss

    zero_grads = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, grads), losses = lax.scan(
        scan_body, (jnp.zeros((), jnp.float32), zero_grads), microbatches)
    n = jnp.asarray(num_microbatches, jnp.float32)
    grads = jax.tree_util.tree_map(lambda g: g * (grad_scale / n), grads)
    return losses, grads


def _payload_spec(tensor_shape, dtype):
    """Normalize the boundary-payload description to a pytree of
    ``jax.ShapeDtypeStruct``. A plain tuple/list of ints (the common
    single-activation case) becomes one leaf of ``dtype``; anything else
    is taken as an already-built spec pytree — the encoder-decoder
    schedule passes a two-leaf dict (reference dual shapes,
    ...without_interleaving.py:29-86)."""
    if (isinstance(tensor_shape, (tuple, list))
            and all(isinstance(d, (int, np.integer)) for d in tensor_shape)):
        return jax.ShapeDtypeStruct(
            tuple(int(d) for d in tensor_shape), dtype)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(tuple(s.shape), s.dtype),
        tensor_shape)


def _pipelined_fwd_bwd(forward_step_func, loss_func, params, microbatches,
                       *, M, V, P, tensor_shape, dtype, axis_name,
                       grad_scale, aux_loss=False):
    """Shared 3-phase tick machine for both pipelined schedules
    (see pipeline_schedule_plan for the tick/unit mapping).

    The stage-boundary payload is a pytree (single activation array for
    GPT-style stacks; an {encoder, decoder} pair for split-rank models);
    every payload op below — stash, ppermute shift, masking, dtype cast —
    is tree-mapped over its leaves.

    ``aux_loss=True`` changes the stage contract to
    ``forward_step_func(...) -> (output_tensor, aux_scalar)``: each
    unit's backward injects its own stage's auxiliary loss (e.g. MoE
    router load-balancing, scaled by grad_scale like the main loss)
    alongside the downstream activation cotangent — total loss =
    last-stage loss_func + sum of per-unit aux, with aux gradients
    flowing to earlier stages through the regular backward wave. The
    reported per-microbatch losses remain the last stage's (loss_func +
    its own aux) only.
    """
    plan = pipeline_schedule_plan(P, M, V)
    S = plan["stash"]
    PV, MV = P * V, M * V
    T0 = V * P - 1  # first backward tick (mb 0 has crossed all V*P stages)
    rank = lax.axis_index(axis_name)
    interleaved = V > 1
    tmap = jax.tree_util.tree_map
    spec = _payload_spec(tensor_shape, dtype)

    def _mask(pred, tree):
        return tmap(lambda a: jnp.where(pred, a, jnp.zeros_like(a)), tree)

    def _select(pred, tree_a, tree_b):
        return tmap(lambda a, b: jnp.where(pred, a, b), tree_a, tree_b)

    def _cast(tree):
        return tmap(lambda a, s: a.astype(s.dtype), tree, spec)

    def take_mb(i):
        return jax.tree_util.tree_map(lambda a: a[i], microbatches)

    if interleaved:
        def take_params(c):
            return jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
                params)

        def add_grads(grads, dp, c, active):
            return jax.tree_util.tree_map(
                lambda a, d: a.at[c].add(
                    jnp.where(active, d.astype(jnp.float32), 0.0)),
                grads, dp)
    else:
        def take_params(c):
            return params

        def add_grads(grads, dp, c, active):
            return jax.tree_util.tree_map(
                lambda a, d: a + jnp.where(active, d.astype(jnp.float32),
                                           0.0),
                grads, dp)

    def fwd_unit(k):
        rnd, rem = k // PV, k % PV
        c, j = rem // P, rem % P
        return c, rnd * P + j, k % S

    def bwd_unit(kb):
        rnd, rem = kb // PV, kb % PV
        c, j = (V - 1) - rem // P, rem % P
        kf = rnd * PV + c * P + j
        return c, rnd * P + j, kf % S

    zero_h = tmap(lambda s: jnp.zeros(s.shape, s.dtype), spec)

    def run_stage(p, h, mb, is_first_u):
        if aux_loss:
            return forward_step_func(p, h, mb, is_first_u)
        return (forward_step_func(p, h, mb, is_first_u),
                jnp.zeros((), jnp.float32))

    def stage_and_maybe_loss(p, h, mb, is_first_u, is_last_u):
        y, aux = run_stage(p, h, mb, is_first_u)
        # Only the last global stage pays for loss_func (for GPT: the
        # vocab projection) — lax.cond skips it at runtime elsewhere, in
        # both the primal and the transpose. Per-unit aux (module doc)
        # rides the same loss output.
        loss = lax.cond(
            is_last_u,
            lambda op: loss_func(*op).astype(jnp.float32),
            lambda op: jnp.zeros((), jnp.float32),
            (p, y, mb))
        return y, loss + aux.astype(jnp.float32)

    # state = (stash, y_prev, dx_prev, losses, grads)
    def fwd_half(t, state):
        with jax.named_scope("pp_fwd_unit"):
            xs, y_prev, dx_prev, losses, grads = state
            recv = send_forward_recv_forward(
                y_prev, axis_name, world=P, circular=interleaved)
            k = t - rank
            active = (k >= 0) & (k < MV)
            c, i, slot = fwd_unit(jnp.clip(k, 0, MV - 1))
            mb = take_mb(i)
            p_c = take_params(c)
            is_first_u = (rank == 0) & (c == 0)
            h_in = _cast(_select(is_first_u, zero_h, recv))
            y, _ = run_stage(p_c, h_in, mb, is_first_u)
            xs = tmap(
                lambda buf, h: lax.dynamic_update_index_in_dim(
                    buf, jnp.where(active, h, buf[slot]), slot, 0),
                xs, h_in)
            y_prev = _mask(active, y)
            return xs, y_prev, dx_prev, losses, grads

    def bwd_half(t, state):
        with jax.named_scope("pp_bwd_unit"):
            xs, y_prev, dx_prev, losses, grads = state
            dy_recv = send_backward_recv_backward(
                dx_prev, axis_name, world=P, circular=interleaved)
            kb = t - T0 - (P - 1 - rank)
            active = (kb >= 0) & (kb < MV)
            c, i, slot = bwd_unit(jnp.clip(kb, 0, MV - 1))
            mb = take_mb(i)
            p_c = take_params(c)
            is_first_u = (rank == 0) & (c == 0)
            is_last_u = (rank == P - 1) & (c == V - 1)
            # the last global stage's backward shares its forward's tick,
            # and fwd_half runs first in a steady tick, so the slot read
            # here is the input stashed moments ago; other reads never
            # collide with this tick's write (ring size >= in-flight).
            h_in = tmap(lambda buf: buf[slot], xs)
            (_, loss), pullback = jax.vjp(
                lambda p, h: stage_and_maybe_loss(p, h, mb, is_first_u,
                                                  is_last_u), p_c, h_in)
            dy_cot = _cast(_mask(active & ~is_last_u, dy_recv))
            # every active unit gets a loss cotangent: the main loss is
            # cond-gated to the last stage (zero transpose elsewhere),
            # while per-unit aux losses (if any) pick it up on their
            # own stage
            loss_cot = jnp.where(active,
                                 jnp.asarray(grad_scale, jnp.float32), 0.0)
            dp_c, dh = pullback((dy_cot, loss_cot))
            grads = add_grads(grads, dp_c, c, active)
            losses = losses.at[i].add(
                jnp.where(active & is_last_u, loss, 0.0))
            dx_prev = _cast(_mask(active, dh))
            return xs, y_prev, dx_prev, losses, grads

    zero_grads = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    stash0 = tmap(lambda s: jnp.zeros((S,) + tuple(s.shape), s.dtype), spec)
    state = (stash0, zero_h, zero_h,
             jnp.zeros((M,), jnp.float32), zero_grads)
    w, s = plan["warmup"], plan["steady"]
    state = lax.fori_loop(0, w, fwd_half, state)
    state = lax.fori_loop(w, w + s,
                          lambda t, st: bwd_half(t, fwd_half(t, st)), state)
    state = lax.fori_loop(w + s, plan["total"], bwd_half, state)
    _, _, _, losses, grads = state
    n = jnp.asarray(M, jnp.float32)
    grads = jax.tree_util.tree_map(lambda g: g / n, grads)
    return losses, grads


def forward_backward_pipelining_without_interleaving(
        forward_step_func: Callable, loss_func: Callable, params,
        microbatches, *, num_microbatches: int,
        tensor_shape, dtype=jnp.float32,
        axis_name: str = PIPELINE_PARALLEL_AXIS,
        grad_scale: float = 1.0,
        pp_size: Optional[int] = None,
        aux_loss: bool = False,
        **unused):
    """True 1F1B over the 'pp' axis in one jitted program (see module doc).

    Parity target: fwd_bwd_pipelining_without_interleaving.py:241-597.
    Returns (per-microbatch losses [M] — nonzero on the last stage only,
    grads pytree scaled by grad_scale / num_microbatches).

    Must run inside shard_map with the 'pp' axis bound; ``tensor_shape``
    is the (seq, microbatch, hidden) activation shape crossing stage
    boundaries (reference get_tensor_shapes,
    ...without_interleaving.py:29-86).
    """
    P = pp_size or _parallel_state().get_pipeline_model_parallel_world_size()
    return _pipelined_fwd_bwd(
        forward_step_func, loss_func, params, microbatches,
        M=num_microbatches, V=1, P=P, tensor_shape=tensor_shape,
        dtype=dtype, axis_name=axis_name, grad_scale=grad_scale,
        aux_loss=aux_loss)


def forward_backward_pipelining_with_interleaving(
        forward_step_func: Callable, loss_func: Callable, params,
        microbatches, *, num_microbatches: int, tensor_shape,
        dtype=jnp.float32, axis_name: str = PIPELINE_PARALLEL_AXIS,
        grad_scale: float = 1.0, pp_size: Optional[int] = None,
        num_model_chunks: Optional[int] = None, aux_loss: bool = False,
        **unused):
    """Interleaved (virtual-pipeline) 1F1B in one steady state.

    Parity target: fwd_bwd_pipelining_with_interleaving.py (516 LoC).
    ``params`` is a pytree whose leaves carry a leading ``num_model_chunks``
    dim (stacked virtual chunks per rank); chunk c on rank r is global
    stage c * P + r. Unlike a sequential-passes scheme (bubble V*(P-1)
    full passes), all chunks share ONE steady state: each global tick maps
    to a (chunk, microbatch) unit per rank via the reference's
    get_model_chunk_id order, so the forward wave fills in V*P - 1 ticks
    and drains in P - 1 — per-rank overhead (V*P-1) fwd units + (P-1) bwd
    units over the M*V useful ticks, matching the reference's rank-0
    warmup of 2(P-1) + (V-1)P forward units. Chunk handoffs (rank P-1's
    chunk-c output -> rank 0's chunk c+1 input, and the reverse for
    grads) have exactly-one-tick latency under this order, so they ride
    the same *circular* ppermute as the intra-chunk shifts — no boundary
    buffers.
    """
    ps = _parallel_state()
    P = pp_size or ps.get_pipeline_model_parallel_world_size()
    V = (num_model_chunks
         or ps.get_virtual_pipeline_model_parallel_world_size() or 1)
    if V == 1:
        return forward_backward_pipelining_without_interleaving(
            forward_step_func, loss_func, params, microbatches,
            num_microbatches=num_microbatches, tensor_shape=tensor_shape,
            dtype=dtype, axis_name=axis_name, grad_scale=grad_scale,
            pp_size=P, aux_loss=aux_loss)
    if num_microbatches % P != 0:
        # reference fwd_bwd_pipelining_with_interleaving.py asserts
        # num_microbatches % pipeline_parallel_size == 0
        raise ValueError(
            f"interleaved schedule requires num_microbatches "
            f"({num_microbatches}) to be a multiple of "
            f"pipeline_model_parallel_size ({P})")
    return _pipelined_fwd_bwd(
        forward_step_func, loss_func, params, microbatches,
        M=num_microbatches, V=V, P=P, tensor_shape=tensor_shape,
        dtype=dtype, axis_name=axis_name, grad_scale=grad_scale,
        aux_loss=aux_loss)


def forward_backward_pipelining_with_split(
        forward_step_func: Callable, loss_func: Callable, params,
        microbatches, *, num_microbatches: int,
        encoder_tensor_shape, decoder_tensor_shape,
        dtype=jnp.float32, axis_name: str = PIPELINE_PARALLEL_AXIS,
        grad_scale: float = 1.0, pp_size: Optional[int] = None,
        split_rank: Optional[int] = None, aux_loss: bool = False,
        **unused):
    """Encoder-decoder (split-rank) 1F1B.

    Parity target: the reference's ``ModelType.encoder_and_decoder`` path —
    dual p2p tensor shapes computed from ``decoder_seq_length``
    (fwd_bwd_pipelining_without_interleaving.py:29-86's get_tensor_shapes)
    with the encoder on ranks ``< split_rank`` and the decoder at/after it
    (parallel_state.py:243-331 places embedding groups around the same
    split). The reference moves *two* tensors across decoder-side stage
    boundaries (encoder memory + decoder stream); here the boundary
    payload is the two-leaf pytree
    ``{"encoder": (enc_seq, mb, h), "decoder": (dec_seq, mb, h)}`` riding
    the same tick machine — encoder ranks advance the encoder leaf and
    pass the decoder leaf through untouched; decoder ranks advance the
    decoder leaf with the encoder leaf as cross-attention memory,
    forwarding it unchanged so every decoder stage sees the final encoder
    output. Interleaving is not supported with a split (matches the
    reference's encoder_or_decoder-only interleaved schedule).

    Stage contract (build with :func:`make_encoder_decoder_step`):

        forward_step_func(params, payload_dict, mb, is_first_stage)
            -> payload_dict
        loss_func(params, payload_dict, mb) -> scalar   # reads "decoder"

    Returns (per-microbatch losses [M] — nonzero on the last stage only,
    grads pytree scaled by grad_scale / num_microbatches).
    """
    P = pp_size or _parallel_state().get_pipeline_model_parallel_world_size()
    split = (split_rank if split_rank is not None
             else _parallel_state().get_pipeline_model_parallel_split_rank())
    if split is None or not 0 < split < P:
        raise ValueError(
            f"encoder-decoder pipelining needs 0 < split_rank < pp_size; "
            f"got split_rank={split}, pp_size={P} (set it via "
            f"initialize_model_parallel(..., "
            f"pipeline_model_parallel_split_rank=...) or pass split_rank=)")
    spec = {
        "encoder": jax.ShapeDtypeStruct(tuple(encoder_tensor_shape), dtype),
        "decoder": jax.ShapeDtypeStruct(tuple(decoder_tensor_shape), dtype),
    }
    return _pipelined_fwd_bwd(
        forward_step_func, loss_func, params, microbatches,
        M=num_microbatches, V=1, P=P, tensor_shape=spec, dtype=dtype,
        axis_name=axis_name, grad_scale=grad_scale, aux_loss=aux_loss)


def make_encoder_decoder_step(encoder_step: Callable, decoder_step: Callable,
                              *, split_rank: Optional[int] = None,
                              axis_name: str = PIPELINE_PARALLEL_AXIS):
    """Build the stage fn for :func:`forward_backward_pipelining_with_split`
    from per-side step functions:

        encoder_step(params, enc_h, mb, is_first_stage) -> enc_h
            (build enc_h from the microbatch when is_first_stage)
        decoder_step(params, dec_h, enc_memory, mb, is_split_stage) -> dec_h
            (build dec_h from the microbatch when is_split_stage — the
            first decoder stage, where the upstream decoder leaf is zeros)

    Rank-side selection is a runtime ``lax.cond`` on the pp mesh position
    vs the split rank — one SPMD program, each rank executes only its own
    side (consuming the split-rank bookkeeping the reference keeps in
    parallel_state.py:469-486 / is_pipeline_stage_before_split).
    ``params`` must carry both sides' weights in a uniform pytree on every
    rank (each rank's unused side receives zero grads).
    """
    split = (split_rank if split_rank is not None
             else _parallel_state().get_pipeline_model_parallel_split_rank())
    if split is None:
        raise ValueError("make_encoder_decoder_step needs a split rank")

    def step(params, payload, mb, is_first_stage):
        rank = lax.axis_index(axis_name)

        def enc_branch(op):
            p, pl, mb_, first = op
            return {"encoder": encoder_step(p, pl["encoder"], mb_, first),
                    "decoder": pl["decoder"]}

        def dec_branch(op):
            p, pl, mb_, _ = op
            return {"encoder": pl["encoder"],
                    "decoder": decoder_step(p, pl["decoder"], pl["encoder"],
                                            mb_, rank == split)}

        return lax.cond(rank >= split, dec_branch, enc_branch,
                        (params, payload, mb, is_first_stage))

    return step


# ---------------------------------------------------------------------------
# the 3-D (data, model, pipe) mesh
# ---------------------------------------------------------------------------

def mesh_3d(data=2, model=2, pipe=None, devices=None):
    """The named 3-D ``(data, model, pipe)`` mesh: ``data`` planes of
    ``model`` x ``pipe`` tiles over the first ``data * model * pipe``
    devices (default: all of them,
    ``pipe = len(devices) // (data * model)``)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if pipe is None:
        if len(devices) % (data * model) != 0:
            raise ValueError(
                f"mesh_3d: {len(devices)} devices do not split into "
                f"data={data} x model={model} planes")
        pipe = len(devices) // (data * model)
    need = data * model * pipe
    if len(devices) < need:
        raise ValueError(
            f"mesh_3d: need {need} devices (data={data} x model={model} "
            f"x pipe={pipe}), have {len(devices)}")
    return Mesh(np.asarray(devices[:need]).reshape(data, model, pipe),
                (DATA_AXIS, MODEL_AXIS, PIPE_AXIS))


def analytic_bubble_fraction(pp, microbatches):
    """The 1F1B bubble model: of ``m + pp - 1`` pipeline slots per
    phase, ``pp - 1`` are idle — fraction ``(pp-1)/(m+pp-1)``
    (docs/parallelism.md has the derivation and the measured
    comparison)."""
    return (pp - 1) / float(microbatches + pp - 1)


def schedule_ticks(pp, microbatches):
    """Host-side 1F1B tick table — the Python mirror of the tick machine
    (V=1): per tick, which (rank, microbatch) forward/backward units
    execute. :func:`build_pipeline_step` drives its unrolled loop off
    this table and stamps each tick's entry onto its ``pp_tick_<t>``
    telemetry span, which is what ``tools/telemetry_report.py`` renders
    as the per-stage microbatch timeline."""
    plan = pipeline_schedule_plan(pp, microbatches)
    w, s, total = plan["warmup"], plan["steady"], plan["total"]
    T0 = pp - 1
    ticks = []
    for t in range(total):
        fwd = [[r, t - r] for r in range(pp)
               if t < w + s and 0 <= t - r < microbatches]
        bwd = [[r, t - T0 - (pp - 1 - r)] for r in range(pp)
               if t >= w and 0 <= t - T0 - (pp - 1 - r) < microbatches]
        phase = ("warmup" if t < w
                 else "steady" if t < w + s else "cooldown")
        ticks.append({"tick": t, "phase": phase, "fwd": fwd, "bwd": bwd})
    return ticks


# ---------------------------------------------------------------------------
# stage-partitioned GPT-2 parameter layout
# ---------------------------------------------------------------------------

def split_stages(seg_params, pp):
    """Partition the mesh2d segment tuple into ``pp`` contiguous stages
    of ``layers // pp`` layers each."""
    layers = len(seg_params)
    if layers % pp:
        raise ValueError(
            f"{layers} layers do not split into pp={pp} stages")
    lp = layers // pp
    return ([tuple(seg_params[s * lp:(s + 1) * lp]) for s in range(pp)],
            lp)


def stack_stage_blocks(seg_params, pp):
    """``(blocks, edge)``: the transformer block params stacked to
    leaves ``[pp, Lp, ...]`` (stage-sharded over ``pipe``, TP dims over
    ``model``) plus the ``edge`` dict — embedding tables, final LN, LM
    head — replicated on every rank (only the owning stage computes
    with them; a pipe psum rebroadcasts their gradients)."""
    stages, _ = split_stages(seg_params, pp)
    per_stage = []
    for stage in stages:
        layer_dicts = [seg["layer"] for seg in stage]
        per_stage.append(jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *layer_dicts))
    blocks = jax.tree_util.tree_map(lambda *ss: jnp.stack(ss), *per_stage)
    edge = {"embed": seg_params[0]["embed"],
            "ln_f": seg_params[-1]["ln_f"],
            "head": seg_params[-1]["head"]}
    return blocks, edge


def pipeline_zero_segments(seg_params):
    """``(segments, partition_dims)`` in the pipeline ZeRO convention:
    one segment per transformer layer in model order plus the
    pipe-replicated edge LAST — the ``params``/``partition_dims``
    inputs of :func:`~apex_tpu.contrib.optimizers.
    distributed_fused_adam.consolidate_zero_state_3d` (and its
    reshard inverse) with ``shared_tail=1``. Matches the segment
    layout :func:`build_pipeline_step`'s DP sync buckets are planned
    over, so per-stage optimizer states line up leaf-for-leaf."""
    from apex_tpu.parallel.mesh2d import gpt2_partition_dims

    _, edge = stack_stage_blocks(seg_params, 1)
    segments = [seg["layer"] for seg in seg_params] + [edge]
    return segments, gpt2_partition_dims(segments)


def pipeline_block_pspecs(blocks):
    """PartitionSpecs for the stacked block leaves: dim 0 (stage) over
    ``pipe``, the mesh2d TP partition dim (shifted by the two stacking
    dims) over ``model``, replicated over ``data``."""
    from apex_tpu.parallel.mesh2d import _COL_B, _COL_W, _ROW_W, _leaf_name

    def spec(path, leaf):
        name = _leaf_name(path)
        if name in _COL_W:
            return P(PIPE_AXIS, None, None, MODEL_AXIS)
        if name in _COL_B or name in _ROW_W:
            return P(PIPE_AXIS, None, MODEL_AXIS)
        return P(PIPE_AXIS)

    return jax.tree_util.tree_map_with_path(spec, blocks)


def place_pipeline_state(mesh, blocks, edge, *extra):
    """Commit the stacked blocks to their ``NamedSharding`` placement
    and the edge + every extra carry tree to the replicated sharding —
    one compiled signature for the first call and the steady state
    (the mesh2d ``place_state`` discipline, including the copy-before-
    device_put donation-aliasing guard)."""
    from apex_tpu.parallel.mesh2d import _norm_spec

    bspecs = jax.tree_util.tree_map(lambda s: _norm_spec(s, mesh),
                                    pipeline_block_pspecs(blocks))
    fresh = jax.tree_util.tree_map(jnp.copy, blocks)
    placed = jax.device_put(
        fresh,
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), bspecs))
    rep = NamedSharding(mesh, P())
    return (placed,) + tuple(
        jax.device_put(jax.tree_util.tree_map(jnp.copy, t), rep)
        for t in (edge,) + extra)


def make_batch_3d(mesh, *, microbatches, batch_per_replica=2, seq=16,
                  vocab=64, seed=1):
    """Token/label batch sharded over ``data`` (replicated over
    ``model`` and ``pipe``): ``microbatches * batch_per_replica`` rows
    per data rank, reshaped to ``[M, b, seq]`` inside the step."""
    rng = np.random.RandomState(seed)
    rows = microbatches * batch_per_replica * mesh.shape[DATA_AXIS]
    tokens = jnp.asarray(rng.randint(0, vocab, (rows, seq)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, vocab, (rows, seq)), jnp.int32)
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    return jax.device_put((tokens, labels), sharding)


# ---------------------------------------------------------------------------
# the host-driven 1F1B train step
# ---------------------------------------------------------------------------

def build_pipeline_step(mesh, seg_params, *, hidden, heads, microbatches,
                        mode="overlapped", compress="int8", lr=0.05,
                        fold_average=True, message_size=10000000,
                        guard_nan=None, straggler=None, donate=True):
    """One jitted 3-D ``(data, model, pipe)`` train step.

    The schedule is the same 1F1B tick math as the reference machine
    (:func:`_pipelined_fwd_bwd` at V=1), host-unrolled over
    :func:`schedule_ticks` — per-tick ``pp_tick_<t>`` spans, one
    recorded ``collective_permute`` per *executed* stage shift (the
    all-zeros tick-0 forward recv and first-backward cotangent recv are
    skipped, see module doc), and the DP bucket psums traced into the
    cooldown region.

    ``mode="overlapped"``: bucket-domain EF residual, ``fold_average``,
    per-bucket DP psums emitted as independent collectives after the
    final backward tick — ``step(blocks, edge, res, tokens, labels) ->
    (blocks, edge, res, loss)``.

    ``mode="baseline"``: identical bucket grid and wire bytes, but a
    leaf-domain residual with per-step flatten/pad marshalling and
    divide-after averaging — same signature.

    ``mode="guarded"``: the overlapped step under
    ``resilience.guarded_update`` with the local non-finite flag OR'd
    over ALL THREE axes — every ``(data, model, pipe)`` coordinate must
    agree to commit — ``step(blocks, edge, res, gst, step_idx, tokens,
    labels) -> (blocks, edge, res, gst, loss)``. ``guard_nan=(step,
    stage, microbatch)`` arms ``faults.inject_nan`` at that exact
    schedule unit's stage input.

    ``straggler=(stage, delay_s)`` is the trace-time straggler fault
    for the online attribution acceptance
    (``telemetry.attribution``): every tick in which ``stage`` has a
    forward or backward unit sleeps ``delay_s`` host seconds inside
    its ``pp_tick_<t>`` span. The sleep happens while the schedule is
    being *traced* — the compiled program is unchanged — so the
    measured span deltas carry a genuine per-stage slowdown that the
    exposure-difference estimator must name.

    Returns ``(jitted_step, state)`` where ``state`` is the placed
    carry tuple (blocks, edge, residual[, guard state]).
    """
    from apex_tpu import resilience
    from apex_tpu.parallel import compression, mesh2d
    from apex_tpu.parallel.distributed import flatten, unflatten
    from apex_tpu.parallel.overlap import OverlappedDataParallel
    from apex_tpu.resilience import faults
    from apex_tpu.resilience.guard import nonfinite_flag

    head_dim = hidden // heads
    dp = mesh.shape[DATA_AXIS]
    tp = mesh.shape[MODEL_AXIS]
    pp = mesh.shape[PIPE_AXIS]
    _, lp = split_stages(seg_params, pp)
    M = int(microbatches)
    plan3 = pipeline_schedule_plan(pp, M)
    w, s, total = plan3["warmup"], plan3["steady"], plan3["total"]
    S, T0 = plan3["stash"], pp - 1
    ticks = schedule_ticks(pp, M)
    if mode not in ("baseline", "overlapped", "guarded"):
        raise ValueError(f"unknown mode {mode!r}")

    blocks, edge = stack_stage_blocks(seg_params, pp)
    bspecs = pipeline_block_pspecs(blocks)

    # DP sync segments: one per layer (every stage's layer l shares
    # shapes, so one LOCAL per-model-rank template serves all) plus the
    # edge — buckets never span a layer/edge boundary.
    layer_local = mesh2d.local_template(seg_params[0]["layer"], tp)
    edge_local = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), edge)
    seg_templates = [layer_local] * lp + [edge_local]

    odp = OverlappedDataParallel(
        axis_name=DATA_AXIS, compress=compress,
        fold_average=(fold_average and mode != "baseline"),
        message_size=message_size)
    plan = odp.plan(seg_templates)
    stateful = compression.needs_residual(compress)
    if not stateful:
        residual = jnp.zeros(())
    elif mode == "baseline":
        # leaf-domain EF state — the honest marshalling baseline
        residual = tuple(jax.tree_util.tree_map(jnp.copy, t)
                         for t in seg_templates)
    else:
        residual = odp.init_residual(seg_templates)

    def run_pipeline(lb, eP, tokens, labels, step_idx=None):
        """The unrolled 1F1B schedule on LOCAL shards. ``lb`` leaves are
        the ``[Lp, ...local]`` stage view; returns ``(gB, gE, loss)``
        with grads already divided by M, edge grads pipe-psummed, and
        the scalar loss reduced over pipe and data."""
        rank = lax.axis_index(PIPE_AXIS)
        is_first = rank == 0
        is_last = rank == pp - 1
        b = tokens.shape[0] // M
        seq_len = tokens.shape[1]
        tok3 = tokens.reshape(M, b, seq_len)
        lab3 = labels.reshape(M, b, seq_len)
        reg = get_registry()
        if reg.enabled:
            reg.event("pipeline", "plan", stages=pp, microbatches=M,
                      warmup=w, steady=s, cooldown=plan3["cooldown"],
                      total=total, stash=S)

        def stage_fwd(lbv, ev, h_in, tok, i):
            x0 = ev["embed"]["wte"][tok] + ev["embed"]["wpe"][:seq_len]
            x = jnp.where(is_first, x0, h_in)
            if guard_nan is not None:
                gstep, gstage, gmb = guard_nan
                nanval = faults.inject_nan(
                    jnp.zeros((), jnp.float32), step_idx, nan_step=gstep)
                # where, not multiply: NaN-safe off the target unit
                x = x + jnp.where((rank == gstage) & (i == gmb),
                                  nanval, 0.0)
            for layer_i in range(lp):
                pl = jax.tree_util.tree_map(
                    lambda a, li=layer_i: a[li], lbv)
                x = mesh2d._block(pl, x, head_dim)
            return x

        def stage_and_loss(lbv, ev, h_in, tok, lab, i):
            x = stage_fwd(lbv, ev, h_in, tok, i)

            def last_loss(op):
                xv, ev_, lab_ = op
                xn = mesh2d._ln(ev_["ln_f"], xv)
                return mesh2d._xent(xn @ ev_["head"]["w"], lab_)

            loss = lax.cond(is_last, last_loss,
                            lambda op: jnp.zeros((), jnp.float32),
                            (x, ev, lab))
            return x, loss

        zero_h = jnp.zeros((b, seq_len, hidden), jnp.float32)
        stash = jnp.zeros((S, b, seq_len, hidden), jnp.float32)
        y_prev = zero_h
        dx_prev = zero_h
        losses = jnp.zeros((M,), jnp.float32)
        gB = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), lb)
        gE = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), eP)
        h_elems = b * seq_len * hidden
        fwd_perm = _perm_fwd(pp)
        bwd_perm = _perm_bwd(pp)

        def shift(arr, perm):
            _telemetry_comm.record_collective(
                "ppermute", elements=h_elems, dtype=jnp.float32,
                axis_name=PIPE_AXIS)
            return lax.ppermute(arr, PIPE_AXIS, perm)

        def take(a3, i):
            return lax.dynamic_index_in_dim(a3, i, 0, keepdims=False)

        one = jnp.asarray(1.0, jnp.float32)
        zero = jnp.asarray(0.0, jnp.float32)
        for tk in ticks:
            t = tk["tick"]
            with _telemetry_trace.span(
                    f"pp_tick_{t}", role="tick", phase=tk["phase"],
                    tick=t, fwd=tk["fwd"], bwd=tk["bwd"]):
                if straggler is not None:
                    s_stage, s_delay = straggler
                    if any(u[0] == s_stage
                           for u in tk["fwd"] + tk["bwd"]):
                        time.sleep(float(s_delay))
                if t < w + s:  # ------------------------ forward half
                    if pp > 1 and t >= 1:
                        # tick 0's upstream is an all-zeros constant:
                        # the host skips the shift XLA would fold away,
                        # keeping measured counters == the static audit
                        y_recv = shift(y_prev, fwd_perm)
                    else:
                        y_recv = zero_h
                    k = t - rank
                    active = (k >= 0) & (k < M)
                    i = jnp.clip(k, 0, M - 1)
                    slot = i % S
                    y = stage_fwd(lb, eP, y_recv, take(tok3, i), i)
                    stash = lax.dynamic_update_index_in_dim(
                        stash,
                        jnp.where(active, y_recv, take(stash, slot)),
                        slot, 0)
                    y_prev = jnp.where(active, y, 0.0)
                if t >= w:  # --------------------------- backward half
                    if pp > 1 and t >= w + 1:
                        dy_recv = shift(dx_prev, bwd_perm)
                    else:
                        dy_recv = zero_h
                    kb = t - T0 - (pp - 1 - rank)
                    active_b = (kb >= 0) & (kb < M)
                    ib = jnp.clip(kb, 0, M - 1)
                    slot_b = ib % S
                    tok = take(tok3, ib)
                    lab = take(lab3, ib)
                    h_in = take(stash, slot_b)
                    (_, loss_u), pull = jax.vjp(
                        lambda lb_, e_, h_: stage_and_loss(
                            lb_, e_, h_, tok, lab, ib), lb, eP, h_in)
                    dy_cot = jnp.where(active_b & (~is_last),
                                       dy_recv, 0.0)
                    loss_cot = jnp.where(active_b, one, zero)
                    d_lb, d_e, dh = pull((dy_cot, loss_cot))
                    gB = jax.tree_util.tree_map(
                        lambda a, d: a + jnp.where(active_b, d, 0.0),
                        gB, d_lb)
                    gE = jax.tree_util.tree_map(
                        lambda a, d: a + jnp.where(active_b, d, 0.0),
                        gE, d_e)
                    losses = losses.at[ib].add(
                        jnp.where(active_b & is_last, loss_u, 0.0))
                    dx_prev = jnp.where(active_b, dh, 0.0)

        gB = jax.tree_util.tree_map(lambda a: a / M, gB)
        gE = jax.tree_util.tree_map(lambda a: a / M, gE)
        if pp > 1:
            # tied-edge psum: only the owning stage produced a nonzero
            # grad; the sum rebroadcasts it so replicated edge copies
            # stay identical after the update
            edge_elems = sum(int(a.size)
                             for a in jax.tree_util.tree_leaves(gE))
            _telemetry_comm.record_collective(
                "psum", elements=edge_elems, dtype=jnp.float32,
                axis_name=PIPE_AXIS)
            gE = lax.psum(gE, PIPE_AXIS)
            _telemetry_comm.record_collective(
                "psum", elements=M, dtype=jnp.float32,
                axis_name=PIPE_AXIS)
            losses = lax.psum(losses, PIPE_AXIS)
        loss = jnp.sum(losses) / M
        if dp > 1:
            _telemetry_comm.record_collective(
                "psum", elements=1, dtype=jnp.float32,
                axis_name=DATA_AXIS)
            loss = lax.psum(loss, DATA_AXIS) / dp
        return gB, gE, loss

    def dp_sync(gB, gE, res):
        """The per-bucket DP psums, traced into the cooldown region —
        K independent collectives (module doc), each in its
        ``ddp_overlap_bucket_<n>`` span with ``bubble=True``. Returns
        ``(syncedB stacked [Lp, ...], syncedE, new_res)``."""
        seg_grads = [jax.tree_util.tree_map(
            lambda a, li=layer_i: a[li], gB) for layer_i in range(lp)]
        seg_grads.append(gE)
        K = lp + 1
        reg = get_registry()
        if reg.enabled:
            reg.event("overlap", "plan", segments=K,
                      buckets=[len(sg) for sg in plan],
                      compress=compress or "none",
                      fold_average=bool(odp.fold_average),
                      pipeline=True)
        synced = [None] * K
        new_res = [None] * K
        seq_no = 0
        bucket_no = sum(len(sg) for sg in plan)
        for k in reversed(range(K)):
            leaves, treedef = jax.tree_util.tree_flatten(seg_grads[k])
            out_leaves = list(leaves)
            if stateful and mode == "baseline":
                rl, rdef = jax.tree_util.tree_flatten(res[k])
                new_rl = list(rl)
            seg_res = []
            bucket_no -= len(plan[k])
            for bi, bucket in enumerate(plan[k]):
                n = bucket_no + bi
                with _telemetry_trace.span(
                        f"ddp_overlap_bucket_{n}", role="bucket",
                        segment=k, seq=seq_no, elements=bucket.n,
                        bubble=True):
                    flat = flatten([leaves[i] for i in bucket.leaf_idx])
                    if not stateful:
                        r2d = None
                    elif mode == "baseline":
                        # marshal the leaf-domain residual into the
                        # block grid (the per-step cost the overlapped
                        # mode eliminates)
                        r2d = compression.pad_to_blocks(
                            flatten([rl[i] for i in bucket.leaf_idx]),
                            odp.compress_block_size)
                    else:
                        r2d = res[k][bi]
                    out, err = odp._sync_flat(flat, r2d)
                    for i, piece in zip(
                            bucket.leaf_idx,
                            unflatten(out, [leaves[i]
                                            for i in bucket.leaf_idx])):
                        out_leaves[i] = piece
                    if stateful and mode == "baseline":
                        err_flat = err.reshape(-1)[:bucket.n]
                        for i, piece in zip(
                                bucket.leaf_idx,
                                unflatten(err_flat,
                                          [rl[i]
                                           for i in bucket.leaf_idx])):
                            new_rl[i] = piece
                    else:
                        seg_res.append(err)
                seq_no += 1
            synced[k] = jax.tree_util.tree_unflatten(treedef, out_leaves)
            if stateful and mode == "baseline":
                new_res[k] = jax.tree_util.tree_unflatten(rdef, new_rl)
            else:
                new_res[k] = tuple(seg_res)
        syncedB = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *synced[:lp])
        syncedE = synced[lp]
        if not stateful:
            return syncedB, syncedE, res
        return syncedB, syncedE, tuple(new_res)

    def _view(bl):
        return jax.tree_util.tree_map(lambda a: a[0], bl)

    def _unview(bl):
        return jax.tree_util.tree_map(lambda a: a[None], bl)

    def _apply(lb, eP, sB, sE):
        return (jax.tree_util.tree_map(lambda a, g: a - lr * g, lb, sB),
                jax.tree_util.tree_map(lambda a, g: a - lr * g, eP, sE))

    if mode == "guarded":
        def fn(bl, eP, res, gst, step_idx, tokens, labels):
            lb = _view(bl)
            gB, gE, loss = run_pipeline(lb, eP, tokens, labels,
                                        step_idx=step_idx)
            # flag from the LOCAL pre-compression grads: an int8 psum
            # can launder a NaN into finite wire garbage
            flag = nonfinite_flag((gB, gE))
            sB, sE, new_res = dp_sync(gB, gE, res)

            def commit(g, st):
                sB_, sE_, r_ = g
                lb_, e_, _ = st
                nlb, ne = _apply(lb_, e_, sB_, sE_)
                return (nlb, ne, r_)

            (new_lb, new_e, out_res), gst = resilience.guarded_update(
                (sB, sE, new_res), commit, (lb, eP, res), gst,
                axis_name=(DATA_AXIS, MODEL_AXIS, PIPE_AXIS), flag=flag)
            return _unview(new_lb), new_e, out_res, gst, loss

        in_specs = (bspecs, P(), P(), P(), P(), P(DATA_AXIS),
                    P(DATA_AXIS))
        out_specs = (bspecs, P(), P(), P(), P())
        donate_argnums = (0, 1, 2, 3) if donate else ()
        state = place_pipeline_state(mesh, blocks, edge, residual,
                                     resilience.init_guard_state())
    else:
        def fn(bl, eP, res, tokens, labels):
            lb = _view(bl)
            gB, gE, loss = run_pipeline(lb, eP, tokens, labels)
            sB, sE, new_res = dp_sync(gB, gE, res)
            new_lb, new_e = _apply(lb, eP, sB, sE)
            return _unview(new_lb), new_e, new_res, loss

        in_specs = (bspecs, P(), P(), P(DATA_AXIS), P(DATA_AXIS))
        out_specs = (bspecs, P(), P(), P())
        donate_argnums = (0, 1, 2) if donate else ()
        state = place_pipeline_state(mesh, blocks, edge, residual)

    step = jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=donate_argnums)
    return step, state
