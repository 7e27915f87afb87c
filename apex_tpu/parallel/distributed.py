"""DistributedDataParallel over a mesh axis.

Parity: reference apex/parallel/distributed.py:131-643. The reference
registers per-param grad hooks, buckets grads into dtype-segregated flat
buffers, and overlaps NCCL allreduce with backward on side streams. Options
re-expressed here: ``allreduce_always_fp32`` (150), ``gradient_average``
(152), ``gradient_predivide_factor`` (153), ``message_size`` bucketing
(accepted; XLA fuses/schedules collectives itself).

TPU design: gradients are a pytree produced by ``jax.grad`` inside a jitted
step; ``all_reduce_gradients`` runs ``lax.psum``/``pmean`` over the 'dp'
mesh axis. XLA's latency-hiding scheduler overlaps these collectives with
remaining backward compute — the stream machinery the reference builds by
hand. ``flatten``/``unflatten`` (apex_C parity, csrc/flatten_unflatten.cpp)
are provided for bucket-style IO and the C++ runtime.
"""

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu import _C
from apex_tpu.parallel import compression
from apex_tpu.parallel.compression import init_residual  # noqa: F401
from apex_tpu.telemetry import comm as _telemetry_comm
from apex_tpu.telemetry import numerics as _numerics
from apex_tpu.telemetry import trace as _telemetry_trace


def _numerics_depth(numerics):
    """Resolve the ``numerics=`` knob: True -> env/default grouping
    depth, an int -> that depth."""
    return (_numerics.default_prefix_depth() if numerics is True
            else int(numerics))


def _grad_sync_stats(local_grads, synced_grads, numerics):
    """The two stat groups the DDP ``numerics=`` knob exposes:
    ``grads/<prefix>`` from the LOCAL PRE-COMPRESSION gradients (an
    int8 psum can launder a replica's NaN into finite wire garbage, so
    only the local view sees the true non-finite source — same
    reasoning as the guard flag) and ``synced/<prefix>`` from the
    post-collective (dequantized) gradients, so int8 quantization error
    is directly observable as the dequant-vs-source rms delta per
    module prefix."""
    depth = _numerics_depth(numerics)
    stats = _numerics.tree_stats(local_grads, prefix_depth=depth,
                                 prefix="grads")
    stats.update(_numerics.tree_stats(synced_grads, prefix_depth=depth,
                                      prefix="synced"))
    return stats


def flatten(tensors):
    """Coalesce a list of SAME-dtype arrays into one flat buffer
    (parity: apex_C.flatten, csrc/flatten_unflatten.cpp).

    Contract: all leaves share one dtype, so ``unflatten(flatten(ts),
    ts)`` is bitwise round-trip-exact. ``jnp.concatenate`` would
    otherwise silently promote a mixed-dtype list to the widest dtype
    and ``unflatten``'s cast-back would lose the excursion — the
    reference kernel only ever coalesces homogeneous buffers, and the
    bucketed allreduce path guarantees it via ``plan_buckets``'s
    dtype segregation."""
    dtypes = {jnp.dtype(t.dtype) for t in tensors}
    if len(dtypes) > 1:
        raise ValueError(
            f"flatten: mixed dtypes {sorted(d.name for d in dtypes)}; "
            f"flatten/unflatten round-trip exactly only over a single "
            f"dtype — group leaves with plan_buckets first")
    return jnp.concatenate([t.reshape(-1) for t in tensors])


def unflatten(flat, tensors):
    """Split a flat buffer back into views shaped like ``tensors``
    (parity: apex_C.unflatten). Under :func:`flatten`'s single-dtype
    contract the ``astype`` is an exact no-op; it remains to cast a
    buffer that came back from a widened comm dtype (e.g. an fp32
    allreduce of bf16 grads)."""
    outs, off = [], 0
    for t in tensors:
        n = t.size
        outs.append(flat[off:off + n].reshape(t.shape).astype(t.dtype))
        off += n
    return outs


def _axis_size_total(axis_name):
    """Axis size, with tuple axes multiplied (dp x ep replica sets);
    an empty tuple means "no reduction" (size 1)."""
    if isinstance(axis_name, (tuple, list)):
        n = 1
        for a in axis_name:
            n *= lax.axis_size(a)
        return n
    return lax.axis_size(axis_name)


def all_reduce_flag(flag, axis_name="dp"):
    """Global-OR of a scalar fault/overflow flag over the replica set —
    the one collective in the resilience guard's hot path
    (``resilience.guard.guarded_update``). One f32 lane on the wire; a
    psum is an OR because flags are non-negative. Tuple axes reduce
    over every named axis; an empty tuple (or None) is the no-op
    single-replica case, mirroring ``_psum_with_policy``."""
    if axis_name is None or (isinstance(axis_name, (tuple, list))
                             and len(axis_name) == 0):
        return jnp.asarray(flag, jnp.float32)
    flag = jnp.asarray(flag, jnp.float32)
    _telemetry_comm.record_collective(
        "psum", elements=flag.size, dtype=flag.dtype, axis_name=axis_name)
    return lax.psum(flag, axis_name)


def _psum_with_policy(g, axis_name, allreduce_always_fp32, gradient_average,
                      gradient_predivide_factor, compress=None,
                      compress_block_size=compression.BLOCK_SIZE,
                      residual=None):
    """The DDP reduction policy (reference distributed.py:429-479
    ``allreduce_bucket``): optional fp32 comm dtype, predivide before /
    postdivide after the psum, cast back to the original dtype.
    ``axis_name`` may be a tuple of mesh axes (e.g.
    ``parallel_state.get_data_parallel_axes()`` = ('dp', 'ep') when expert
    parallelism borrows devices from the replica axis); an empty tuple
    skips the reduction (used as ``expert_axis_name=()`` to leave expert
    shards untouched in a pre-sync pass, e.g. before a ZeRO optimizer
    that reduce-scatters over dp itself).

    ``compress`` selects the comm payload: None (full width, honoring
    ``allreduce_always_fp32``), "bf16" (cast payload), or "int8"
    (block-quantized with error feedback — see parallel/compression.py).
    A compress mode owns the comm dtype, so it overrides
    ``allreduce_always_fp32``. With ``compress="int8"`` the return is
    ``(g, new_residual)`` and ``residual`` (fp32, same shape as ``g``,
    zeros on step 0) is added into the payload before quantization; the
    residual lives in the pre-psum, predivided gradient domain, so keep
    ``gradient_predivide_factor`` fixed across steps. ``compress="int4"``
    (dual-quantized half-byte payload) behaves exactly like int8 — same
    residual contract at half the wire width."""
    stateful = compression.needs_residual(compress)
    if isinstance(axis_name, (tuple, list)) and len(axis_name) == 0:
        return (g, residual) if stateful else g
    orig_dtype = g.dtype
    if compress is None and allreduce_always_fp32:
        g = g.astype(jnp.float32)
    if gradient_predivide_factor != 1.0:
        g = g / gradient_predivide_factor
    if compress is not None:
        shape = g.shape
        flat_r = None if residual is None else residual.reshape(-1)
        g, new_residual = compression.psum_compressed(
            g.reshape(-1), axis_name, mode=compress, residual=flat_r,
            block_size=compress_block_size)
        g = g.reshape(shape)
    else:
        _telemetry_comm.record_collective(
            "psum", elements=g.size, dtype=g.dtype, axis_name=axis_name)
        g = lax.psum(g, axis_name)
    if gradient_average:
        n = _axis_size_total(axis_name)
        g = g / (n / gradient_predivide_factor)
    g = g.astype(orig_dtype)
    return (g, new_residual.reshape(g.shape)) if stateful else g


def _leaf_path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def all_reduce_gradients(grads, axis_name="dp", *, allreduce_always_fp32=False,
                         gradient_average=True, gradient_predivide_factor=1.0,
                         expert_param_predicate=None, expert_axis_name="dp",
                         compress=None,
                         compress_block_size=compression.BLOCK_SIZE,
                         residual=None, numerics=None):
    """Allreduce a grad pytree over a mesh axis (the DDP hot path).

    With expert parallelism (mesh has an 'ep' axis), dense params replicate
    over dp x ep while expert shards replicate over dp alone: pass
    ``axis_name=parallel_state.get_data_parallel_axes()`` plus
    ``expert_param_predicate=transformer.moe.is_expert_param`` (matched
    against the '/'-joined leaf path) so each group reduces over the right
    replica set. Reducing an MoE model over 'dp' alone silently diverges
    the dense params across ep.

    ``compress=None|"bf16"|"int8"|"int4"`` selects the comm payload (see
    parallel/compression.py). With ``"int8"``/``"int4"`` the return
    becomes ``(grads, residual)`` — carry the residual pytree to the
    next call (``residual=None`` starts from zeros).

    ``numerics=True`` (or an int grouping depth) appends a per-module
    stats dict as the LAST return element — ``grads/<prefix>`` rows
    from the local pre-compression gradients, ``synced/<prefix>`` from
    the post-collective result (telemetry/numerics.py; in-graph, no
    host callback). Feed it to a
    :class:`~apex_tpu.telemetry.recorder.FlightRecorder` /
    ``resilience.guarded_update(stats=...)``.
    """
    if numerics:
        out = all_reduce_gradients(
            grads, axis_name,
            allreduce_always_fp32=allreduce_always_fp32,
            gradient_average=gradient_average,
            gradient_predivide_factor=gradient_predivide_factor,
            expert_param_predicate=expert_param_predicate,
            expert_axis_name=expert_axis_name, compress=compress,
            compress_block_size=compress_block_size, residual=residual)
        if compression.needs_residual(compress):
            synced, new_residual = out
            return synced, new_residual, _grad_sync_stats(grads, synced,
                                                          numerics)
        return out, _grad_sync_stats(grads, out, numerics)

    if compression.needs_residual(compress):
        if residual is None:
            residual = init_residual(grads)
        paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(grads)
        res_leaves = jax.tree_util.tree_leaves(residual)
        new_g, new_r = [], []
        for (path, g), r in zip(paths_leaves, res_leaves):
            ax = axis_name
            if expert_param_predicate is not None and \
                    expert_param_predicate(_leaf_path_str(path)):
                ax = expert_axis_name
            g2, r2 = _psum_with_policy(
                g, ax, allreduce_always_fp32, gradient_average,
                gradient_predivide_factor, compress=compress,
                compress_block_size=compress_block_size, residual=r)
            new_g.append(g2)
            new_r.append(r2)
        return (jax.tree_util.tree_unflatten(treedef, new_g),
                jax.tree_util.tree_unflatten(treedef, new_r))

    if expert_param_predicate is None:
        return jax.tree_util.tree_map(
            lambda g: _psum_with_policy(g, axis_name, allreduce_always_fp32,
                                        gradient_average,
                                        gradient_predivide_factor,
                                        compress=compress,
                                        compress_block_size=compress_block_size),
            grads)

    def fix(path, g):
        ax = (expert_axis_name if expert_param_predicate(_leaf_path_str(path))
              else axis_name)
        return _psum_with_policy(g, ax, allreduce_always_fp32,
                                 gradient_average, gradient_predivide_factor,
                                 compress=compress,
                                 compress_block_size=compress_block_size)

    return jax.tree_util.tree_map_with_path(fix, grads)


def plan_buckets(leaves, message_size=10000000):
    """Host-side bucket planning (reference distributed.py:287-320
    ``sync_bucket_structure``): group the flat leaf list into
    dtype-segregated, in-order buckets capped at ``message_size`` elements.
    Planning runs in the native runtime (apex_tpu_C.assign_buckets).

    Returns a list of buckets, each a list of leaf indices.
    """
    by_dtype = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype).name, []).append(i)
    buckets = []
    for idxs in by_dtype.values():
        sizes = [int(leaves[i].size) for i in idxs]
        ids = _C.assign_buckets(sizes, message_size)
        cur, cur_id = [], 0
        for i, b in zip(idxs, ids):
            if b != cur_id:
                buckets.append(cur)
                cur, cur_id = [], b
            cur.append(i)
        if cur:
            buckets.append(cur)
    return buckets


def all_reduce_gradients_bucketed(grads, axis_name="dp", *,
                                  message_size=10000000,
                                  allreduce_always_fp32=False,
                                  gradient_average=True,
                                  gradient_predivide_factor=1.0,
                                  expert_param_predicate=None,
                                  expert_axis_name="dp",
                                  compress=None,
                                  compress_block_size=compression.BLOCK_SIZE,
                                  residual=None):
    """Bucketed DDP allreduce: flatten same-dtype runs of leaves into
    ``message_size``-element buckets and psum each bucket as ONE collective
    (reference allreduce_bucket over apex_C-flattened buffers,
    distributed.py:429-479). Fewer, larger ICI collectives than the
    per-leaf path; use inside a jitted step. Expert-parallel handling as in
    :func:`all_reduce_gradients` — expert leaves bucket separately and
    reduce over ``expert_axis_name``.

    ``compress`` works per BUCKET (one quantization grid per flat
    buffer — fewer ragged tails than per-leaf); with ``"int8"`` or
    ``"int4"`` the return is ``(grads, residual)`` and the residual
    pytree stays leaf-shaped (it is flattened into the bucket alongside
    the grads), so the same residual state works for either sync
    path."""
    stateful = compression.needs_residual(compress)
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(grads)
    leaves = [l for _, l in paths_leaves]
    if stateful:
        if residual is None:
            residual = init_residual(grads)
        res_leaves = jax.tree_util.tree_leaves(residual)
    if expert_param_predicate is None:
        groups = [(axis_name, list(range(len(leaves))))]
    else:
        expert = [i for i, (p, _) in enumerate(paths_leaves)
                  if expert_param_predicate(_leaf_path_str(p))]
        expert_set = set(expert)
        dense = [i for i in range(len(leaves)) if i not in expert_set]
        groups = [(axis_name, dense), (expert_axis_name, expert)]
    out = [None] * len(leaves)
    out_res = [None] * len(leaves)
    n = 0
    for ax, idxs in groups:
        if not idxs:
            continue
        for bucket in plan_buckets([leaves[i] for i in idxs], message_size):
            bucket = [idxs[j] for j in bucket]
            # named_scope = the TPU analog of the reference's NVTX ranges
            # around allreduce_bucket (distributed.py:429, prof flag)
            with jax.named_scope(f"ddp_allreduce_bucket_{n}"):
                flat = flatten([leaves[i] for i in bucket])
                if stateful:
                    flat_r = flatten([res_leaves[i] for i in bucket])
                    flat, flat_r = _psum_with_policy(
                        flat, ax, allreduce_always_fp32, gradient_average,
                        gradient_predivide_factor, compress=compress,
                        compress_block_size=compress_block_size,
                        residual=flat_r)
                    for i, piece in zip(
                            bucket,
                            unflatten(flat_r,
                                      [res_leaves[i] for i in bucket])):
                        out_res[i] = piece
                else:
                    flat = _psum_with_policy(flat, ax, allreduce_always_fp32,
                                             gradient_average,
                                             gradient_predivide_factor,
                                             compress=compress,
                                             compress_block_size=
                                             compress_block_size)
                for i, piece in zip(
                        bucket, unflatten(flat, [leaves[i] for i in bucket])):
                    out[i] = piece
            n += 1
    if stateful:
        return (jax.tree_util.tree_unflatten(treedef, out),
                jax.tree_util.tree_unflatten(treedef, out_res))
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_params(params, axis_name="dp"):
    """Make params bitwise-identical across the axis (or tuple of axes) by
    broadcasting rank 0 (parity: DDP ctor broadcast, reference
    distributed.py:257)."""
    axes = (axis_name,) if not isinstance(axis_name, (tuple, list)) \
        else tuple(axis_name)

    def bcast(p):
        rank = jnp.zeros((), jnp.int32)
        for a in axes:
            rank = rank * lax.axis_size(a) + lax.axis_index(a)
        masked = jnp.where(rank == 0, p, jnp.zeros_like(p))
        return lax.psum(masked, axes)

    return jax.tree_util.tree_map(bcast, params)


class DistributedDataParallel:
    """Wrap a loss/grad computation with dp-axis gradient sync.

    Two usage modes:

    1. Wrap a grad function to sync its output (hook-parity)::

         ddp = DistributedDataParallel(axis_name="dp")
         grads = ddp.sync(grads)          # inside shard_map/pmap

    2. Wrap an apply fn so ``jax.grad`` of the wrapped fn yields synced
       grads automatically (closest to the reference's module wrapper —
       gradients of all params are averaged during backward)::

         model_fn = ddp(model_fn)         # psum-of-grads via custom_vjp
    """

    def __init__(self, module: Optional[Callable] = None, message_size: int = 10000000,
                 delay_allreduce: bool = False, shared_param: Any = None,
                 allreduce_trigger_params: Any = None,
                 retain_allreduce_buffers: bool = False,
                 allreduce_always_fp32: bool = False,
                 num_allreduce_streams: int = 1,
                 allreduce_communicators: Any = None,
                 gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0,
                 gradient_average_split_factor: Any = None,
                 prof: bool = False,
                 axis_name: str = "dp",
                 expert_param_predicate: Optional[Callable] = None,
                 expert_axis_name: str = "dp",
                 compress: Optional[str] = None,
                 compress_block_size: int = compression.BLOCK_SIZE,
                 numerics=None):
        self.module = module
        self.axis_name = axis_name
        self.message_size = message_size
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.delay_allreduce = delay_allreduce
        self.needs_refresh = True
        # Expert parallelism: dense params sync over axis_name (pass
        # parallel_state.get_data_parallel_axes() = ('dp','ep')), expert
        # shards over expert_axis_name. Supported in .sync(); the
        # module-wrapping mode syncs every param uniformly.
        self.expert_param_predicate = expert_param_predicate
        self.expert_axis_name = expert_axis_name
        # Compressed gradient collectives (parallel/compression.py):
        # None | "bf16" | "int8" | "int4". The int modes make .sync
        # stateful — it returns (grads, residual) and the caller
        # threads the residual pytree through the jitted step (donate
        # it like optimizer state).
        self.compress = compress
        self.compress_block_size = compress_block_size
        # In-graph numerics (telemetry/numerics.py): True / an int
        # grouping depth makes .sync also return a per-module stats
        # dict — pre-compression local grads + post-sync (dequantized)
        # grads, so int8 quantization error shows as a rms delta.
        self.numerics = numerics

    def init_residual(self, grads_or_params):
        """Zero error-feedback state for ``compress="int8"``/``"int4"``
        (a pytree shaped like the grads; donate it through the train
        step)."""
        return init_residual(grads_or_params)

    def memory_report(self, jitted_step, *args, **kwargs):
        """HBM accounting for the jitted step this DDP instance syncs
        inside (``telemetry.memory.step_memory`` — XLA's own
        ``memory_analysis()`` -> argument/output/temp bytes, peak, and
        the ``memory/hbm_headroom`` gauge), tagged with the sync
        config: the int8 payload trades wire bytes for quantization
        temps, and this is where that trade shows up as bytes. Host-
        side AOT only — never dispatches the step. Returns the report
        dict (None when the backend offers no analysis)."""
        from apex_tpu.telemetry import memory as _memory

        report = _memory.step_memory(jitted_step, *args, **kwargs)
        if report is not None:
            report = dict(report, compress=self.compress or "none",
                          axis_name=str(self.axis_name))
        return report

    def sync(self, grads, residual=None):
        """Bucketed grad allreduce honoring ``message_size`` (reference
        create_hooks bucketing); pass ``message_size=None`` at construction
        for the per-leaf path.

        With ``compress="int8"`` or ``"int4"`` returns
        ``(grads, residual)``; pass the previous step's residual in
        (``None`` starts from zeros — step 0 of error feedback). With ``numerics=`` set at construction, a
        per-module stats dict (``grads/*`` pre-compression local,
        ``synced/*`` post-collective — see ``_grad_sync_stats``) is
        appended as the last return element, for either sync path."""
        kw = {}
        if self.compress is not None:
            kw = dict(compress=self.compress,
                      compress_block_size=self.compress_block_size)
            if compression.needs_residual(self.compress):
                kw["residual"] = residual
        # host-side span (trace-time when called inside jit); the comm
        # byte counters accumulate underneath via _psum_with_policy
        with _telemetry_trace.span("ddp/sync",
                                   compress=self.compress or "none",
                                   bucketed=bool(self.message_size),
                                   numerics=bool(self.numerics)):
            if self.message_size:
                out = all_reduce_gradients_bucketed(
                    grads, self.axis_name, message_size=self.message_size,
                    allreduce_always_fp32=self.allreduce_always_fp32,
                    gradient_average=self.gradient_average,
                    gradient_predivide_factor=self.gradient_predivide_factor,
                    expert_param_predicate=self.expert_param_predicate,
                    expert_axis_name=self.expert_axis_name, **kw)
            else:
                out = all_reduce_gradients(
                    grads, self.axis_name,
                    allreduce_always_fp32=self.allreduce_always_fp32,
                    gradient_average=self.gradient_average,
                    gradient_predivide_factor=self.gradient_predivide_factor,
                    expert_param_predicate=self.expert_param_predicate,
                    expert_axis_name=self.expert_axis_name, **kw)
            if not self.numerics:
                return out
            if compression.needs_residual(self.compress):
                synced, new_residual = out
                return synced, new_residual, _grad_sync_stats(
                    grads, synced, self.numerics)
            return out, _grad_sync_stats(grads, out, self.numerics)

    def __call__(self, fn=None, *args, **kwargs):
        """If constructed around a module/apply fn, call it; DDP on TPU is
        transparent in forward (sync happens on gradients).

        Gradient-sync note: under JAX's shard_map, cotangents of
        *replicated* params are summed across the axis automatically at the
        shard_map boundary (the vma-typed transpose) — the allreduce the
        reference implements with hooks+NCCL. The wrapper therefore only
        applies the averaging / predivide policy by scaling the backward
        cotangent; ``sync``/``all_reduce_gradients`` remain for grads of
        per-device (varying) params.
        """
        target = fn if callable(fn) and self.module is None else self.module
        if target is None:
            raise TypeError("DistributedDataParallel needs a callable module")
        if self.expert_param_predicate is not None:
            raise NotImplementedError(
                "expert_param_predicate requires per-param axis selection; "
                "use DistributedDataParallel(...).sync(grads) instead of "
                "the module-wrapping mode")
        if fn is not None and target is self.module:
            args = (fn,) + args

        axis_name = self.axis_name
        gradient_average = self.gradient_average

        @functools.wraps(target)
        def wrapped(*a, **kw):
            inner = functools.partial(target, **kw) if kw else target
            return _ddp_identity(inner, axis_name, gradient_average, *a)

        if callable(fn) and self.module is None:
            return wrapped
        return wrapped(*args, **kwargs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ddp_identity(fn, axis_name, gradient_average, *args):
    return fn(*args)


def _ddp_fwd(fn, axis_name, gradient_average, *args):
    out, vjp = jax.vjp(fn, *args)
    return out, vjp


def _ddp_bwd(fn, axis_name, gradient_average, vjp, g):
    # Two shard_map autodiff regimes exist (JAX >= 0.8):
    # - checked (vma typing on): cotangents of replicated params are
    #   auto-psummed at the shard_map boundary, so DDP only applies the
    #   averaging policy by scaling the cotangent.
    # - unchecked (check_vma=False): cotangents stay per-device, so DDP
    #   performs the allreduce itself.
    # Discriminate via the vma type of axis_index (varying iff checking
    # on). shard_map sets check_vma uniformly, but probe every axis of a
    # tuple axis_name and insist they agree rather than trusting the
    # first one.
    axes = (tuple(axis_name) if isinstance(axis_name, (tuple, list))
            else (axis_name,))
    states = {
        ax in getattr(jax.typeof(lax.axis_index(ax)), "vma", frozenset())
        for ax in axes}
    if len(states) != 1:
        raise ValueError(
            f"mixed vma checking states across mesh axes {axes}; DDP "
            f"cannot tell whether the shard_map boundary will psum "
            f"cotangents")
    checked = states.pop()
    if checked:
        if gradient_average:
            n = _axis_size_total(axis_name)
            g = jax.tree_util.tree_map(lambda c: c / n, g)
        return vjp(g)
    grads = vjp(g)
    return tuple(
        all_reduce_gradients(gr, axis_name, gradient_average=gradient_average)
        for gr in grads)


_ddp_identity.defvjp(_ddp_fwd, _ddp_bwd)


class Reducer:
    """Manual-trigger gradient reducer (parity: reference
    distributed.py:91-128 — user calls ``.reduce()`` when ready)."""

    def __init__(self, module_or_grads_list=None, axis_name="dp"):
        self.axis_name = axis_name

    def reduce(self, grads, **kwargs):
        return all_reduce_gradients(grads, self.axis_name, **kwargs)
