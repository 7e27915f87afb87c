"""Megatron-style tensor-parallel layers.

Parity: reference apex/transformer/tensor_parallel/layers.py:174-813 —
``VocabParallelEmbedding`` (masked local lookup + allreduce),
``ColumnParallelLinear`` (460), ``RowParallelLinear`` (645),
``LinearWithGradAccumulationAndAsyncCommunication`` (279-438: async grad
allreduce, sequence-parallel all-gather fwd + reduce-scatter bwd, fused
wgrad accumulation), and the param partition-attribute helpers (70-107).

TPU design: layers are flax modules holding the *local shard* of each
weight; they run inside ``shard_map`` over the 'tp' mesh axis. The
forward/backward collective pairing is expressed through the custom-vjp
region ops in :mod:`mappings`; XLA's async collectives + latency-hiding
scheduler provide the comm/compute overlap the reference hand-schedules.
The fused wgrad-accum GEMM (fused_weight_gradient_mlp_cuda,
layers.py:415-429) is unnecessary: XLA accumulates the weight-grad einsum
directly into the gradient buffer with buffer donation.

Partitioned-vs-duplicated init parity (reference random.py:204-236): weight
shards are initialized from a per-rank key folded with the tp rank, so
TP=n layers statistically match a TP=1 layer sliced n ways.
"""

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer.parallel_state import (
    TENSOR_PARALLEL_AXIS,
    get_tensor_model_parallel_world_size,
)
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    scatter_to_tensor_model_parallel_region,
)
from apex_tpu.transformer.tensor_parallel.utils import (  # noqa: F401
    # VocabUtility re-exported for reference-apex layers API parity
    VocabUtility,
    divide,
)

_MODEL_PARALLEL_ATTRIBUTE_DEFAULTS = {
    "tensor_model_parallel": False,
    "partition_dim": -1,
    "partition_stride": 1,
}


# -- param attribute helpers (reference layers.py:70-107) -------------------
# JAX arrays are immutable values without attributes; partition metadata
# lives in a side dict pytree produced by ``Module.param_attributes``.

def set_tensor_model_parallel_attributes(attrs: dict, is_parallel: bool,
                                         dim: int, stride: int) -> dict:
    attrs.update({"tensor_model_parallel": is_parallel, "partition_dim": dim,
                  "partition_stride": stride})
    return attrs


def set_defaults_if_not_set_tensor_model_parallel_attributes(attrs: dict) -> dict:
    for k, v in _MODEL_PARALLEL_ATTRIBUTE_DEFAULTS.items():
        attrs.setdefault(k, v)
    return attrs


def copy_tensor_model_parallel_attributes(dst: dict, src: dict) -> dict:
    for k in _MODEL_PARALLEL_ATTRIBUTE_DEFAULTS:
        if k in src:
            dst[k] = src[k]
    return dst


def _tp_rank_key(key):
    """Fold the tp rank into an RNG key for partitioned init (the TPU analog
    of CudaRNGStatesTracker's tp-offset seed, reference random.py:204)."""
    try:
        rank = lax.axis_index(TENSOR_PARALLEL_AXIS)
    except Exception:
        rank = 0
    return jax.random.fold_in(key, rank)


def _partitioned_init(init_fn):
    def wrapped(key, shape, dtype):
        return init_fn(_tp_rank_key(key), shape, dtype)
    return wrapped


def linear_with_grad_accumulation_and_async_allreduce(
        input, weight, bias=None, gradient_accumulation_fusion=False,
        async_grad_allreduce=True, sequence_parallel_enabled=False,
        axis_name=TENSOR_PARALLEL_AXIS):
    """Functional core of ColumnParallelLinear
    (reference layers.py:279-438).

    - sequence_parallel_enabled: all-gather the seq-sharded input on entry
      (fwd) / reduce-scatter the input grad on exit (bwd).
    - else async_grad_allreduce: identity fwd / allreduce of input grad bwd.
    The flags select collectives; accumulation fusion is XLA's job.

    ``weight`` (and with it ``bias``) may be a tuple of column groups of
    one weight: the input is gathered once, each group gets a matmul of
    its own and the outputs come back as a tuple.
    """
    if sequence_parallel_enabled:
        total_input = gather_from_sequence_parallel_region(input, True, axis_name)
    elif async_grad_allreduce:
        total_input = copy_to_tensor_model_parallel_region(input, axis_name)
    else:
        total_input = input

    def linear(weight, bias):
        out = jnp.matmul(total_input, weight,
                         preferred_element_type=jnp.float32)
        out = out.astype(input.dtype)
        if bias is not None:
            out = out + bias
        return out

    if isinstance(weight, tuple):
        return tuple(map(linear, weight,
                         bias if bias is not None else len(weight) * (None,)))
    return linear(weight, bias)


class ColumnParallelLinear(nn.Module):
    """Linear with output-dim partitioning: Y = XA + b, A = [A_1 .. A_p]
    (reference layers.py:460). Holds the local shard A_i of shape
    [input_size, output_size / tp]."""

    input_size: int
    output_size: int
    bias: bool = True
    gather_output: bool = True
    init_method: Callable = nn.initializers.lecun_normal()
    stride: int = 1
    keep_master_weight_for_test: bool = False
    skip_bias_add: bool = False
    no_async_tensor_model_parallel_allreduce: bool = False
    params_dtype: Any = jnp.float32
    use_cpu_initialization: bool = False
    gradient_accumulation_fusion: bool = False
    sequence_parallel_enabled: bool = False
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    def __call__(self, input_, column_groups=None):
        """``column_groups``: a function that cuts an array's last axis,
        the local shard's columns, into a tuple of groups. The call then
        returns one output a group, each written by a matmul of its own
        in its own array, where slicing them out of the one output would
        copy each (fused projections whose parts go to a kernel: q, k and
        v). The stored weight is the same; needs ``gather_output=False``
        and the bias added here."""
        world = get_tensor_model_parallel_world_size()
        out_per_partition = divide(self.output_size, world)
        weight = self.param(
            "weight", _partitioned_init(self.init_method),
            (self.input_size, out_per_partition), self.params_dtype)
        b = (self.param("bias", nn.initializers.zeros, (out_per_partition,),
                        self.params_dtype) if self.bias else None)
        if column_groups is not None:
            if self.gather_output or self.skip_bias_add:
                raise ValueError("column_groups needs gather_output=False "
                                 "and skip_bias_add=False")
            weight = column_groups(weight)
            b = None if b is None else column_groups(b)
        bias_for_matmul = None if self.skip_bias_add else b
        out_parallel = linear_with_grad_accumulation_and_async_allreduce(
            input_, weight, bias_for_matmul,
            gradient_accumulation_fusion=self.gradient_accumulation_fusion,
            async_grad_allreduce=not self.no_async_tensor_model_parallel_allreduce,
            sequence_parallel_enabled=self.sequence_parallel_enabled,
            axis_name=self.axis_name)
        if self.gather_output:
            assert not self.sequence_parallel_enabled
            output = gather_from_tensor_model_parallel_region(
                out_parallel, self.axis_name)
        else:
            output = out_parallel
        if self.skip_bias_add:
            return output, b
        return output


class RowParallelLinear(nn.Module):
    """Linear with input-dim partitioning: Y = XA, A = [A_1; ..; A_p]
    (reference layers.py:645). Holds the local shard of shape
    [input_size / tp, output_size]; output is allreduced (or
    reduce-scattered under sequence parallelism)."""

    input_size: int
    output_size: int
    bias: bool = True
    input_is_parallel: bool = False
    init_method: Callable = nn.initializers.lecun_normal()
    stride: int = 1
    keep_master_weight_for_test: bool = False
    skip_bias_add: bool = False
    params_dtype: Any = jnp.float32
    use_cpu_initialization: bool = False
    gradient_accumulation_fusion: bool = False
    sequence_parallel_enabled: bool = False
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    def __call__(self, input_):
        world = get_tensor_model_parallel_world_size()
        in_per_partition = divide(self.input_size, world)
        weight = self.param(
            "weight", _partitioned_init(self.init_method),
            (in_per_partition, self.output_size), self.params_dtype)
        b = (self.param("bias", nn.initializers.zeros, (self.output_size,),
                        self.params_dtype) if self.bias else None)
        if self.input_is_parallel:
            input_parallel = input_
        else:
            assert not self.sequence_parallel_enabled
            input_parallel = scatter_to_tensor_model_parallel_region(
                input_, self.axis_name)
        out_parallel = jnp.matmul(input_parallel, weight,
                                  preferred_element_type=jnp.float32)
        out_parallel = out_parallel.astype(input_.dtype)
        if self.sequence_parallel_enabled:
            output_ = reduce_scatter_to_sequence_parallel_region(
                out_parallel, self.axis_name)
        else:
            output_ = reduce_from_tensor_model_parallel_region(
                out_parallel, self.axis_name)
        if self.skip_bias_add:
            return output_, b
        if b is not None:
            output_ = output_ + b
        return output_


class VocabParallelEmbedding(nn.Module):
    """Embedding with vocab-dim partitioning (reference layers.py:174-276):
    masked local lookup followed by an allreduce over the tp axis.
    ``attend`` projects hidden states back onto the vocab shard — the
    tied LM head (reference parallel_lm_logits uses the embedding table).
    """

    num_embeddings: int
    embedding_dim: int
    init_method: Callable = nn.initializers.normal(stddev=0.02)
    params_dtype: Any = jnp.float32
    use_cpu_initialization: bool = False
    axis_name: str = TENSOR_PARALLEL_AXIS

    def setup(self):
        world = get_tensor_model_parallel_world_size()
        per_partition = divide(self.num_embeddings, world)
        self.weight = self.param(
            "weight", _partitioned_init(self.init_method),
            (per_partition, self.embedding_dim), self.params_dtype)

    def attend(self, h):
        """[..., hidden] @ table.T -> vocab-parallel logits
        [..., vocab/tp] (fp32 accumulation)."""
        return jnp.einsum("...h,vh->...v", h, self.weight.astype(h.dtype),
                          preferred_element_type=jnp.float32)

    def __call__(self, input_):
        world = get_tensor_model_parallel_world_size()
        per_partition = divide(self.num_embeddings, world)
        weight = self.weight
        if world > 1:
            try:
                rank = lax.axis_index(self.axis_name)
            except Exception:
                rank = 0
            start = rank * per_partition
            masked = input_ - start
            in_range = (input_ >= start) & (input_ < start + per_partition)
            masked = jnp.where(in_range, masked, 0)
            out = weight[masked]
            out = jnp.where(in_range[..., None], out, 0.0)
            out = reduce_from_tensor_model_parallel_region(out, self.axis_name)
        else:
            out = weight[input_]
        return out


# -- sequence-parallel gradient sync ----------------------------------------
# The reference tags tp-replicated params with ``sequence_parallel_enabled``
# and allreduces their grads over the TP group (layers.py sequence_parallel
# attr + transformer/layers/layer_norm.py:26-99). JAX param pytrees carry no
# attributes, so the tagging is a path predicate: True for params whose
# forward consumed only the local sequence shard (layernorms, position
# embeddings, row-parallel biases added after the reduce-scatter) and whose
# grads are therefore partial sums over the tp axis.

def allreduce_sequence_parallel_grads(grads, is_sequence_parallel_param,
                                      axis_name=TENSOR_PARALLEL_AXIS):
    """psum the grads of seq-partial params over the tp axis.

    ``is_sequence_parallel_param(path: str) -> bool`` receives the
    '/'-joined param path. Call inside shard_map when
    ``sequence_parallel_enabled`` models train with tp > 1.
    """

    def fix(path, g):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if is_sequence_parallel_param(name):
            return lax.psum(g, axis_name)
        return g

    return jax.tree_util.tree_map_with_path(fix, grads)
