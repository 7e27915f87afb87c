"""apex_tpu.transformer — Megatron-style model parallelism over a device mesh.

Parity: reference apex/transformer/__init__.py (parallel_state,
tensor_parallel, pipeline_parallel, amp, functional, layers, enums,
microbatches, testing); beyond it: context_parallel, moe, ssm.
"""

from apex_tpu.transformer import parallel_state  # noqa: F401
from apex_tpu.transformer import tensor_parallel  # noqa: F401
from apex_tpu.transformer import pipeline_parallel  # noqa: F401
from apex_tpu.transformer import functional  # noqa: F401
from apex_tpu.transformer import layers  # noqa: F401
from apex_tpu.transformer.enums import AttnMaskType, AttnType, LayerType, ModelType  # noqa: F401
from apex_tpu.transformer.microbatches import build_num_microbatches_calculator  # noqa: F401
from apex_tpu.transformer import amp  # noqa: F401
from apex_tpu.transformer import context_parallel  # noqa: F401
from apex_tpu.transformer import moe  # noqa: F401
from apex_tpu.transformer import ssm  # noqa: F401
