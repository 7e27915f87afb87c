"""apex_tpu — a TPU-native framework with the capabilities of NVIDIA Apex.

This is a ground-up JAX/XLA/Pallas re-design of the capabilities of the
reference (juncongmoo/apex, mounted at /root/reference):

- ``apex_tpu.amp``            — mixed precision (bf16 autocast, loss scaling),
  parity with ``apex/amp`` (reference apex/amp/frontend.py:197).
- ``apex_tpu.optimizers``     — fused optimizers (Adam/LAMB/SGD/NovoGrad/
  Adagrad/MixedPrecisionLamb), parity with ``apex/optimizers``.
- ``apex_tpu.multi_tensor_apply`` — the multi-tensor-apply engine
  (reference apex/multi_tensor_apply/multi_tensor_apply.py:24-30).
- ``apex_tpu.normalization``  — FusedLayerNorm / FusedRMSNorm backed by
  Pallas TPU kernels (reference apex/normalization/fused_layer_norm.py).
- ``apex_tpu.parallel``       — data-parallel runtime: DistributedDataParallel
  semantics over an XLA ``psum``, SyncBatchNorm, LARC
  (reference apex/parallel/).
- ``apex_tpu.transformer``    — Megatron-style tensor/pipeline/sequence
  parallelism over a ``jax.sharding.Mesh`` (reference apex/transformer/).
- ``apex_tpu.contrib``        — fused extras: xentropy, clip_grad, focal loss,
  flash attention, fused dense/MLP (reference apex/contrib/).
- ``apex_tpu.models``         — ResNet, GPT, BERT, DCGAN model families used
  by the examples and benchmarks (reference examples/, apex/transformer/testing/).
- ``apex_tpu.telemetry``      — unified tracing/metrics/XLA cost accounting
  (spans, collective byte counters, MFU from ``cost_analysis()``); no
  reference counterpart — see docs/observability.md.

Design notes (TPU-first, not a port):
- CUDA multi-tensor kernels -> one jitted update over the parameter pytree;
  XLA fuses the elementwise work. Hot spots use Pallas kernels.
- NCCL process groups      -> mesh axis names + lax collectives over ICI/DCN.
- CUDA streams / hooks     -> XLA latency-hiding scheduler inside one jit.
- fp16 + loss scaling      -> bf16 by default (scaler kept for API parity and
  for explicit fp16 use).
"""

import sys as _sys
import time as _time

# the package's own import is a span of set-up (telemetry.compile_watch
# record_import, at the bottom); whoever imports jax first pays for it
_IMPORT_START = _time.perf_counter()
_JAX_PRELOADED = "jax" in _sys.modules

import logging as _pylogging

__version__ = "0.1.0"

from apex_tpu._logging import RankInfoFormatter, deprecated_warning  # noqa: F401

# Light-weight subpackages are imported eagerly so `import apex_tpu` gives the
# same surface as `import apex` (reference apex/__init__.py imports amp etc.
# lazily behind try/except; we are pure-Python+JAX so imports are cheap).
from apex_tpu import telemetry  # noqa: F401
from apex_tpu import analysis  # noqa: F401
from apex_tpu import multi_tensor_apply  # noqa: F401
from apex_tpu import optimizers  # noqa: F401
from apex_tpu import normalization  # noqa: F401
from apex_tpu import amp  # noqa: F401
from apex_tpu import parallel  # noqa: F401
from apex_tpu import fp16_utils  # noqa: F401
from apex_tpu import resilience  # noqa: F401
from apex_tpu import transformer  # noqa: F401

_pylogging.getLogger(__name__).addHandler(_pylogging.NullHandler())

telemetry.compile_watch.record_import(
    __name__, _IMPORT_START, _time.perf_counter(), _JAX_PRELOADED)
