"""apex_tpu.kernels — the Pallas fused-kernel layer (csrc parity).

One registry (:mod:`apex_tpu.kernels.registry`) holds every hand-written
kernel's gate, and one rule there (``PallasGate.path``) says which path a
call takes: the compiled kernel on a TPU, the Pallas interpreter where a
test forced it, the jnp oracle otherwise, and everywhere under
``APEX_TPU_KERNELS=0``, the one switch. The kernel families behind their
existing Python entry points:

- :mod:`apex_tpu.kernels.softmax` — scaled-masked / upper-triangular
  softmax fwd + fused bwd (entry:
  ``apex_tpu.transformer.functional.fused_softmax``)
- :mod:`apex_tpu.kernels.optim` — fused multi-tensor Adam/LAMB updates
  over the bucket-domain ZeRO state (entry: the
  ``apex_tpu.contrib.optimizers`` ZeRO classes)
- :mod:`apex_tpu.kernels.quant4` — int4 dual-quantization pack/unpack
  (entry: ``apex_tpu.parallel.compression`` ``compress="int4"``; the
  int8 ``quant`` pair lives in ``compression`` itself)
- :mod:`apex_tpu.kernels.fused_cc` — matmul + collective, the verify
  window's flash attention, int4 quantize + pack around a collective
- :mod:`apex_tpu.kernels.topk_select` — the sparse-attention indexer's
  top-k selection with its rows of scores held in VMEM (entry:
  ``apex_tpu.models.transformer_lm.topk_selection``)
- :mod:`apex_tpu.kernels.grouped_matmul` — the experts' grouped matmul
  over the row tiles that hold assignments, with its two backward
  products (entry: ``apex_tpu.transformer.moe.layer.ExpertMLP``'s ragged
  layout)
- ``apex_tpu.contrib.fmha`` (``flash_attention``),
  ``apex_tpu.contrib.gqa_decode`` and ``apex_tpu.contrib.mla_decode``
  register their gates here too.

See docs/kernels.md for the rule, parity bounds, and wire formats.
"""

from apex_tpu.kernels import (  # noqa: F401
    grouped_matmul,
    optim,
    quant4,
    softmax,
    topk_select,
)
from apex_tpu.kernels.registry import (  # noqa: F401
    KernelRegistry,
    PallasGate,
    choose_block,
    get_kernel_registry,
    kernel_gate,
)
