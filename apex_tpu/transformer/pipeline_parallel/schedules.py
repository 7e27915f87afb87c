"""Compat shim: the pipeline schedules moved to
``apex_tpu.parallel.pipeline`` (the 3-D mesh subsystem), which hosts
the reference-parity schedule machinery unchanged — this module
re-exports it so the ``apex.transformer.pipeline_parallel.schedules``
API surface keeps resolving here (one DeprecationWarning per process,
shared with the ``p2p_communication`` shim)."""

from apex_tpu.parallel.pipeline import (  # noqa: F401
    PIPELINE_PARALLEL_AXIS,
    _payload_spec,
    _pipelined_fwd_bwd,
    _warn_moved,
    forward_backward_no_pipelining,
    forward_backward_pipelining_with_interleaving,
    forward_backward_pipelining_with_split,
    forward_backward_pipelining_without_interleaving,
    get_forward_backward_func,
    listify_model,
    make_encoder_decoder_step,
    pipeline_schedule_plan,
)

_warn_moved("apex_tpu.transformer.pipeline_parallel.schedules")
