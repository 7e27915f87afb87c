"""Compat shim: the shared Pallas gating moved to
:mod:`apex_tpu.kernels.registry` (the kernel registry — one code path
deciding pallas-vs-oracle-vs-interpret for every kernel, master switch
``APEX_TPU_KERNELS`` with per-kernel overrides). Import ``PallasGate``
and ``choose_block`` from there; this module re-exports them for the
existing decode-kernel call sites."""

from apex_tpu.kernels.registry import (  # noqa: F401
    PallasGate,
    choose_block,
    lane_block_ok,
)
