"""The kernel registry: the one rule that decides, for every hand-written
Pallas kernel, which of three paths a call takes, and counts the call.

:meth:`PallasGate.path` is that rule. A call runs

- the **oracle** (the kernel's jnp formulation, the reference it is
  tested against, not a degraded path) when ``APEX_TPU_KERNELS=0``, when
  the call's shapes do not fit the kernel (``fits``: the kernel module's
  own predicate), or when the backend is not a TPU;
- the kernel in the Pallas **interpreter** when the first two allow it
  and the gate was told :meth:`~PallasGate.force_interpret` (CPU tests:
  shows the dataflow is right, not that it compiles);
- the compiled **pallas** kernel otherwise, on a TPU.

``APEX_TPU_KERNELS=0`` is the one switch: it reproduces the plain-XLA
lowering everywhere and beats a forced interpreter. Nothing else is read
from the environment; a test that wants one kernel's oracle beside its
kernel uses ``force_interpret(False, [name])``.

The same call records the dispatch (trace time): the counters
``kernels/dispatch``, ``kernels/<name>/<path>`` and
``kernels/dispatch/<name>_<path>``, a ``kernel`` JSONL event and, for the
numbers a kernel module adds about the call's shape, the gauges
``kernels/<name>/<field>``, only when the process-wide metrics registry
is enabled.
"""

import os

import jax

_SWITCH = "APEX_TPU_KERNELS"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


class PallasGate:
    """One registered kernel: its name and whether tests have put it in
    the interpreter (``interpret``, which its ``pallas_call``s read)."""

    def __init__(self, name: str):
        self.name = name
        self.interpret = False

    def force_interpret(self, on: bool):
        self.interpret = bool(on)

    def path(self, fits: bool = True, *, record: bool = True,
             **fields) -> str:
        """``"pallas"``, ``"interpret"`` or ``"oracle"`` for one call
        (the rule in the module docstring), recorded as this kernel's
        dispatch with the kernel module's own ``fields``. A predicate
        that only asks on a caller's behalf, before the entry that will
        count the call, passes ``record=False``."""
        if os.environ.get(_SWITCH) == "0" or not fits:
            path = "oracle"
        elif self.interpret:
            path = "interpret"
        else:
            path = "pallas" if _on_tpu() else "oracle"
        if record:
            _REGISTRY.dispatch(self.name, path, **fields)
        return path


def choose_block(cache_len: int, preferred: int):
    """Largest tile size that divides the cache buffer: the preferred
    size, then the 256/128 rungs (a 1280-long buffer should stream in
    256-tiles, not silently lose the kernel), then the whole buffer for
    short caches. None -> no dividing block; caller falls back."""
    if cache_len <= preferred:
        return cache_len
    for b in (preferred, 256, 128):
        if b <= cache_len and cache_len % b == 0:
            return b
    return None


def lane_block_ok(gate: PallasGate, batch: int, lanes: int) -> bool:
    """Can a ``[T, batch*lanes]`` cache view stream in ``(block_t,
    lanes)`` tiles? The TPU lowering wants the lane block to tile 128
    or span the array; the interpreter has no tiling rule."""
    return gate.interpret or batch == 1 or lanes % 128 == 0


class KernelRegistry:
    """Process-wide table of registered kernels and their gates."""

    def __init__(self):
        self._gates = {}

    def register(self, name: str) -> PallasGate:
        """Idempotent: the first registration makes the gate; later
        calls return it (so module reloads don't reset interpret
        state)."""
        return self._gates.setdefault(name, PallasGate(name))

    def gate(self, name: str) -> PallasGate:
        return self._gates[name]

    def names(self):
        return sorted(self._gates)

    def force_interpret(self, on: bool, names=None):
        """Run kernels in interpreter mode regardless of backend (CPU
        tests). ``names=None`` flips every registered gate."""
        for n in (self._gates if names is None else names):
            self._gates[n].force_interpret(on)

    def dispatch(self, name: str, path: str, **fields):
        """Record one kernel dispatch (trace-time; what
        :meth:`PallasGate.path` calls): ``path`` is ``"pallas"``,
        ``"interpret"`` or ``"oracle"``; ``fields`` are numbers a
        kernel module says about the call's shape (the flash entries: the
        tiles a head runs by class), kept on the event and, the last
        call's, as gauges ``kernels/<name>/<field>``. No-op when
        telemetry is disabled — zero overhead off."""
        from apex_tpu.telemetry.registry import get_registry

        reg = get_registry()
        if not reg.enabled:
            return
        reg.counter("kernels/dispatch").inc()
        reg.counter(f"kernels/{name}/{path}").inc()
        # flat per-(kernel, path) counter: lands in every summary's
        # ``counters`` dict, so a run's JSON proves which path actually
        # ran — a silent oracle fallback shows up as
        # ``kernels/dispatch/<name>_oracle`` instead of vanishing
        reg.counter(f"kernels/dispatch/{name}_{path}").inc()
        reg.event("kernel", "dispatch", kernel=name, path=path, **fields)
        for field, value in fields.items():
            reg.gauge(f"kernels/{name}/{field}").set(value)


_REGISTRY = KernelRegistry()


def get_kernel_registry() -> KernelRegistry:
    return _REGISTRY


def kernel_gate(name: str) -> PallasGate:
    """Register-or-fetch the named kernel's gate on the process-wide
    registry — the one-liner kernel modules use at import time."""
    return _REGISTRY.register(name)
