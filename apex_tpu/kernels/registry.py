"""The kernel registry: ONE code path deciding pallas-vs-oracle-vs-
interpret for every hand-written kernel in the tree.

Before this module each kernel family carried its own gating — the
decode kernels shared ``contrib._pallas_gate``, compression had a lazy
copy of it behind ``APEX_TPU_COMPRESS_PALLAS``, layer norm had a third
formulation behind ``APEX_TPU_PALLAS_LN`` — and a fix to backend
detection (or a fleet-wide "turn the kernels off" switch) had no single
place to land. Now every kernel registers here and the decision ladder
is uniform:

1. ``APEX_TPU_DISABLE_PALLAS=1`` — global kill, every kernel off.
2. The kernel's own env var (``APEX_TPU_KERNEL_<NAME>``): ``0`` off,
   anything else an explicit opt-in.
3. The kernel's documented legacy alias (e.g. ``APEX_TPU_PALLAS_LN``
   for the norm kernels, ``APEX_TPU_COMPRESS_PALLAS`` — deprecated,
   one warning per process — for the quantize kernels), same ``0``/on
   semantics.
4. The master switch ``APEX_TPU_KERNELS``: ``0`` turns every
   non-overridden kernel off, ``1`` explicitly opts every kernel in
   (including the default-off ones), unset leaves each kernel at its
   registered default.
5. Runnability: interpreter mode (tests — ``force_interpret``) always
   runs the kernel; otherwise kernels only run on a real TPU backend,
   and a kernel registered ``default=False`` (e.g. layer norm, where
   XLA's own fusion measured faster end-to-end) additionally needs an
   explicit opt-in from one of the env layers above.

``APEX_TPU_KERNELS=0`` therefore reproduces the plain-XLA lowering
bit-identically everywhere — the jnp oracle is not a degraded path, it
is the reference the kernels are tested against.

Telemetry: :meth:`KernelRegistry.dispatch` records per-kernel dispatch
counters and a ``kernel`` JSONL event, but ONLY when the process-wide
metrics registry is enabled — disabled-registry dispatches touch
nothing (the PR-2 zero-overhead-off contract).
"""

import os
import warnings

import jax

_MASTER_ENV = "APEX_TPU_KERNELS"
_GLOBAL_KILL = "APEX_TPU_DISABLE_PALLAS"

# legacy aliases that warn when consulted (once per process, per var)
_DEPRECATED_ENVS = frozenset({"APEX_TPU_COMPRESS_PALLAS"})
_warned_legacy = set()


def _warn_legacy(legacy_env, env_var):
    if legacy_env in _DEPRECATED_ENVS and legacy_env not in _warned_legacy:
        _warned_legacy.add(legacy_env)
        warnings.warn(
            f"{legacy_env} is deprecated; use {env_var} (per-kernel) or "
            f"{_MASTER_ENV} (all kernels) instead",
            DeprecationWarning, stacklevel=3)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


class PallasGate:
    """Per-kernel enable switch (the decision ladder in the module
    docstring). ``env_var=0`` opts out; interpreter mode (tests) wins
    over backend detection; otherwise TPU-only, and ``default=False``
    kernels need an explicit env opt-in even there."""

    def __init__(self, env_var: str, *, default: bool = True,
                 legacy_env=None):
        self.env_var = env_var
        self.default = default
        self.legacy_env = legacy_env
        self.interpret = False

    def force_interpret(self, on: bool):
        self.interpret = bool(on)

    def _env_vote(self):
        """The env-layer decision: True/False when some layer spoke,
        None when everything is unset (fall through to the default)."""
        if os.environ.get(_GLOBAL_KILL, "0") == "1":
            return False
        v = os.environ.get(self.env_var)
        if v is not None:
            return v != "0"
        if self.legacy_env is not None:
            lv = os.environ.get(self.legacy_env)
            if lv is not None:
                _warn_legacy(self.legacy_env, self.env_var)
                return lv != "0"
        master = os.environ.get(_MASTER_ENV)
        if master is not None:
            return master != "0"
        return None

    def enabled(self) -> bool:
        vote = self._env_vote()
        if vote is False:
            return False
        if self.interpret:
            return True
        if not _on_tpu():
            return False
        # on TPU, an unset env stack falls back to the registered
        # default; default-off kernels run only on an explicit opt-in
        return bool(vote) if vote is not None else self.default


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

    def register(self, name: str, *, default: bool = True,
                 legacy_env=None, env_var=None) -> PallasGate:
        """Idempotent: the first registration fixes the gate; later
        calls return it (so module reloads don't reset interpret
        state)."""
        gate = self._gates.get(name)
        if gate is None:
            env = env_var or "APEX_TPU_KERNEL_" + name.upper()
            gate = PallasGate(env, default=default, legacy_env=legacy_env)
            self._gates[name] = gate
        return gate

    def gate(self, name: str) -> PallasGate:
        return self._gates[name]

    def names(self):
        return sorted(self._gates)

    def enabled(self, name: str) -> bool:
        return self._gates[name].enabled()

    def force_interpret(self, on: bool, names=None):
        """Run kernels in interpreter mode regardless of backend (CPU
        tests). ``names=None`` flips every registered gate."""
        for n in (self._gates if names is None else names):
            self._gates[n].force_interpret(on)

    def dispatch(self, name: str, path: str, **fields):
        """Record one kernel dispatch (trace-time, from the wrapper):
        ``path`` is ``"pallas"``, ``"interpret"`` or ``"oracle"``.
        No-op when telemetry is disabled — zero overhead off."""
        from apex_tpu.telemetry.registry import get_registry

        reg = get_registry()
        if not reg.enabled:
            return
        reg.counter("kernels/dispatch").inc()
        reg.counter(f"kernels/{name}/{path}").inc()
        # flat per-(kernel, path) counter: lands in every summary's
        # ``counters`` dict, so bench JSONs prove which path actually
        # ran — a silent oracle fallback shows up as
        # ``kernels/dispatch/<name>_oracle`` instead of vanishing
        reg.counter(f"kernels/dispatch/{name}_{path}").inc()
        reg.event("kernel", "dispatch", kernel=name, path=path, **fields)


_REGISTRY = KernelRegistry()


def get_kernel_registry() -> KernelRegistry:
    return _REGISTRY


def kernel_gate(name: str, **kwargs) -> PallasGate:
    """Register-or-fetch the named kernel's gate on the process-wide
    registry — the one-liner kernel modules use at import time."""
    return _REGISTRY.register(name, **kwargs)


def dispatch_path(gate: PallasGate) -> str:
    """The telemetry label for a dispatch through ``gate``: which of
    the three code paths this call will take."""
    if not gate.enabled():
        return "oracle"
    return "interpret" if gate.interpret else "pallas"


def record_dispatch(name: str, gate: PallasGate, **fields):
    """Convenience: label the path and record it in one call; returns
    True when the Pallas kernel (compiled or interpreted) runs."""
    path = dispatch_path(gate)
    _REGISTRY.dispatch(name, path, **fields)
    return path != "oracle"
