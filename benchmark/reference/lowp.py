"""The control's arithmetic: the precision below the configuration's.

Both configurations state bfloat16, so the step below is fp8: every
operand of every matrix product is rounded to e4m3 (4 exponent bits, 3
mantissa bits) with a per-tensor scale (absmax -> 240, the format's
largest finite value; the usual fp8 recipe). The reference run with this
in place of the identity stands where the program stands in the
comparison, and has to come out as not correct.

The rounding is ``lax.reduce_precision``, which the compiler has to keep;
a convert to a narrow type and back is dropped on the TPU as excess
precision. What would be subnormal in e4m3 (under absmax / 15360) becomes
zero.
"""

import jax
import jax.numpy as jnp

E4M3_MAX = 240.0


def _straight_through(x, rounded):
    """``rounded`` forward, identity backward: the products' gradients
    are taken at the rounded operands, and the rounding itself (whose
    cast would push the cotangent through fp8 too) passes them on."""
    return x + jax.lax.stop_gradient(rounded - x)


def fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    rounded = jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                       mantissa_bits=3)
    return _straight_through(x, rounded * scale)


def bf16(x):
    """One step *above* the control: what the configuration states. Used
    by tests to show that the stated precision passes."""
    return _straight_through(
        x, jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7))
