"""f32 ``exp`` and ``log`` accurate to about one ulp on every platform.

BP's sum-product math is log-sum-exp all the way down, and its stopping
rule compares message changes against ``eps`` (1e-5 for LBP in this
repository's smoke and multi-device tests).
The TPU v5e's own f32 transcendentals are too coarse for that: over a
million inputs, ``jnp.log`` was off by up to 1.1e-4 (4,000 ulp) on [1, 48]
and ``jnp.exp`` by up to 70 ulp on [-30, 0], against about 1 ulp on the CPU.
One message update then carries ~1e-4 of noise, LBP on a 200x200 Ising grid
stalls with residuals between 5e-5 and 2e-4 instead of reaching 1e-5, and a
12-vertex chain, where BP is exact, lands 3.5e-5 off its brute-force
marginals instead of 1.3e-7.

The two functions here are the Cephes single-precision algorithms: a
Cody-Waite range reduction and a short polynomial, with the power of two
applied (``exp``) or taken out (``log``) through the exponent bits. They use
only adds, multiplies, floor, compares and bit casts, which are exact f32
operations on the TPU's vector unit, so the same code is accurate in XLA
fusions and inside the Pallas kernels, and the CPU runs the same program.

Domains: ``exp`` returns 0 below log(FLT_MIN) (about -87.3, so NEG_INF
inputs give exactly 0) and saturates at exp(88); ``log`` takes x > 0 and
treats anything below FLT_MIN, zero included, as FLT_MIN (about -87.3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_LOG2E = 1.44269504088896341
# ln 2 split so that n * _LN2_HI is exact for |n| < 2**15.
_LN2_HI = 0.693359375
_LN2_LO = -2.12194440e-4
_EXP_LO = -87.33654475     # log(FLT_MIN): below it exp flushes to 0
_EXP_HI = 88.0             # 2**127 * sqrt(2) still fits in f32
_FLT_MIN = 1.17549435e-38
_SQRT_HALF = 0.707106781186547524


def exp(x: jax.Array) -> jax.Array:
    """e**x (Cephes ``expf``), computed in f32 and returned in x's
    floating dtype."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    xc = jnp.clip(x, _EXP_LO, _EXP_HI)
    n = jnp.floor(xc * _LOG2E + 0.5)
    r = xc - n * _LN2_HI - n * _LN2_LO
    p = 1.9875691500e-4
    for c in (1.3981999507e-3, 8.3334519073e-3, 4.1665795894e-2,
              1.6666665459e-1, 5.0000001201e-1):
        p = p * r + c
    y = p * r * r + r + 1.0
    two_n = jax.lax.bitcast_convert_type(
        jnp.left_shift(n.astype(jnp.int32) + 127, 23), jnp.float32)
    return jnp.where(x < _EXP_LO, 0.0, y * two_n).astype(dtype)


def log(x: jax.Array) -> jax.Array:
    """Natural log (Cephes ``logf``) for x > 0, computed in f32 and
    returned in x's floating dtype."""
    dtype = x.dtype
    x = jnp.maximum(x.astype(jnp.float32), _FLT_MIN)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    # x = m * 2**e with m in [0.5, 1).
    e = jnp.right_shift(bits, 23) - 126
    m = jax.lax.bitcast_convert_type(
        jnp.bitwise_or(jnp.bitwise_and(bits, 0x007FFFFF), 0x3F000000),
        jnp.float32)
    # Re-centre on 1: f = m' - 1 with m' in [sqrt(1/2), sqrt(2)).
    low = m < _SQRT_HALF
    e = jnp.where(low, e - 1, e).astype(jnp.float32)
    f = jnp.where(low, m + m - 1.0, m - 1.0)
    z = f * f
    p = 7.0376836292e-2
    for c in (-1.1514610310e-1, 1.1676998740e-1, -1.2420140846e-1,
              1.4249322787e-1, -1.6668057665e-1, 2.0000714765e-1,
              -2.4999993993e-1, 3.3333331174e-1):
        p = p * f + c
    y = p * f * z + e * _LN2_LO - 0.5 * z
    return (f + y + e * _LN2_HI).astype(dtype)


__all__ = ["exp", "log"]
