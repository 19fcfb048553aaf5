"""Float-float arithmetic in float32, for device code that must round like
float64.

A value is a pair ``(hi, lo)`` of float32 arrays whose unevaluated sum
carries about 46 bits of significand (relative error ~1e-14).  The query
grid (`mapper.query_grid`) computes its sky coordinates this way and rounds
once at the end, so that on the device it gives the float64 TAN grid
rounded to float32, bit for bit, as a float32 evaluation cannot (one ulp of
RA at 37 deg is 0.035 px, and a bright star's wing moves by ~1% for it).

Two rules keep the error-free transforms error free under XLA:

* Every product that feeds a sum is exact.  `two_prod` splits each factor
  into two 12-bit halves by masking the low mantissa bits (not Veltkamp's
  multiply, which a fused multiply-add would spoil) and sums the four exact
  partial products, so a compiler that fuses a multiply into an add
  changes nothing.
* No error-free sum sees a literal constant: XLA folds ``(x + c) - c`` to
  ``x``.  Constants that enter `two_sum` come through `const`, an
  optimization barrier.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def const(c) -> jnp.ndarray:
    """A float32 scalar the compiler cannot fold into the sums it enters."""
    return jax.lax.optimization_barrier(jnp.float32(c))


def split_constant(c: float):
    """A float64 constant as a float-float pair of host float32 values."""
    hi = np.float32(c)
    return hi, np.float32(c - np.float64(hi))


def two_sum(a, b):
    """``a + b`` as ``(s, e)`` with ``s + e`` exact (Knuth)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _renorm(a, b):
    """``(s, e)`` with ``s + e == a + b`` for ``|a| >= |b|`` (Dekker)."""
    s = a + b
    return s, b - (s - a)


def _halves(a):
    """``a`` as ``hi + lo``, each with at most 12 significant bits."""
    bits = jax.lax.bitcast_convert_type(jnp.asarray(a, jnp.float32), jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFFF000), jnp.float32)
    return hi, a - hi


def two_prod(a, b):
    """``a * b`` as ``(p, e)``, to ~2^-46 of the product, from four exact
    partial products."""
    ah, al = _halves(a)
    bh, bl = _halves(b)
    m, e1 = two_sum(ah * bl, al * bh)
    p, e2 = two_sum(ah * bh, m)
    return p, e2 + (e1 + al * bl)


def add(x, y):
    s, e = two_sum(x[0], y[0])
    return _renorm(s, e + (x[1] + y[1]))


def neg(x):
    return -x[0], -x[1]


def mul(x, y):
    p, e = two_prod(x[0], y[0])
    return _renorm(p, e + (x[0] * y[1] + x[1] * y[0]))


def div(x, y):
    """``x / y``: a float32 quotient, corrected by its exact residual, so an
    approximate hardware division costs no precision."""
    q = x[0] / y[0]
    r = add(x, neg(mul((q, jnp.zeros_like(q)), y)))
    return _renorm(q, (r[0] + r[1]) / y[0])


ATAN_SERIES_MAX = 0.25


def atan(v):
    """``atan v`` of a float-float ``|v| <= ATAN_SERIES_MAX``: ``v`` plus a
    float32 tail ``v^3 P(v^2)`` (the series to ``v^19``), which is small
    enough that float32 carries it to the float-float's precision."""
    z = v[0] * v[0]
    p = jnp.float32(0.0)
    for k in range(9, 0, -1):
        p = np.float32((-1) ** k / (2 * k + 1)) + z * p
    return add(v, (v[0] * z * p, jnp.zeros_like(z)))


def round_sum(a, x):
    """float32 ``a + x`` (``a`` float32, ``x`` float-float), rounded once."""
    s, e = two_sum(a, x[0])
    return s + (e + x[1])
