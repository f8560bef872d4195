"""Numerics that give the same bits on every backend.

The wire bytes a peer packs must not depend on the device that packed
them: a decoder regenerates what its peers drew, the golden matrix pins
the bytes, and a TPU peer must agree with a CPU peer.  XLA leaves four
things to the backend that this module pins down:

* :func:`div_rn` — f32 division by a traced divisor.  XLA's TPU backend
  lowers it to a reciprocal sequence that is not always the IEEE
  round-to-nearest-even quotient: on a TPU v5e, 36 % of 2^20 quotients
  ``(x − min)/(max − min)`` differed from the CPU backend's by an ulp.
* :func:`fma_rn` — ``a·b + c``.  XLA:CPU contracts a multiply and the add
  that consumes it into one FMA (one rounding) where the product is
  elementwise; the TPU backend rounds the product first.  On a v5e the
  per-coordinate ternary pass-through value differed from the CPU's in
  135 bf16 wire values of one of 8 peers at d = 2^20.
* :func:`ordered_sum` / :func:`ordered_mean` — f32 sums.  A ``reduce``
  adds in the backend's own order; XLA:CPU's order is reproduced here
  with elementwise adds, which every backend rounds the same way.
* :func:`count_prefix` — an integer prefix count whose compile time on
  TPU does not grow with its length (same integers as ``jnp.cumsum``).

On XLA:CPU each helper returns exactly what the plain jnp op returns, so
CPU bytes — and the golden wire matrix — are unchanged.  The ops are
plain jnp/lax, usable inside Pallas kernel bodies where noted.

How :func:`div_rn` works: for a positive normal quotient q with 24-bit
mantissa Mq and biased exponent eq, the midpoints to its neighbours are
(4·Mq ± 2)·2^(eq−152) (4·Mq − 1 below a power of two).  The sign of
a − m·b for a midpoint m is the sign of the integer (ma·2^s − M·mb) with
s = ea − eb − eq + 152, where ma, mb are the mantissas of a and b.  While
q is within two ulps of a/b that integer is below 2^27 in magnitude, so
it can be evaluated modulo 2^32 in wrapping int32 arithmetic even though
the products have ~50 bits.  It uses integer arithmetic only (no float
multiply that a compiler could contract into an FMA).

How :func:`fma_rn` works: the 48-bit product of the two 24-bit mantissas
is formed exactly in int32 as two 24-bit limbs, each converted to an f32
(exact) and scaled by a power of two (exact), so a·b = uh + ul with no
rounding.  The one-rounding sum RN(uh + ul + c) then follows Boldo and
Melquiond's emulation by rounding to odd ("Emulation of FMA and correctly
rounded sums: proved algorithms using rounding to odd", IEEE TC 2008):
(ph, pl) = TwoSum(uh, ul); (th, tl) = TwoSum(c, ph); v = RO(tl + pl);
result = RN(th + v).  Every float multiply in it has an exact product,
so a compiler that contracts one into an FMA changes nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_MANT = 0x7FFFFF
_HIDDEN = 0x800000


def _fields(x):
    """(bits, biased exponent, 24-bit mantissa) of |x| as int32."""
    bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32)
    return bits, bits >> 23, (bits & _MANT) | _HIDDEN


def _correct(a, b, q):
    """One correction step: move q by at most one ulp towards RN(a/b)."""
    _, ea, ma = _fields(a)
    _, eb, mb = _fields(b)
    iq, eq, mq = _fields(q)
    s = ea - eb - eq + 152
    ok = ((ea >= 1) & (ea <= 254) & (eb >= 1) & (eb <= 254)
          & (eq >= 2) & (eq <= 253) & (s >= 0) & (s <= 31))
    shifted = jnp.left_shift(ma, jnp.where(ok, s, 0))

    def side(m):  # sign source of a − m·b in units of 2^(eq−152+eb−150)
        return shifted - m * mb  # wraps mod 2^32; |true value| < 2^27

    up = side(4 * mq + 2)
    down = side(jnp.where(mq == _HIDDEN, 4 * mq - 1, 4 * mq - 2))
    even = (iq & 1) == 0
    step = jnp.where(up > 0, 1,
           jnp.where(up == 0, jnp.where(even, 0, 1),
           jnp.where(down < 0, -1,
           jnp.where(down == 0, jnp.where(even, 0, -1), 0))))
    fixed = jax.lax.bitcast_convert_type(iq + step, jnp.float32)
    return jnp.where(ok, jnp.copysign(fixed, q), q)


def div_rn(a, b):
    """``a / b`` rounded to nearest even, identically on every backend.

    Elementwise with broadcasting, f32.  Zero, infinite, NaN and subnormal
    operands or quotients keep the backend's own result.  Two correction
    steps cover a backend quotient up to two ulps off.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    a, b = jnp.broadcast_arrays(a, b)
    q = a / b
    for _ in range(2):
        q = _correct(a, b, q)
    return q


def _pow2(k):
    """2^k as f32 for int32 ``k`` in [-126, 127] (built from its bits)."""
    return jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)


def _two_sum(a, b):
    """Knuth's TwoSum: s = RN(a + b) and the exact error a + b − s."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _add_round_odd(a, b):
    """a + b rounded to odd: the exact sum when representable, else the
    neighbour of RN(a + b) on the exact sum's side whose last bit is 1."""
    s, err = _two_sum(a, b)
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    # int32 bits + 1 moves |s| one ulp up for either sign, − 1 down
    away = (err > 0) == (s > 0)
    nudge = (err != 0) & ((bits & 1) == 0)
    bits = jnp.where(nudge, bits + jnp.where(away, 1, -1), bits)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def fma_rn(a, b, c):
    """``a·b + c`` with a single rounding to nearest even, identically on
    every backend: the bits XLA:CPU gives where it contracts the multiply
    into the add.

    Elementwise with broadcasting, f32.  Operands that are zero, infinite,
    NaN or subnormal, and exponents near either end of the range (a product
    or addend whose last bit would fall below 2^-126, or a result that
    could overflow), keep the backend's own ``a * b + c``; with a zero
    operand that is exact everywhere.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    c = jnp.asarray(c, jnp.float32)
    a, b, c = jnp.broadcast_arrays(a, b, c)
    _, ea, ma = _fields(a)
    _, eb, mb = _fields(b)
    _, ec, _ = _fields(c)
    # ma·mb = hi·2^24 + lo exactly, with 12-bit halves of each mantissa
    ah, al = ma >> 12, ma & 0xFFF
    bh, bl = mb >> 12, mb & 0xFFF
    mid = ah * bl + al * bh                      # < 2^25
    low = al * bl + ((mid & 0xFFF) << 12)        # < 2^25
    lo = low & 0xFFFFFF
    hi = ah * bh + (mid >> 12) + (low >> 24)     # < 2^24
    e = ea + eb - 300                            # a·b = (hi·2^24 + lo)·2^e
    ok = ((ea >= 1) & (ea <= 254) & (eb >= 1) & (eb <= 254)
          & (ec >= 24) & (ec <= 253) & (e >= -126) & (e <= 78))
    e = jnp.where(ok, e, 0)
    sign = jnp.where((a < 0) != (b < 0), -1.0, 1.0).astype(jnp.float32)
    uh = sign * hi.astype(jnp.float32) * _pow2(e + 24)
    ul = sign * lo.astype(jnp.float32) * _pow2(e)
    ph, pl = _two_sum(uh, ul)
    th, tl = _two_sum(c, ph)
    fused = th + _add_round_odd(tl, pl)
    return jnp.where(ok, fused, a * b + c)


PREFIX_GROUP = 128
_PREFIX_SCAN_MAX = 4096


def count_prefix(counts, bound: int = 1):
    """Inclusive int32 cumsum of a 1-D array of counts in [0, bound].

    The same integers as ``jnp.cumsum``, built to compile fast on TPU,
    where a long cumsum lowers to a reduce-window whose compile time grows
    with the length (37 s for 2^20 int32 on a v5e; a cumsum over the flat
    coordinates sits in every ternary pack and decode).  Groups of
    :data:`PREFIX_GROUP` are summed by one triangular f32 matmul — exact,
    every partial sum stays below 2^24 — and the group totals recurse;
    short vectors, or bounds that would leave f32's exact range, use
    ``jnp.cumsum`` directly.
    """
    c = jnp.asarray(counts).astype(jnp.int32)
    d = c.shape[0]
    g = PREFIX_GROUP
    if d <= _PREFIX_SCAN_MAX or bound * g >= 1 << 24:
        return jnp.cumsum(c)
    rows = jnp.pad(c, (0, (-d) % g)).reshape(-1, g).astype(jnp.float32)
    tri = jnp.triu(jnp.ones((g, g), jnp.float32))
    within = jax.lax.dot(rows, tri, precision=jax.lax.Precision.HIGHEST
                         ).astype(jnp.int32)
    totals = within[:, -1]
    before = count_prefix(totals, bound * g) - totals
    return (within + before[:, None]).reshape(-1)[:d]


# XLA:CPU rewrites a long reduce into reduce-windows of this many
# elements (TreeReductionRewriter), each summed left to right from 0.
_SUM_WINDOW = 32


def _sum_left_to_right(rows):
    """Sum of each row of an (m, w) f32 matrix, columns added in order."""
    acc = jnp.zeros(rows.shape[:1], jnp.float32)
    for j in range(rows.shape[1]):
        acc = acc + rows[:, j]
    return acc


def ordered_sum(x):
    """f32 sum of all elements of ``x`` in XLA:CPU's reduction order.

    Bit-identical to ``jnp.sum(x)`` on XLA:CPU and the same bits on any
    backend, because it is a fixed chain of elementwise adds.  XLA:CPU
    sums a vector longer than :data:`_SUM_WINDOW` in windows of that size,
    padding the vector with zeros split evenly before and after (the
    extra one after), and repeats on the window sums until at most
    :data:`_SUM_WINDOW` remain; every window, and the last vector, is
    added left to right starting from 0.  tests/test_numerics.py pins the
    equality against ``jnp.sum`` on XLA:CPU.
    """
    v = jnp.asarray(x, jnp.float32).reshape(-1)
    w = _SUM_WINDOW
    while v.shape[0] > w:
        m = -(-v.shape[0] // w)
        pad = m * w - v.shape[0]
        v = _sum_left_to_right(
            jnp.pad(v, (pad // 2, pad - pad // 2)).reshape(m, w))
    return _sum_left_to_right(v.reshape(1, -1))[0]


def ordered_mean(x):
    """f32 mean of all elements of ``x``: :func:`ordered_sum` times the f32
    reciprocal of the count, rounded on the host — what XLA:CPU makes of
    ``jnp.mean``'s division by a constant, so bit-identical to it there."""
    return ordered_sum(x) * (np.float32(1) / np.float32(max(1, x.size)))
