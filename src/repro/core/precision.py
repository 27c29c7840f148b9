"""The simulator's one precision policy.

Every hot path (the capture forward's integer bit counts, the batched
greedy, the fused DSE eval, the virtual-time scan and the fleet sketches)
runs its jax programs in 64-bit so that results match the numpy reference
engines bit for bit, or within the contract each module states.  They all
enter 64-bit mode through ``x64()`` so that the policy lives in one place.

Traced constants must be created INSIDE the ``x64()`` scope: a ``jnp``
array made outside it is downcast to 32 bits.

Exact virtual time.  A TPU has no float64 datapath: XLA emulates float64
there with pairs of float32, which rounds adds, multiplies and even the
values themselves away from IEEE float64.  The virtual-time engines promise
completion times bit-identical to the event engine's float64 arithmetic,
so on the device they carry every time as its IEEE bit pattern in an int64
(``to_bits`` / ``from_bits`` on the host) and add with ``add``, an IEEE
round-to-nearest-even float64 add written in int64 integer ops, which are
exact on every backend.  For non-negative doubles (and +-inf) the bit
patterns order as the values do, so ``max``, ``min``, ``where`` and sorts
work on them unchanged.  ``add``, ``sub``, ``value``, ``inf`` and ``ninf``
take either representation and dispatch on the dtype, so one kernel body
serves the float64 numpy reference and the int64 device path.
"""

from __future__ import annotations

import jax
import numpy as np

__all__ = ["add", "from_bits", "inf", "is_bits", "ninf", "sub", "to_bits", "value", "x64"]

_MANT = (1 << 52) - 1
_HIDDEN = 1 << 52
_INF = 0x7FF0000000000000
_NINF = -(1 << 52)  # 0xFFF0000000000000 read as int64


def x64():
    """Context manager: jax arrays default to float64 / int64 inside it."""
    return jax.enable_x64(True)


def to_bits(a) -> np.ndarray:
    """Host float64 array -> its IEEE bit patterns as int64."""
    return np.ascontiguousarray(a, dtype=np.float64).view(np.int64)


def from_bits(a) -> np.ndarray:
    """Host int64 bit patterns -> the float64 values."""
    return np.ascontiguousarray(a, dtype=np.int64).view(np.float64)


def is_bits(a) -> bool:
    """Whether ``a`` holds times as int64 bit patterns (else float64)."""
    dtype = getattr(a, "dtype", None)
    return dtype is not None and np.issubdtype(dtype, np.integer)


def inf(a):
    """+inf in ``a``'s representation."""
    return _INF if is_bits(a) else np.inf


def ninf(a):
    """-inf in ``a``'s representation."""
    return _NINF if is_bits(a) else -np.inf


def _unpack(xp, a):
    """(biased exponent, significand) of non-negative doubles; subnormals
    get exponent 1 and no hidden bit, as the encoding defines them."""
    e = a >> 52
    m = a & _MANT
    return xp.maximum(e, 1), xp.where(e > 0, m | _HIDDEN, m)


def _shr_sticky(xp, m, d):
    """``m >> d`` with any shifted-out bit ORed into bit 0 (``d >= 0``)."""
    d = xp.minimum(d, 62)
    s = m >> d
    return s | ((s << d) != m).astype(m.dtype)


def _round_pack(xp, e, m):
    """Round a significand carrying 3 extra low bits (guard, round, sticky)
    to nearest even and pack it with biased exponent ``e``."""
    low = m & 7
    m = m >> 3
    m = m + ((low > 4) | ((low == 4) & ((m & 1) == 1))).astype(m.dtype)
    carry = m >> 53  # rounding overflowed into a new binade
    m = m >> carry
    e = xp.where(m >= _HIDDEN, e + carry, 0)
    return xp.where(e >= 0x7FF, _INF, (e << 52) | (m & _MANT))


def add(xp, a, b):
    """``a + b`` for non-negative times: IEEE float64 on float arrays,
    the same result on int64 bit patterns."""
    if not is_bits(a):
        return a + b
    big, small = xp.maximum(a, b), xp.minimum(a, b)
    eb, mb = _unpack(xp, big)
    es, ms = _unpack(xp, small)
    m = (mb << 3) + _shr_sticky(xp, ms << 3, eb - es)
    carry = m >> 56
    m = (m >> carry) | (m & carry)
    return xp.where(big >= _INF, big, _round_pack(xp, eb + carry, m))


def sub(xp, a, b):
    """``a - b`` for ``a >= b >= 0``, ``b`` finite: IEEE float64 on float
    arrays, the same result on int64 bit patterns."""
    if not is_bits(a):
        return a - b
    ea, ma = _unpack(xp, a)
    eb, mb = _unpack(xp, b)
    m = (ma << 3) - _shr_sticky(xp, mb << 3, ea - eb)
    e = ea
    # normalize: shift the leading one up to bit 55, or stop at the
    # subnormal exponent (binary search over the shift)
    for k in (32, 16, 8, 4, 2, 1):
        ok = ((m >> (56 - k)) == 0) & (e > k)
        m = xp.where(ok, m << k, m)
        e = xp.where(ok, e - k, e)
    return xp.where(a >= _INF, a, _round_pack(xp, e, m))


def value(xp, a):
    """The float64 value of ``a`` (non-negative or +inf).  From bit
    patterns it is built as significand x exact powers of two: exact for
    normal values where float64 is IEEE (CPU), rounded by the float64
    emulation on a TPU."""
    if not is_bits(a):
        return a
    e = a >> 52
    m = a & _MANT
    x = xp.where(e > 0, m | _HIDDEN, m).astype(xp.float64) * 2.0**-52
    p = xp.where(e > 0, e - 1023, -1022)
    q = xp.abs(p)
    for i in range(9, -1, -1):
        step = xp.where(p < 0, 2.0 ** -(1 << i), 2.0 ** (1 << i))
        x = xp.where(((q >> i) & 1) == 1, x * step, x)
    return xp.where(e >= 0x7FF, xp.inf, x)
